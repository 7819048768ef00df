"""Plain scalar reference for a mapped CGRA configuration.

Reads one mapping record as the pool stores it (the ``mappings`` entries
of a ``CompileResult`` JSON: the DFG's nodes and edges, ``ii``, ``time``
and ``routes``) and executes it cycle by cycle: each node fires at
``time + k * ii`` for iteration ``k``, reads its operands from the last
routing resource of each in-edge's route, and every route step carries
its producer's value forward.  The DFG's own semantics (the interpreter
that gives every node's value per iteration) is evaluated alongside, and
the mapping is accepted only when every value the fabric produces equals
it.  Nothing here imports the program: the operator table, the leaf
values and the operand order are written out from the paper's DFG
semantics as the pool's records encode them.

``round_to`` rounds every produced value to a narrower float type (the
precision control); ``None`` keeps float64.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

#: the leaf value node ``n`` presents in iteration ``it`` (no external
#: inputs are bound in a sweep)
def leaf_value(n: int, it: int) -> float:
    return float(it + 1 + n % 5)


def apply_op(op: str, a: float, b: float, c: float, leaf: float) -> float:
    if op in ("input", "const", "load"):
        return leaf
    if op in ("store", "output"):
        return a
    table = {
        "add": lambda: a + b,
        "sub": lambda: a - b,
        "mul": lambda: a * b,
        "mac": lambda: a * b + c,
        "shl": lambda: a * 2.0,
        "shr": lambda: a / 2.0,
        "and": lambda: float(int(a) & int(b)),
        "or": lambda: float(int(a) | int(b)),
        "xor": lambda: float(int(a) ^ int(b)),
        "not": lambda: float(~int(a) & 0xFFFF),
        "min": lambda: min(a, b),
        "max": lambda: max(a, b),
        "abs": lambda: abs(a),
        "cmp": lambda: float(a > b),
        "select": lambda: b if a != 0.0 else c,
    }
    return table[op]()


def rounder(round_to: Optional[str]) -> Callable[[float], float]:
    if round_to is None:
        return float
    import ml_dtypes

    dt = np.dtype(getattr(ml_dtypes, round_to, None) or round_to)
    return lambda x: float(np.asarray(x, np.float64).astype(dt))


def _topo(nodes: List[int], edges) -> List[int]:
    """Order nodes so every intra-iteration (distance 0) producer comes
    first; ties by node id."""
    preds = {n: set() for n in nodes}
    for src, dst, dist, _ in edges:
        if dist == 0:
            preds[dst].add(src)
    order, done = [], set()
    while len(order) < len(nodes):
        ready = [n for n in nodes if n not in done and preds[n] <= done]
        if not ready:
            raise ValueError("cycle through distance-0 edges")
        n = min(ready)
        order.append(n)
        done.add(n)
    return order


def interpret(record: dict, iterations: int,
              rnd=float) -> Dict[int, List[float]]:
    """The DFG's value for every node and iteration (its semantics)."""
    nodes = {int(n): op for n, op, _ in record["dfg"]["nodes"]}
    edges = [tuple(int(x) for x in e) for e in record["dfg"]["edges"]]
    order = _topo(sorted(nodes), edges)
    ins: Dict[int, List[Tuple[int, int, int]]] = {n: [] for n in nodes}
    for src, dst, dist, operand in edges:
        ins[dst].append((operand, src, dist))
    hist: Dict[int, List[float]] = {n: [] for n in nodes}
    for it in range(iterations):
        vals: Dict[int, float] = {}
        for n in order:
            ops = []
            for operand, src, dist in ins[n]:
                if dist == 0:
                    ops.append((operand, vals[src]))
                else:
                    past = it - dist
                    ops.append((operand, hist[src][past] if past >= 0
                                else 0.0))
            ops.sort()
            abc = [v for _, v in ops] + [0.0] * (3 - len(ops))
            vals[n] = rnd(apply_op(nodes[n], abc[0], abc[1], abc[2],
                                   leaf_value(n, it)))
        for n in order:
            hist[n].append(vals[n])
    return hist


def simulate(record: dict, iterations: int, round_to: Optional[str] = None,
             tol: Tuple[float, float] = (1e-6, 1e-6)):
    """Execute the mapping; returns ``(ok, values, reason)`` where
    ``values`` maps ``(node, iteration)`` to the value the fabric
    produced (``None`` when a read found no value)."""
    rnd = rounder(round_to)
    nodes = {int(n): op for n, op, _ in record["dfg"]["nodes"]}
    edges = [tuple(int(x) for x in e) for e in record["dfg"]["edges"]]
    ii = int(record["ii"])
    time = {int(n): int(t) for n, t in record["time"].items()}
    routes = {int(i): [(int(r), int(t)) for r, t in path]
              for i, path in record["routes"].items()}
    want = interpret(record, iterations)
    # route step (rid, offset from the producer's issue) per edge
    steps = {i: [(rid, t - time[edges[i][0]]) for rid, t in path]
             for i, path in routes.items()}
    horizon = int(record["makespan"]) + ii * iterations + 2
    val: Dict[Tuple[int, int], float] = {}
    held: Dict[Tuple[int, int, int], float] = {}   # (rid, net, iter)
    ins: Dict[int, List[Tuple[int, int, int, int]]] = {n: [] for n in nodes}
    for i, (src, dst, dist, operand) in enumerate(edges):
        ins[dst].append((operand, src, dist, i))
    for t in range(horizon):
        fired = {}
        for n, tn in time.items():
            if t < tn or (t - tn) % ii:
                continue
            it = (t - tn) // ii
            if it >= iterations:
                continue
            ops = []
            for operand, src, dist, i in ins[n]:
                if nodes[src] in ("const", "input"):
                    ops.append((operand, want[src][it]))
                    continue
                if it - dist < 0:
                    ops.append((operand, 0.0))
                    continue
                if not routes.get(i):
                    return False, None, f"edge {i} into node {n} unrouted"
                rid = routes[i][-1][0]
                v = held.get((rid, src, it - dist))
                if v is None:
                    return False, None, (
                        f"cycle {t}: node {n} iteration {it} finds no value "
                        f"of node {src} on resource {rid}")
                ops.append((operand, v))
            ops.sort()
            abc = [v for _, v in ops] + [0.0] * (3 - len(ops))
            leaf = (want[n][it] if nodes[n] in ("const", "input", "load")
                    else 0.0)
            fired[(n, it)] = rnd(apply_op(nodes[n], abc[0], abc[1], abc[2],
                                          leaf))
        val.update(fired)
        moved = {}
        for i, path in steps.items():
            src = edges[i][0]
            for rid, off in path:
                k, rem = divmod(t + 1 - (time[src] + off), ii)
                if rem or not 0 <= k < iterations or (src, k) not in val:
                    continue
                moved[(rid, src, k)] = val[(src, k)]
        held.update(moved)
    atol, rtol = tol
    for n in time:
        if nodes[n] in ("const", "input"):
            continue
        for it in range(iterations):
            got = val.get((n, it))
            if got is None:
                return False, val, f"node {n} iteration {it}: no value"
            if abs(got - want[n][it]) > atol + rtol * abs(want[n][it]):
                return False, val, (f"node {n} iteration {it}: got {got}, "
                                    f"want {want[n][it]}")
    return True, val, None


def value_gap(got: Dict[Tuple[int, int], float],
              want: Dict[Tuple[int, int], float]) -> Tuple[float, int]:
    """``(widest relative gap, keys missing on either side)``: the gap of
    a value is ``|got - want| / max(|want|, 1)``."""
    missing = len(set(got) ^ set(want))
    gap = 0.0
    for key, w in want.items():
        g = got.get(key)
        if g is not None:
            gap = max(gap, abs(g - w) / max(abs(w), 1.0))
    return gap, missing
