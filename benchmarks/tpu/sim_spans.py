"""What the readers of ``repro.sim``'s own spans, counters and scopes share.

Every ``simulate_batch`` call returns its spans (``sim.upload``,
``sim.cycle_loop``, ``sim.pullback``, ``sim.check``, ... under the root
``sim.simulate_batch``) on its ``BatchResult``, stamped with
``time.time_ns()``; the traffic keeps one result per window call in
``run.results``.  The cycle loop's ops carry the loop's named scopes
(``sim_cycle_loop/.../execute/presence``, ``.../commit``) in the
``op_name`` metadata of the compiled program, which the trace's op events
do not carry, so the map from op to scope is read from the compiled text
of the cell's runner.

A program without spans (one from before they existed) gives ``None``
here, never an error.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: the program's root span of one call, and the harness's span around it
ROOT = "sim.simulate_batch"
HARNESS_SPAN = "simulate_batch"
#: the cycle loop's program (``jit_run``) and the scopes inside it
MODULE = "run"
LOOP_SCOPE = "sim_cycle_loop"
PHASES = ("execute", "commit")
STEPS = ("operand_read", "presence", "alu", "value_write")   # of execute
#: label of an op whose metadata names no scope of the loop
NO_SCOPE = "none"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\bmetadata=\{'
                    r'[^}]*?op_name="([^"]*)"', re.M)
_SCOPES: Dict[tuple, Dict[str, str]] = {}


def has_spans(run) -> bool:
    """Every call of the window returned its spans."""
    res = getattr(run, "results", None)
    return bool(res) and all(getattr(r, "spans", None) for r in res)


def _ms(sp) -> float:
    return (sp.end_ns - sp.start_ns) / 1e6


def span_ms(run, name: str) -> Optional[float]:
    """Mean milliseconds per call of the program's span ``name``."""
    if not has_spans(run):
        return None
    return sum(_ms(sp) for r in run.results for sp in r.spans
               if sp.name == name) / len(run.results)


def _covered(lo: int, hi: int, intervals) -> int:
    """Length of the union of ``intervals`` inside ``[lo, hi)``."""
    total, reach = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def idle_unspanned_ms(tr, run) -> Optional[float]:
    """Mean milliseconds per call in which the chip was idle inside the
    harness's span and no phase span of the program ran.  Each call's
    spans are shifted onto the trace's clock so that its root starts
    where the harness's span of the same call starts; calls pair in
    order and their counts must agree."""
    calls = tr.span_calls(HARNESS_SPAN)
    if not has_spans(run) or len(calls) != len(run.results):
        return None
    busy = tr.busy_intervals(tr.devices[0])
    idle = 0
    for hs, res in zip(calls, run.results):
        root = next((sp for sp in res.spans if sp.parent is None), None)
        if root is None or root.name != ROOT:
            return None
        shift = hs.start - root.start_ns
        phases = [(sp.start_ns + shift, sp.end_ns + shift)
                  for sp in res.spans if sp.parent == ROOT]
        idle += hs.end - hs.start - _covered(hs.start, hs.end,
                                              busy + phases)
    return idle / len(calls) / 1e6


def scope_of(op_name: str) -> str:
    """``jit(run)/sim_cycle_loop/while/body/closed_call/execute/presence/
    reduce_or`` -> ``execute/presence``; an op of the loop outside both
    phases -> ``sim_cycle_loop``; an op outside the loop -> ``none``."""
    parts = op_name.split("/")
    if LOOP_SCOPE not in parts:
        return NO_SCOPE
    rest = parts[parts.index(LOOP_SCOPE) + 1:]
    for i, p in enumerate(rest):
        if p in PHASES:
            inner = [q for q in rest[i + 1:-1] if q in STEPS]
            return f"{p}/{inner[0]}" if inner else p
    return LOOP_SCOPE


def op_scopes(run) -> Dict[str, str]:
    """``op name -> op_name metadata`` of the cell's cycle-loop program,
    from its compiled text (a compile-cache load after the window)."""
    from repro.sim import step

    pb = run.prepared.packed
    key = (pb.hmax, pb.iterations, pb.shape, run.backend == "pallas")
    if key not in _SCOPES:
        runner = step._jit_runner(*key)
        text = runner.lower(*step.device_args(pb)).compile().as_text()
        _SCOPES[key] = dict(_INSTR.findall(text))
    return _SCOPES[key]


def _ops_inside(ops, calls) -> List[Tuple[str, int, int]]:
    calls = sorted(calls)
    starts = [s for s, _ in calls]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < calls[i][1]:
            out.append(op)
    return out


def phase_ms(tr, run) -> Optional[Dict[str, float]]:
    """Device milliseconds per call of the cycle loop's ops by scope
    (:func:`scope_of` of each op's metadata; an op the compiled text does
    not name counts as ``none``), each op by its self time as
    ``Trace.top_ops`` counts it."""
    if not has_spans(run):
        return None
    calls = tr.module_calls(MODULE)
    if not calls:
        return None
    ops = _ops_inside(tr.devices[0].ops, calls)
    loop = type(tr)(devices=[type(tr.devices[0])(modules=[], ops=ops)],
                    spans=[], window=tr.window)
    scopes = op_scopes(run)
    out: Dict[str, float] = defaultdict(float)
    for name, secs in loop.top_ops(len(ops)):
        label = scope_of(scopes[name]) if name in scopes else NO_SCOPE
        out[label] += secs * 1e3 / len(calls)
    return dict(out)


def phase_total_ms(tr, run, phase: str) -> Optional[float]:
    """Device milliseconds per call of every op under ``phase``."""
    split = phase_ms(tr, run)
    if split is None:
        return None
    return sum(ms for label, ms in split.items()
               if label.split("/")[0] == phase)
