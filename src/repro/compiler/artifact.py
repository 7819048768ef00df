"""Serializable mapping artifacts.

A :class:`CompileResult` is the JSON-round-trippable output of
:func:`repro.compiler.compile`: the headline numbers (II, cycles, makespan),
per-stage timings, motif-cover statistics, the **full** placement/routing
mapping (including the DFG it maps, so segments produced by the spatial
partitioner round-trip too), and arch + mapper + seed provenance.

Because the mapping itself is stored, a loaded artifact can be re-verified
with :meth:`CompileResult.simulate` — the cycle-accurate simulator replays
the configuration against the DFG oracle — **without re-running place &
route**.  This is what lets a results cache / serving tier hand out mappings
and still prove them correct on the consumer side.

Schema (``repro.compiler/artifact@5``; ``@1``–``@4`` artifacts still load —
``route_cache``, the place/route/negotiate timing keys, the uniform
per-pass stats, the ``degraded`` provenance block, and the
``compiled_sim`` forms are simply absent)::

    {
      "schema":   "repro.compiler/artifact@5",
      "workload": {"name", "unroll", "iterations", "domain"}
                  | {"dfg_name", "iterations", "dfg_sha256"},  # raw-DFG input
      "arch":     "plaid2x2",          # registered arch name
      "mapper":   "hierarchical",      # registered mapper name
      "seed":     0,
      "budget":   null | int,          # SA/negotiation step budget override
      "ii":       int | null,          # null = mapper found no mapping
      "cycles":   int | null,
      "makespan": int | null,
      "timings":  {"frontend": s, "pnr": s, "verify": s, "total": s,
                   "place": s, "route": s, "negotiate": s},  # 3-way P&R split
      "route_cache": {"hits_exact", "hits_scoped", "misses", "evictions",
                      "hit_rate"} | null,  # cross-move route memoization
      "pass_stats": [{"name", "wall_s", "calls", ...}] | null,
                                         # repro.mapping per-pass breakdown
      "motifs":   {"n_units", "fanout", "fanin", "unicast", "single"} | null,
      "mappings": [{"dfg": DFG.to_json(), "ii", "place", "time", "routes",
                    "makespan"}],      # one per segment (spatial) else one
      "spatial":  {"segments", "extra_mem_ops", "analytic"} | null,
      "compiled_sim": null | {         # repro.sim lowered forms (PR 8):
          "iterations": int,           #   ref-oracle trip count lowered for
          "mappings_sha256": str,      #   binds forms to `mappings` content
          "forms": [CompiledSim.to_json() | null]},  # null = unlowerable
      "verified": true | false | null, # null = verification not requested
      "degraded": null | {             # graceful-degradation provenance:
          "requested_mapper": str,     #   the mapper the caller asked for
          "fallback": str,             #   the mapper that actually ran
          "reason": "timeout" | "infeasible",
          "deadline_s": s, "elapsed_s": s, "where": str},  # timeout leg only
      "provenance": {"created_utc", "repro_version"}
    }

A non-null ``degraded`` block means ``mapper`` names the **fallback** that
produced the stored mapping, not the mapper the caller requested; degraded
artifacts are never inserted into the artifact store (their compile key
names the requested mapper).

``place``/``time``/``routes`` keys are node / edge indices (stringified by
JSON; restored to ``int`` on load).
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

ARTIFACT_SCHEMA = "repro.compiler/artifact@5"
#: schemas ``load()`` accepts; @1 predates the placement engine (PR 3) and
#: simply lacks route_cache / the per-stage P&R timing keys, @2 predates
#: the repro.mapping pass pipeline (PR 5) and lacks the per-pass stats,
#: @3 predates graceful degradation (PR 6) and lacks the degraded block,
#: @4 predates the serving farm (PR 8) and lacks the compiled_sim forms
SUPPORTED_SCHEMAS = ("repro.compiler/artifact@1", "repro.compiler/artifact@2",
                     "repro.compiler/artifact@3", "repro.compiler/artifact@4",
                     ARTIFACT_SCHEMA)
# 0.4.0: mapper decomposition into repro.mapping + pathfinder negotiation
# default flipped to "selective" (a mapper-behavior change: store keys must
# namespace away from 0.3.x artifacts)
REPRO_VERSION = "0.4.0"


def mapping_to_record(mapping) -> Dict[str, object]:
    """Serialize a :class:`~repro.mapping.Mapping` (with its DFG)."""
    return {
        "dfg": mapping.dfg.to_json(),
        "ii": mapping.ii,
        "makespan": mapping.makespan,
        "place": {int(n): int(fu) for n, fu in mapping.place.items()},
        "time": {int(n): int(t) for n, t in mapping.time.items()},
        "routes": {
            int(idx): [[int(rid), int(t)] for rid, t in path]
            for idx, path in mapping.routes.items()
        },
    }


def normalize_record(rec: Dict[str, object]) -> Dict[str, object]:
    """Coerce a JSON-decoded mapping record back to canonical in-memory
    form (string keys -> ints, route steps as 2-lists) — the single place
    that knows the record's key/value types; shared by ``from_json`` and
    ``mapping_from_record`` so a load -> to_json round-trip is
    value-identical to :func:`mapping_to_record` output.

    ``ii``/``makespan`` may be ``null`` (the mapper found no mapping or an
    analytic spatial segment): the record still loads — only
    :meth:`CompileResult.simulate` refuses to run on it."""
    ii = rec.get("ii")
    makespan = rec.get("makespan")
    return {
        "dfg": rec["dfg"],
        "ii": None if ii is None else int(ii),
        "makespan": None if makespan is None else int(makespan),
        "place": {int(n): int(fu) for n, fu in rec["place"].items()},
        "time": {int(n): int(t) for n, t in rec["time"].items()},
        "routes": {
            int(idx): [[int(rid), int(t)] for rid, t in path]
            for idx, path in rec["routes"].items()
        },
    }


def mapping_from_record(rec: Dict[str, object], arch_name: str):
    """Rebuild a validated :class:`~repro.mapping.Mapping` from a
    record — no place & route runs; ``Mapping.validate()`` re-checks every
    structural invariant (placement legality, route presence/timing,
    modulo-slot capacity) before the mapping is handed out."""
    from repro.core.arch import make_arch
    from repro.core.dfg import DFG
    from repro.mapping import Mapping

    rec = normalize_record(rec)
    if rec["ii"] is None:
        raise ValueError(
            "mapping record has ii=null (no mapping found); nothing to "
            "rebuild"
        )
    dfg = DFG.from_json(rec["dfg"])
    m = Mapping(make_arch(arch_name), dfg, rec["ii"])
    m.place = dict(rec["place"])
    m.time = dict(rec["time"])
    for idx, path in rec["routes"].items():
        m.set_route(idx, [(rid, t) for rid, t in path])
    m.validate()
    return m


@dataclass
class CompileResult:
    """See module docstring for the on-disk schema."""

    arch: str
    mapper: str
    seed: int
    budget: Optional[int] = None
    workload: Dict[str, object] = field(default_factory=dict)
    ii: Optional[int] = None
    cycles: Optional[int] = None
    makespan: Optional[int] = None
    timings: Dict[str, float] = field(default_factory=dict)
    motifs: Optional[Dict[str, int]] = None
    mappings: List[Dict[str, object]] = field(default_factory=list)
    spatial: Optional[Dict[str, object]] = None
    #: lowered ``repro.sim`` forms of ``mappings`` (see module docstring):
    #: lets a verify-on-load consumer (the serve daemon above all) skip the
    #: lowering + ``dfg.eval`` half of a batched verification.  Bound to
    #: the mapping content by ``mappings_sha256`` — a mismatch (edited or
    #: tampered mappings) falls back to fresh lowering, so the forms can
    #: never vouch for a mapping they were not lowered from.
    compiled_sim: Optional[Dict[str, object]] = None
    verified: Optional[bool] = None
    #: graceful-degradation provenance (see module docstring); non-null
    #: means ``mapper`` is the fallback that ran, not the requested mapper
    degraded: Optional[Dict[str, object]] = None
    provenance: Dict[str, object] = field(default_factory=dict)
    route_cache: Optional[Dict[str, object]] = None
    #: uniform per-pass breakdown from the repro.mapping pipeline: one row
    #: per pass ({"name", "wall_s", "calls", ...}), in execution order
    pass_stats: Optional[List[Dict[str, object]]] = None
    #: set by ``compile(..., store=...)`` only: True = served from the
    #: store without P&R, False = freshly compiled (and inserted), None =
    #: no store involved.  Runtime-only — never serialized, so a hit
    #: round-trips byte-identically to the artifact it was stored from.
    store_hit: Optional[bool] = field(default=None, compare=False)

    # -- identity ----------------------------------------------------------
    @property
    def key(self) -> str:
        """Workload key as used by the collect cache / golden files."""
        w = self.workload
        if "name" in w and "unroll" in w:
            return f"{w['name']}_u{w['unroll']}"
        return str(w.get("dfg_name", "dfg"))

    @property
    def mapped(self) -> bool:
        return bool(self.mappings) or (
            self.spatial is not None and self.spatial.get("analytic")
        )

    # -- JSON round-trip ---------------------------------------------------
    def to_json(self) -> Dict[str, object]:
        return {
            "schema": ARTIFACT_SCHEMA,
            "workload": self.workload,
            "arch": self.arch,
            "mapper": self.mapper,
            "seed": self.seed,
            "budget": self.budget,
            "ii": self.ii,
            "cycles": self.cycles,
            "makespan": self.makespan,
            "timings": self.timings,
            "motifs": self.motifs,
            "mappings": self.mappings,
            "spatial": self.spatial,
            "compiled_sim": self.compiled_sim,
            "verified": self.verified,
            "degraded": self.degraded,
            "provenance": self.provenance,
            "route_cache": self.route_cache,
            "pass_stats": self.pass_stats,
        }

    @classmethod
    def from_json(cls, data: Dict[str, object]) -> "CompileResult":
        schema = data.get("schema")
        if schema not in SUPPORTED_SCHEMAS:
            raise ValueError(
                f"unsupported artifact schema {schema!r} "
                f"(supported: {', '.join(SUPPORTED_SCHEMAS)})"
            )
        mappings = [normalize_record(rec) for rec in data.get("mappings", [])]
        return cls(
            arch=data["arch"],
            mapper=data["mapper"],
            seed=int(data["seed"]),
            budget=data.get("budget"),
            workload=data.get("workload") or {},
            ii=data.get("ii"),
            cycles=data.get("cycles"),
            makespan=data.get("makespan"),
            timings=data.get("timings") or {},
            motifs=data.get("motifs"),
            mappings=mappings,
            spatial=data.get("spatial"),
            compiled_sim=data.get("compiled_sim"),
            verified=data.get("verified"),
            degraded=data.get("degraded"),
            provenance=data.get("provenance") or {},
            route_cache=data.get("route_cache"),
            pass_stats=data.get("pass_stats"),
        )

    def save(self, path: str) -> str:
        # temp-file + os.replace: an interrupted save (crash, kill -9)
        # leaves the previous artifact intact, never a truncated file
        from repro.compiler.fsio import atomic_write_json

        return atomic_write_json(path, self.to_json(), indent=1,
                                 sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "CompileResult":
        with open(path) as f:
            return cls.from_json(json.load(f))

    # -- re-verification (no P&R) ------------------------------------------
    def rebuild_mappings(self) -> List[object]:
        """Live, validated :class:`Mapping` objects for every stored record
        (one per spatial segment; exactly one for modulo mappers)."""
        return [mapping_from_record(rec, self.arch) for rec in self.mappings]

    def populate_compiled_sim(self, iterations: int = 3) -> bool:
        """Lower the stored mappings into ``repro.sim`` tensor form and
        attach them as ``compiled_sim`` (segments the lowering cannot
        express are recorded as ``null`` and keep using the scalar
        oracle).  Returns ``False`` — leaving the artifact unchanged —
        when there is nothing lowerable; never raises: the forms are an
        accelerator, not a requirement."""
        from repro.compiler.fsio import sha256_of_json
        from repro.sim.lower import LoweringUnsupported, lower_mapping

        if not self.mappings:
            return False
        try:
            rebuilt = self.rebuild_mappings()
        except (ValueError, KeyError):
            return False
        forms: List[Optional[Dict[str, object]]] = []
        for m in rebuilt:
            try:
                forms.append(lower_mapping(m, iterations=iterations)
                             .to_json())
            except LoweringUnsupported:
                forms.append(None)
        self.compiled_sim = {
            "iterations": iterations,
            "mappings_sha256": sha256_of_json(self.mappings),
            "forms": forms,
        }
        return True

    def _stored_prepared(self, iterations: int):
        """Rebuild a ``repro.sim`` :class:`PreparedBatch` from the
        artifact's ``compiled_sim`` forms, or ``None`` when they are
        absent, lowered for a different trip count, malformed, or no
        longer bound to the mapping content (``mappings_sha256``
        mismatch) — every ``None`` means "lower freshly"."""
        cs = self.compiled_sim
        if not isinstance(cs, dict) or not self.mappings:
            return None
        if cs.get("iterations") != iterations:
            return None
        forms_json = cs.get("forms")
        if not isinstance(forms_json, list) \
                or len(forms_json) != len(self.mappings):
            return None
        from repro.compiler.fsio import sha256_of_json

        if cs.get("mappings_sha256") != sha256_of_json(self.mappings):
            return None
        from repro.sim.batch import PreparedBatch, pack_bucket
        from repro.sim.lower import CompiledSim

        scalar_idx: List[int] = []
        batch_idx: List[int] = []
        forms = []
        try:
            for i, fj in enumerate(forms_json):
                if fj is None:
                    scalar_idx.append(i)
                else:
                    batch_idx.append(i)
                    forms.append(CompiledSim.from_json(fj))
        except (KeyError, TypeError, ValueError):
            return None
        return PreparedBatch(
            iterations=iterations, n_mappings=len(self.mappings),
            scalar_idx=scalar_idx, batch_idx=batch_idx, forms=forms,
            packed=pack_bucket(forms) if forms else None)

    def simulate(self, iterations: int = 3) -> List[Dict[Tuple[int, int], float]]:
        """Cycle-accurately execute the stored mapping(s) against the DFG
        reference oracle; returns the per-(node, iteration) value dict of
        each mapping.  Raises if no routed mapping was stored (mapper
        failure, or the spatial analytic fallback).

        Multi-mapping artifacts (spatial segments) verify through the
        batched backend (``repro.sim.verify_mappings``) — one vectorized
        call instead of a per-segment scalar loop; this is the single
        choke point, so ``compile(..., verify=)``, the store's
        verify-on-load policies, and ``inspect --verify`` all inherit it.
        A *disproven* mapping raises ``AssertionError`` from either
        engine.  An ``OSError`` from the batched path (the fault harness's
        ``sim.batch`` site) degrades to the scalar oracle rather than
        skipping verification — an unverified artifact is never reported
        verified.  Any other backend fault, a jax runtime error on the
        device above all, raises: a device failure must fail the verify,
        not hide behind the host oracle."""
        from repro.compiler.errors import MappingInfeasible
        from repro.core.simulate import simulate as _simulate

        if not self.mappings:
            # MappingInfeasible subclasses ValueError, so pre-taxonomy
            # handlers (and VERIFY_FAILURES) keep catching this
            raise MappingInfeasible(
                f"artifact {self.key}/{self.mapper} holds no routed mapping "
                "to simulate"
            )
        rebuilt = self.rebuild_mappings()
        prepared = self._stored_prepared(iterations)
        if len(rebuilt) > 1 or prepared is not None:
            from repro.sim.batch import verify_mappings

            try:
                return verify_mappings(rebuilt, iterations=iterations,
                                       prepared=prepared)
            except AssertionError:
                raise  # a genuine disproof — exactly what verify is for
            except OSError as e:
                print(
                    f"warning: batched verify backend failed "
                    f"({type(e).__name__}: {e}); degrading to the scalar "
                    f"simulator for {self.key}/{self.mapper}", flush=True,
                )
        return [
            _simulate(m, iterations=iterations) for m in rebuilt
        ]

    # -- display -----------------------------------------------------------
    def summary(self) -> Dict[str, object]:
        out = {
            "key": self.key,
            "arch": self.arch,
            "mapper": self.mapper,
            "seed": self.seed,
            "ii": self.ii,
            "cycles": self.cycles,
            "makespan": self.makespan,
            "segments": len(self.mappings),
            "verified": self.verified,
            "timings": {k: round(v, 3) for k, v in self.timings.items()},
        }
        if self.route_cache:
            out["route_cache"] = self.route_cache
        if self.pass_stats:
            out["passes"] = self.pass_stats
        if self.motifs:
            out["motifs"] = self.motifs
        if self.spatial:
            out["spatial"] = self.spatial
        if self.degraded:
            out["degraded"] = self.degraded
        return out


def new_provenance() -> Dict[str, object]:
    return {
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "repro_version": REPRO_VERSION,
        # whether REPRO_QUICK budget clamping was live at compile time —
        # the store key needs it (a clamped-budget mapping must never be
        # served to a full-budget consumer), and only the artifact itself
        # can carry it into a later `store put`
        "quick": bool(os.environ.get("REPRO_QUICK")),
    }
