"""Batched verification of CGRA mappings through
``repro.sim.batch.simulate_batch``, as a sweep's post-sweep verification
sends it (``core/collect.py:_batch_verify_store``): every mapping of the
configuration's pool in one call.

Set-up loads the pool, lowers each mapping once (``repro.sim.lower``) and
packs them into one bucket, in an order drawn from the seed, so every
seed sends the same mappings in the same padded shape.  The bucket is
prepared once, as ``simulate_batch(..., prepared=...)`` reruns the
verification of the same artifacts.  One warm-up call loads or compiles
that shape.

The window calls ``simulate_batch`` on the prepared bucket, on the cell's
``backend``, until ``--seconds`` have passed; the rate counts every
mapping whose verdict came back, over the whole window.

Correct: once the window has closed, the plain float64 reference
(``reference/cgra.py``) runs every pool mapping.  Every verdict of the
window must equal the reference's; the values of a sample of
``check_values`` verdicts drawn from the seed must hold every
(node, iteration) the reference produced, with the widest relative gap
under the cell's limit.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness import Check, Context, span
import work

SPANS = ("simulate_batch",)
#: what ``end_to_end`` reports and what ``checks`` compares
END_TO_END = ("verify_mappings_per_s",)
CHECKS = ("verdicts_wrong", "values_missing", "value_gap", "answers_missing")


class Traffic:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.c = ctx.cell.config
        self.I = self.c["iterations"]
        self.backend = ctx.cell.params["backend"]
        self.results: List = []              # one BatchResult per call
        self.window_s = 0.0
        self._ref = None

    def setup(self) -> None:
        from repro.sim.batch import PreparedBatch, pack_bucket
        from repro.sim.lower import lower_mapping

        self.pool = self.ctx.cell.config_module.load_pool(self.c)
        rng = np.random.default_rng([self.ctx.seed % 2**64])
        self.order = rng.permutation(len(self.pool))   # member -> pool index
        forms = [lower_mapping(self.pool[i].mapping, iterations=self.I)
                 for i in self.order]
        self.mappings = [self.pool[i].mapping for i in self.order]
        self.prepared = PreparedBatch(
            iterations=self.I, n_mappings=len(forms), scalar_idx=[],
            batch_idx=list(range(len(forms))), forms=forms,
            packed=pack_bucket(forms))
        self._call()

    def _call(self):
        from repro.sim.batch import simulate_batch

        return simulate_batch(self.mappings, iterations=self.I,
                              backend=self.backend, prepared=self.prepared)

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:                              # whole calls, at least one
            with span("simulate_batch"):
                res = self._call()
            self.results.append(res)
            self.window_s = time.perf_counter() - t0
            if self.window_s >= seconds:
                break

    def _returned(self, res) -> int:
        """Verdicts that came back from the device path."""
        return sum(1 for v in res if v is not None
                   and v.backend == self.backend)

    @property
    def attempted(self) -> int:
        return len(self.results) * len(self.order)

    @property
    def failed(self) -> int:
        return self.attempted - sum(self._returned(r) for r in self.results)

    def end_to_end(self) -> Dict[str, float]:
        done = sum(self._returned(r) for r in self.results)
        return {"verify_mappings_per_s": done / self.window_s}

    def call_bytes(self) -> int:
        """Least bytes of one call (``work.py``)."""
        return work.bucket_bytes((e.record for e in self.pool), self.I)

    def release(self) -> None:
        pass

    # -- correctness ------------------------------------------------------
    def _reference(self, round_to=None):
        from reference import cgra

        return [cgra.simulate(e.record, self.I, round_to=round_to,
                              tol=(float("inf"), 0.0) if round_to else
                              (1e-6, 1e-6))
                for e in self.pool]

    def _compare(self, answers, ref) -> List[Check]:
        """``answers``: ``(pool index, ok, values or None)`` per verdict."""
        from reference import cgra

        lim = self.ctx.cell.params["limits"]
        wrong = sum(ok != ref[i][0] for i, ok, _ in answers)
        rng = np.random.default_rng([self.ctx.seed % 2**64, 2**33])
        k = min(self.ctx.cell.params["check_values"], len(answers))
        gap, missing = 0.0, 0
        for j in rng.choice(len(answers), size=k, replace=False):
            i, ok, values = answers[j]
            if ok and ref[i][0]:
                g, m = cgra.value_gap(values(), ref[i][1])
                gap, missing = max(gap, g), missing + m
        return [Check("verdicts_wrong", wrong, lim["verdicts_wrong"]),
                Check("values_missing", missing, lim["values_missing"]),
                Check("value_gap", gap, lim["value_gap"]),
                Check("answers_missing", self.failed, lim["answers_missing"])]

    def checks(self) -> List[Check]:
        self._ref = self._reference()
        answers = []
        for res in self.results:
            for i, v in zip(self.order, res):
                if v is not None:
                    answers.append((i, v.ok, lambda v=v: v.values))
        return self._compare(answers, self._ref)

    def control_checks(self) -> List[Check]:
        """The reference with every value rounded to bfloat16, in the
        program's place, on the same calls."""
        ctrl = self._reference(round_to="bfloat16")
        answers = [(i, ctrl[i][0], lambda i=i: ctrl[i][1])
                   for _ in self.results for i in self.order]
        return self._compare(answers, self._ref)
