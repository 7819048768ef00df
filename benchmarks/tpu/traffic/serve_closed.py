"""Closed-loop serving through ``repro.serve.loop.generate``.

``clients`` clients each wait for their answer before they send again, so
their requests go to the server as one batch per ``generate`` call.  Every
prompt is ``prompt_len`` token ids drawn from the seed and the batch
number; every answer is ``new_tokens`` greedy tokens.  Every seed sends
the same sizes, so only the token ids differ between seeds.

A request is done when its last token is on the host.  The window runs
whole calls until ``--seconds`` have passed; the rate counts every token
of every request completed over the whole window.

Correct: once the window has closed and the weights are freed, the plain
float32 reference (``reference/qwen3.py``) runs over a sample of completed
requests drawn from the seed, each prompt followed by its served tokens,
and reports how far below the reference's best logit each served token's
logit lies.  The widest such gap is held to the cell's limit.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from harness import Check, Context, span
import work

SPANS = ("prompts", "generate")
#: what ``end_to_end`` reports and what ``checks`` compares
END_TO_END = ("output_tokens_per_s",)
CHECKS = ("served_logit_gap",)


class Traffic:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        p = ctx.cell.params
        self.c = ctx.cell.config
        self.B, self.T, self.new = p["clients"], p["prompt_len"], \
            p["new_tokens"]
        self.batches: List[Dict] = []
        self.window_s = 0.0
        self.params = None
        self._ref = None

    # -- inputs ---------------------------------------------------------
    def prompts(self, batch: int) -> np.ndarray:
        rng = np.random.default_rng([self.ctx.seed % 2**64, batch])
        return rng.integers(0, self.c["vocab_size"], (self.B, self.T),
                            dtype=np.int32)

    def setup(self) -> None:
        cm = self.ctx.cell.config_module
        self.cfg = cm.model_config(self.c)
        self.params = cm.make_params(self.c, self.cfg, self.ctx.seed)
        # warm-up: the one shape the window sends, from a stream of its own
        self._serve(self.prompts(2**32))

    def _serve(self, prompts: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from repro.serve.loop import generate

        tokens, _ = generate(self.cfg, self.params, jnp.asarray(prompts),
                             max_new_tokens=self.new)
        return np.asarray(tokens)

    # -- the measured window ----------------------------------------------
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:                              # whole calls, at least one
            i = len(self.batches)
            with span("prompts"):
                prompts = self.prompts(i)
            with span("generate"):
                tokens = self._serve(prompts)
            self.batches.append({"prompts": prompts, "tokens": tokens})
            self.window_s = time.perf_counter() - t0
            if self.window_s >= seconds:
                break

    def _answered(self, b: Dict) -> np.ndarray:
        """Per request of a batch: did a whole answer come back?"""
        t = b["tokens"]
        if t.shape != (self.B, self.new):
            return np.zeros(self.B, bool)
        return ((t >= 0) & (t < self.c["vocab_size"])).all(axis=1)

    @property
    def attempted(self) -> int:
        return len(self.batches) * self.B

    @property
    def failed(self) -> int:
        return self.attempted - sum(int(self._answered(b).sum())
                                    for b in self.batches)

    def end_to_end(self) -> Dict[str, float]:
        """Tokens of the requests completed, over the window.  No latency
        percentile: every request of a call has the call's latency, and a
        window holds a few calls."""
        tokens = sum(int(self._answered(b).sum()) * self.new
                     for b in self.batches)
        return {"output_tokens_per_s": tokens / self.window_s}

    def work(self) -> Dict:
        """Work of one ``generate`` call at this cell's sizes."""
        return work.generate_work(self.c, self.B, self.T, self.new)

    def release(self) -> None:
        self.params = None
        gc.collect()

    # -- correctness ------------------------------------------------------
    def _sample(self):
        """The requests compared: ``check_requests`` of those completed,
        drawn from the seed, each from a slot (a row of the batch) of its
        own, so that a fault in any one slot shows once the sample holds
        as many requests as there are clients (every request has the same
        length)."""
        if not self.batches:
            return []
        rng = np.random.default_rng([self.ctx.seed % 2**64, 2**33])
        k = self.ctx.cell.params["check_requests"]
        answered = np.stack([self._answered(b) for b in self.batches])
        pick = []
        for r in rng.permutation(self.B)[:k]:
            calls = np.flatnonzero(answered[:, r])
            if calls.size:
                pick.append((int(rng.choice(calls)), int(r)))
        return sorted(pick)

    def _reference(self, quant=None):
        from reference import qwen3

        sample = self._sample()
        seqs = [np.concatenate([self.batches[i]["prompts"][r],
                                self.batches[i]["tokens"][r][:-1]])
                for i, r in sample]
        served = np.stack([self.batches[i]["tokens"][r] for i, r in sample])
        positions = list(range(self.T - 1, self.T + self.new - 1))
        return qwen3.logits(self.c, self.ctx.seed, seqs, positions, quant), \
            served

    def checks(self) -> List[Check]:
        from reference import qwen3

        if not self._sample():
            return [Check("served_logit_gap", float("inf"), self._limit())]
        self._ref, served = self._reference()
        gap = float(qwen3.served_gaps(self._ref, served).max())
        return [Check("served_logit_gap", gap, self._limit())]

    def _limit(self):
        """The cell's limit; ``None`` until readings on the chip set it."""
        return self.ctx.cell.params.get("limits", {}).get(
            "served_logit_gap")

    def control_checks(self) -> List[Check]:
        """The same number for the reference computed in float8 (e4m3) in
        the program's place: the gap of the token it puts first."""
        from reference import qwen3

        ctrl, _ = self._reference(quant="float8_e4m3fn")
        gap = float(qwen3.served_gaps(self._ref, ctrl.argmax(-1)).max())
        return [Check("served_logit_gap", gap, self._limit())]
