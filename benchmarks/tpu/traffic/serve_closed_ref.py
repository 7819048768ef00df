"""Closed-loop serving, as ``serve_closed.py``, for any configuration whose
file names its plain reference (``reference``) and whose decode work the
latent-attention, mixture-of-experts counts describe (``work_mla_moe.py``).

The load, the window, the rate and the sample (one request per slot) are
``serve_closed``'s.  Besides, every call's ``generate`` info (the program's
own counters, such as ``moe_routed`` and ``moe_rows``) is kept beside its
batch, for the per-layer metrics.

Correct: two numbers over the compared requests' served tokens, each
token read by how far its logit lies below the reference's best.
``served_slot_gap`` is the mean over a request's tokens, at the request
whose mean is the largest: a fault in a slot's cache, position or tokens
moves every token of the request, and the float8 control moves them
all.  ``served_far_share`` is the share of the tokens, in %, that lie
more than the cell's ``far_gap`` below the best: a sparse fault, one
wrong token in a request, which a mean over its tokens dilutes, puts
that token there.  Not ``serve_closed``'s worst single token: in a deep
mixture of experts with random weights, bfloat16 rounding flips
near-tied expert selections that the float32 reference makes the other
way, and a token after such flips can lie as far below the reference's
best as the float8 control's tokens do (Moonlight on a v5e: program
1.73-2.93, control 2.67-3.00 at the worst token).
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List

import numpy as np

from harness import Check, load_module
import work_mla_moe

HERE = Path(__file__).resolve().parent
_base = load_module(HERE / "serve_closed.py", "bench_serve_closed_base")

SPANS = _base.SPANS
END_TO_END = _base.END_TO_END
CHECKS = ("served_slot_gap", "served_far_share")


_REFS: Dict[str, object] = {}


def _reference_module(path: str):
    """The plain reference a configuration file names, loaded once a
    process (``readings.py`` makes a ``Traffic`` for every seed)."""
    if path not in _REFS:
        _REFS[path] = load_module(HERE.parent / path)
    return _REFS[path]


class Traffic(_base.Traffic):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.ref = _reference_module(self.c["reference"])
        self.infos: List[Dict] = []

    def setup(self) -> None:
        super().setup()
        self.infos.clear()                      # the warm-up call's

    def _serve(self, prompts: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from repro.serve.loop import generate

        tokens, info = generate(self.cfg, self.params, jnp.asarray(prompts),
                                max_new_tokens=self.new)
        self.infos.append(info)
        return np.asarray(tokens)

    def work(self) -> Dict:
        return work_mla_moe.generate_work(self.c, self.B, self.T, self.new)

    # -- correctness: serve_closed's, with the file's reference ------------
    def _reference(self, quant=None):
        sample = self._sample()
        seqs = [np.concatenate([self.batches[i]["prompts"][r],
                                self.batches[i]["tokens"][r][:-1]])
                for i, r in sample]
        served = np.stack([self.batches[i]["tokens"][r] for i, r in sample])
        positions = list(range(self.T - 1, self.T + self.new - 1))
        return self.ref.logits(self.c, self.ctx.seed, seqs, positions,
                               quant), served

    def _gap_checks(self, tokens) -> List[Check]:
        """``served_slot_gap`` and ``served_far_share`` of ``tokens`` (one
        row per compared request) against the float32 reference's
        logits."""
        gaps = self.ref.served_gaps(self._ref, tokens)
        far = 100.0 * float(np.mean(gaps > self.ctx.cell.params["far_gap"]))
        return [Check("served_slot_gap", float(gaps.mean(axis=1).max()),
                      self._limit("served_slot_gap")),
                Check("served_far_share", far,
                      self._limit("served_far_share"))]

    def _limit(self, name):
        return self.ctx.cell.params.get("limits", {}).get(name)

    def checks(self) -> List[Check]:
        if not self._sample():
            return [Check(name, float("inf"), self._limit(name))
                    for name in CHECKS]
        self._ref, served = self._reference()
        return self._gap_checks(served)

    def control_checks(self) -> List[Check]:
        ctrl, _ = self._reference(quant="float8_e4m3fn")
        return self._gap_checks(ctrl.argmax(-1))
