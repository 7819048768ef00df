"""``prefill_mfu``: model FLOPs of the prefill calls (``work.prefill_flops``
at the cell's batch and prompt length: every matmul, causal attention,
the output head on the last token) over their device time
(``train/steps.make_prefill_step``, the program ``jit_prefill_step``),
over the chip's bf16 peak.  Moves ``output_tokens_per_s``."""

MODULE = "prefill_step"


def read(tr, run, ctx):
    secs, n = tr.module_seconds(MODULE)
    if not n or n != len(run.batches) or secs <= 0:
        return None
    flops = n * run.work()["prefill_flops"]
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / secs
