"""``decode_mfu``: model FLOPs of the decode steps (``work.decode_flops``,
step ``i`` of a call attending ``prompt + i + 1`` positions) over their
device time (``make_serve_step``, the program ``jit_serve_step``), over
the chip's bf16 peak.  Moves ``output_tokens_per_s``."""

MODULE = "serve_step"


def read(tr, run, ctx):
    secs, n = tr.module_seconds(MODULE)
    w = run.work()
    if not n or n != len(run.batches) * w["decode_steps"] or secs <= 0:
        return None
    flops = len(run.batches) * w["decode_flops"]
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / secs
