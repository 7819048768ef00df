"""``decode_roofline``: the decode steps' share of their memory roofline,
which bounds them: the least bytes per step (``work.decode_bytes``: every
weight once and the keys and values of the positions attended, all in
bf16) over the chip's HBM bandwidth, over the steps' device time.
Moves ``output_tokens_per_s``."""

MODULE = "serve_step"


def read(tr, run, ctx):
    secs, n = tr.module_seconds(MODULE)
    w = run.work()
    if not n or n != len(run.batches) * w["decode_steps"] or secs <= 0:
        return None
    moved = len(run.batches) * w["decode_bytes"]
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
