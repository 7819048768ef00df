"""``mla_moe_decode_roofline``: the decode steps' share of their memory
roofline in a latent-attention, mixture-of-experts cell: the least bytes
per step (``work_mla_moe.decode_bytes``: every held weight once, the
embedding only in the step's rows, and the cached latents of the
positions attended) over the chip's HBM bandwidth, over the steps'
device time (program ``jit_serve_step``).  Moves ``output_tokens_per_s``."""

import serve_scopes


def read(tr, run, ctx):
    secs, _ = tr.module_seconds(serve_scopes.MODULE)
    if serve_scopes.steps_in_window(tr, run) is None or secs <= 0:
        return None
    moved = len(run.batches) * run.work()["decode_bytes"]
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
