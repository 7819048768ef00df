"""``mla_decode_roofline``: the latent attention's least bytes a decode
step (``work_mla_moe.mla_bytes``: every layer's attention weights once
and the cached latents it attends) over the chip's HBM bandwidth, over
the device time of the decode steps' ops under the named scope ``mla``
(``models/mla.py:decode_attention``).  Moves ``output_tokens_per_s``."""

import serve_scopes


def read(tr, run, ctx):
    return serve_scopes.roofline(tr, run, ctx, "mla", "mla_bytes")
