"""``moe_experts_roofline``: the held routed experts' weight bytes
(``work_mla_moe.expert_bytes``, every held expert once a step, even one
no token chose) over the chip's HBM bandwidth, over the device time of
the decode steps' ops under the named scope ``moe/experts``: each layer's
slice of the held experts' weights, which the TPU copies out of the layer
stack, and the grouped matmuls of ``models/moe.py:moe_block``.  Moves
``output_tokens_per_s``."""

import serve_scopes


def read(tr, run, ctx):
    return serve_scopes.roofline(tr, run, ctx, "moe/experts", "expert_bytes")
