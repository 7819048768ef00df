"""Pallas kernel for the batched simulator's ALU apply stage.

One simulated cycle of the whole PE grid applies, per (mapping, node)
lane, the node's opcode to its three gathered operands — a pure
elementwise dispatch over a static opcode tensor, which is exactly the
shape the VPU wants.  The gathers/scatters around it stay in jnp (XLA
fuses them); this kernel replaces the 20-way ``jnp.where`` ladder in
``repro.sim.step.apply_ops_jnp`` for ``backend="pallas"``.

The opcode dispatch is still a where-ladder *inside* the kernel, but over
VMEM-resident blocks: every lane evaluates every op and keeps its own —
branch-free, as TPU vector hardware requires (and exactly what the
domain-hardwired PCU of the paper does in silicon: all functional units
compute, the configuration selects).

Because the stage is elementwise, the ``(B, N)`` operands are flattened
into a lane-dense ``(rows, 128)`` view and walked by a 1-D row grid of
fixed-size blocks: VMEM use is set by the block, not by the batch, so a
bucket the size of an architecture sweep compiles like a small one.

On CPU hosts the kernel executes with ``interpret=True`` (the same
``jax.default_backend()`` convention as ``repro.kernels.ops``).  A failure
of the kernel raises; nothing falls back to plain jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.sim.lower import OPS
from repro.sim.step import _jnp_alu

#: lanes of the flattened view (one VPU vreg row)
_LANES = 128
#: rows per grid block: 512 x 128 f32 = 256 KiB per operand, six operands
#: double-buffered stay far inside the default scoped-VMEM limit
_BLOCK_ROWS = 512
#: float32 sublane count (block rows must be a multiple of it)
_SUBLANES = 8


def _kernel(code_ref, a_ref, b_ref, c_ref, leaf_ref, o_ref):
    code = code_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    c = c_ref[...]
    leaf = leaf_ref[...]
    out = jnp.zeros_like(a)
    for i in range(len(OPS)):
        out = jnp.where(code == i, _jnp_alu(jnp, i, a, b, c, leaf), out)
    o_ref[...] = out


def sim_alu(opcode, a, b, c, leaf, *, interpret: bool = None):
    """Elementwise ``_apply(opcode, a, b, c, leaf)`` over float32 arrays
    of any one shape; the result has that shape."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = opcode.shape
    n = opcode.size
    rows = -(-n // _LANES)
    block = min(_BLOCK_ROWS, -(-rows // _SUBLANES) * _SUBLANES)
    rows = -(-rows // block) * block

    def lanes(x, dtype):
        x = x.astype(dtype).reshape(-1)
        return jnp.pad(x, (0, rows * _LANES - n)).reshape(rows, _LANES)

    args = [lanes(opcode, jnp.int32)] + [
        lanes(x, jnp.float32) for x in (a, b, c, leaf)]
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(rows // block,),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
        name="sim_alu",
    )(*args)
    return out.reshape(-1)[:n].reshape(shape)
