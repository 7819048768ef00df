"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: the TPU compiler builds each kernel at a real size for one
chip of a ``v5e:2x2`` topology described on the host, so a kernel that the
chip's compiler would refuse (VMEM overrun, unaligned tiling) fails here
and not on the chip.  The topology is described inside a fixture, only
once a test of this file runs, and every compile stays in this process.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import motif_pcu as mp
from repro.kernels.sim_alu import sim_alu


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip, so
    # keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("shape", [(4096, 128), (16384, 256)])
def test_sim_alu_compiles_for_v5e(one_chip, shape):
    compiled = _compile(
        functools.partial(sim_alu, interpret=False), one_chip,
        (shape, jnp.int32), *[(shape, jnp.float32)] * 4)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sched", [mp.FANIN, mp.FANOUT, mp.UNICAST],
                         ids=["fanin", "fanout", "unicast"])
def test_motif_pcu_compiles_for_v5e(one_chip, sched):
    compiled = _compile(
        lambda x: mp.motif_pcu(sched, 3, x, interpret=False), one_chip,
        ((3, 1 << 20), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_serve_step_reads_the_cache_in_place_on_v5e(one_chip):
    """Qwen3-14B's attention widths at the decode cell's batch and cache
    length: the compiled serve step's temporaries are smaller than one KV
    head's keys in one layer, so it neither copies the cache nor lays a
    layer's slice out anew."""
    from repro.configs import get_config
    from repro.models import zoo
    from repro.models.layers import shapes_of
    from repro.train import steps

    cfg = get_config("qwen3_14b").replace(n_layers=2, d_model=1024, d_ff=2048,
                                          vocab_size=2048)
    B, S = 32, 1152
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    args = (on_chip(shapes_of(zoo.param_spec(cfg))),
            on_chip(shapes_of(zoo.cache_spec(cfg, B, S))),
            jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip))
    compiled = jax.jit(steps.make_serve_step(cfg), donate_argnums=(1,)).lower(
        *args).compile()
    head_keys = B * S * cfg.resolved_head_dim * 2  # bf16
    assert compiled.memory_analysis().temp_size_in_bytes < head_keys
