"""Optimizer, data determinism, compression numerics, elastic reshard."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_shim import given, settings, strategies as st

from repro.configs import smoke_config
from repro.configs.base import ShapeSpec
from repro.parallel.compression import (
    compress_tree_int8, compress_with_feedback, init_residual,
)
from repro.train import checkpoint as ckpt_lib
from repro.train import optimizer as opt_lib
from repro.train.data import Prefetcher, batch_for_step


def test_adamw_converges_quadratic():
    cfg = opt_lib.AdamWConfig(learning_rate=0.1, weight_decay=0.0,
                              warmup_steps=0, total_steps=100)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt_lib.init_opt_state(params, cfg)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = opt_lib.apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clip_metric():
    cfg = opt_lib.AdamWConfig(grad_clip=1.0)
    params = {"w": jnp.ones(4)}
    state = opt_lib.init_opt_state(params, cfg)
    _, _, m = opt_lib.apply_updates(params, {"w": 100 * jnp.ones(4)}, state, cfg)
    assert float(m["grad_norm"]) > 100


def test_data_deterministic_and_prefetch():
    cfg = smoke_config("llama3_2_3b")
    shape = ShapeSpec("s", 16, 2, "train")
    b1 = batch_for_step(cfg, shape, seed=7, step=3)
    b2 = batch_for_step(cfg, shape, seed=7, step=3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    pf = Prefetcher(cfg, shape, seed=7, start_step=0)
    s0, batch0 = pf.next()
    pf.close()
    assert s0 == 0
    np.testing.assert_array_equal(batch0["tokens"], batch_for_step(cfg, shape, 7, 0)["tokens"])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000))
def test_int8_compression_bounded_error(seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((32, 16)) * rng.uniform(0.001, 10), jnp.float32)
    out = compress_tree_int8({"g": g})["g"]
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(out - g))) <= scale * 0.51 + 1e-9


def test_error_feedback_reduces_bias():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(256) * 0.01, jnp.float32)
    res = init_residual({"g": g})
    total_plain = jnp.zeros_like(g)
    total_fb = jnp.zeros_like(g)
    r = res
    for _ in range(16):
        total_plain += compress_tree_int8({"g": g})["g"]
        out, r = compress_with_feedback({"g": g}, r)
        total_fb += out["g"]
    err_plain = float(jnp.linalg.norm(total_plain - 16 * g))
    err_fb = float(jnp.linalg.norm(total_fb - 16 * g))
    assert err_fb <= err_plain + 1e-6


def test_checkpoint_elastic_reshard(tmp_path):
    """Save, then restore with explicit (different) shardings — the elastic
    path: a restarted job re-lays out the same global arrays."""
    params = {"a": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones(3)}
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}
    ckpt_lib.save(str(tmp_path), 5, {"params": params, "opt_state": opt, "extra": {"x": 1}})
    assert ckpt_lib.latest_step(str(tmp_path)) == 5
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    pick = lambda x: sh if getattr(x, "ndim", 0) >= 1 else rep
    shardings = {"params": jax.tree.map(pick, params),
                 "opt_state": jax.tree.map(pick, opt)}
    out = ckpt_lib.restore(str(tmp_path), 5, {"params": params, "opt_state": opt},
                           shardings=shardings)
    np.testing.assert_array_equal(np.asarray(out["params"]["a"]), np.asarray(params["a"]))
    assert out["extra"]["x"] == 1


def test_checkpoint_gc(tmp_path):
    params = {"a": jnp.ones(2)}
    opt = {"m": params, "v": params, "step": jnp.zeros((), jnp.int32)}
    for s in (1, 2, 3, 4):
        ckpt_lib.save(str(tmp_path), s, {"params": params, "opt_state": opt}, keep=2)
    import os
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(kept) == 2 and kept[-1] == "step_00000004"
