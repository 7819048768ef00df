"""Decode-vs-forward consistency: teacher-forced decode must reproduce the
full forward pass logits position by position (KV-cache correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import zoo
from repro.models.layers import init_of, output_head

ARCHS = ["llama3_2_3b", "h2o_danube_3_4b", "falcon_mamba_7b", "zamba2_1_2b",
         "granite_moe_1b_a400m", "whisper_tiny", "moonlight_16b_a3b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_matches_forward(arch):
    cfg = smoke_config(arch).replace(attn_impl="naive")
    params = init_of(zoo.param_spec(cfg), jax.random.PRNGKey(0))
    B, T = 2, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 4)), jnp.int32)
    batch = {"tokens": tokens[:, :T]}
    if cfg.family == "encdec":
        batch["audio_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model)) * 0.05, jnp.float32
        ).astype(jnp.bfloat16)
    cache, logits_prefill = zoo.prefill(cfg, params, batch)
    from repro.serve.kvcache import grow_cache
    cache = grow_cache(cache, 4, window=cfg.sliding_window)
    # teacher-forced decode of the next 4 tokens
    decode_logits = []
    for i in range(4):
        cache, logits = zoo.decode_step(cfg, params, cache, tokens[:, T + i : T + i + 1])
        decode_logits.append(logits[:, 0])
    # reference: full forward over T+4 tokens
    full_batch = dict(batch, tokens=tokens)
    h = zoo.forward(cfg, params, full_batch)
    if isinstance(h, tuple):
        h = h[0]
    ref_logits = (h @ output_head(cfg, params).T).astype(jnp.float32)
    for i in range(4):
        got = np.asarray(decode_logits[i], np.float32)
        want = np.asarray(ref_logits[:, T + i], np.float32)
        np.testing.assert_allclose(got, want, rtol=0.12, atol=0.25)


def test_generate_token_budget_exact():
    """``generate`` must emit exactly ``max_new_tokens`` tokens — the seed
    loop emitted one token even at ``max_new_tokens=0``."""
    from repro.serve.loop import generate

    cfg = smoke_config("llama3_2_3b").replace(n_layers=2)
    params = init_of(zoo.param_spec(cfg), jax.random.PRNGKey(0))
    prompts = jnp.zeros((2, 8), jnp.int32)

    t0, info0 = generate(cfg, params, prompts, max_new_tokens=0)
    assert t0.shape == (2, 0)
    assert info0["cache_length"] == 8  # prefill only, cache still usable

    t1, info1 = generate(cfg, params, prompts, max_new_tokens=1)
    assert t1.shape == (2, 1)
    assert info1["cache_length"] == 8  # one greedy token, no decode step

    # the single token agrees with the first token of a longer decode
    t4, _ = generate(cfg, params, prompts, max_new_tokens=4)
    assert t4.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t4[:, :1]))


@pytest.mark.parametrize("T", [16, 13])
@pytest.mark.parametrize("arch", ["h2o_danube_3_4b", "granite_moe_1b_a400m", "moonlight_16b_a3b"])
def test_sliding_window_ring_buffer(arch, T):
    """A window-bounded ring, wrapped by the prompt (evenly or not) and
    again by decoding, attends what the full forward pass attends."""
    cfg = smoke_config(arch).replace(attn_impl="naive", sliding_window=8)
    params = init_of(zoo.param_spec(cfg), jax.random.PRNGKey(0))
    B = 1
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T + 6)), jnp.int32)
    cache, _ = zoo.prefill(cfg, params, {"tokens": tokens[:, :T]})
    assert cache["latent" if cfg.kv_lora_rank else "k"].shape[2] == 8  # window-bounded
    for i in range(6):
        cache, logits = zoo.decode_step(cfg, params, cache, tokens[:, T + i : T + i + 1])
    full = zoo.forward(cfg, params, {"tokens": tokens, "labels": tokens})
    if isinstance(full, tuple):
        full = full[0]
    ref = (full @ output_head(cfg, params).T).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(logits[:, 0], np.float32), np.asarray(ref[:, T + 5], np.float32),
        rtol=0.12, atol=0.25,
    )


def test_serve_launcher_is_seeded():
    """``launch.serve.serve`` (the path ``chip_smoke.py`` drives at full
    width) answers one batch, and the same seed gives the same tokens."""
    from repro.launch.serve import serve

    cfg = smoke_config("llama3_2_3b").replace(n_layers=2)
    _, prompts, t_a, info = serve(cfg, batch=2, prompt_len=8, new_tokens=3,
                                  seed=5)
    _, _, t_b, _ = serve(cfg, batch=2, prompt_len=8, new_tokens=3, seed=5)
    assert prompts.shape == (2, 8) and t_a.shape == (2, 3)
    assert info["cache_length"] == 8 + 3 - 1
    np.testing.assert_array_equal(np.asarray(t_a), np.asarray(t_b))


def test_compile_cache_placement(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache sits at the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jc")
        assert cc.enable_compile_cache() == "/elsewhere/jc"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(cc.REPO_CACHE_DIR)
        assert cc.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert cc.REPO_CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("keys", [("k", "v"), ("latent",)])
def test_grow_cache_grows_every_sequence_cache(keys):
    """Keys and values, or latent attention's latents, gain the decode
    headroom along the sequence; the new slots are empty (``pos`` -1) and
    what else the cache holds passes through."""
    from repro.serve.kvcache import grow_cache

    cache = {k: jnp.ones((3, 2, 5, 4), jnp.bfloat16) for k in keys}
    cache.update(pos=jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (2, 5)),
                 length=jnp.full((2,), 5, jnp.int32),
                 counters={"moe_rows": jnp.arange(3, dtype=jnp.int32)})
    grown = grow_cache(cache, 3)
    for k in keys:
        assert grown[k].shape == (3, 2, 8, 4)
        np.testing.assert_array_equal(np.asarray(grown[k][:, :, 5:], np.float32), 0)
    np.testing.assert_array_equal(np.asarray(grown["pos"][0]),
                                  [0, 1, 2, 3, 4, -1, -1, -1])
    assert grown["counters"] is cache["counters"]


def _ring_pos(lengths, S):
    """Absolute position each slot holds once ``lengths[b]`` positions have
    been written with slot = position % S (-1: empty)."""
    s = np.arange(S)[None]
    n = np.asarray(lengths)[:, None]
    p = s + S * ((n - 1 - s) // S)
    return np.where(p >= 0, p, -1).astype(np.int32)


# (Hq, Hkv, window, S, per-row lengths)
ROW_CASES = {
    "group1": (4, 4, 0, 16, [0, 3, 9, 15]),
    "group5": (40, 8, 0, 16, [1, 7, 15]),
    "ring_wrapped": (10, 2, 8, 8, [5, 8, 13, 30]),
    "ring_below_window": (10, 2, 12, 8, [3, 7]),
    "ring_below_window_wrapped": (10, 2, 12, 8, [9, 20]),
}


@pytest.mark.parametrize("case", ROW_CASES)
def test_decode_attention_row_matches_full_cache(case):
    """The new-row attention over the cache as it was before the step equals
    writing the row first and attending the whole cache (float32)."""
    from repro.models.layers import decode_attention, decode_attention_row

    Hq, Hkv, window, S, lengths = ROW_CASES[case]
    B, hd = len(lengths), 16
    rng = np.random.default_rng(7)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f32(B, 1, Hq, hd), f32(B, 1, Hkv, hd), f32(B, 1, Hkv, hd)
    kc, vc = f32(B, S, Hkv * hd), f32(B, S, Hkv * hd)
    length = jnp.asarray(lengths, jnp.int32)
    pos = jnp.asarray(_ring_pos(lengths, S))
    b, slot = jnp.arange(B), length % S

    new_pos = pos.at[b, slot].set(length)
    valid = (new_pos >= 0) & (new_pos <= length[:, None])
    if window:
        valid &= (length[:, None] - new_pos) < window
    want = decode_attention(
        q, kc.at[b, slot].set(k.reshape(B, -1)).reshape(B, S, Hkv, hd),
        vc.at[b, slot].set(v.reshape(B, -1)).reshape(B, S, Hkv, hd), valid)
    got = decode_attention_row(q, kc, vc, valid & (jnp.arange(S) != slot[:, None]), k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("case", ROW_CASES)
def test_step_mask_follows_the_ring(case):
    """A step writes position ``length`` at slot ``length % S`` and attends
    every written slot within the window but the one it overwrites."""
    from repro.serve import kvcache

    _, _, window, S, lengths = ROW_CASES[case]
    n = np.asarray(lengths)[:, None]
    pos = _ring_pos(lengths, S)
    step = kvcache.step({"pos": jnp.asarray(pos), "length": jnp.asarray(lengths, jnp.int32)},
                        window)
    want = (pos >= 0) & (np.arange(S) != n % S)
    if window:
        want &= n - pos < window
    np.testing.assert_array_equal(np.asarray(step.slot), n[:, 0] % S)
    np.testing.assert_array_equal(np.asarray(step.pos), _ring_pos(n[:, 0] + 1, S))
    np.testing.assert_array_equal(np.asarray(step.mask), want)


@pytest.mark.parametrize("arch", ["llama3_2_3b", "h2o_danube_3_4b", "granite_moe_1b_a400m",
                                  "zamba2_1_2b", "whisper_tiny", "moonlight_16b_a3b"])
def test_decode_step_writes_only_the_new_row(arch):
    """No layer hands back a whole sequence cache from the layer scan, and a
    step leaves every slot but each row's current one bit for bit as it was."""
    from repro.serve.kvcache import SEQ_KEYS, grow_cache

    cfg = smoke_config(arch).replace(n_layers=2, attn_impl="naive")
    if cfg.sliding_window:
        cfg = cfg.replace(sliding_window=8)  # a 16-token prompt wraps the ring
    params = init_of(zoo.param_spec(cfg), jax.random.PRNGKey(0))
    B, T = 2, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"tokens": tokens[:, :T]}
    if cfg.family == "encdec":
        batch["audio_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model)) * 0.05, jnp.float32
        ).astype(jnp.bfloat16)
    cache, _ = zoo.prefill(cfg, params, batch)
    cache = grow_cache(cache, 4, window=cfg.sliding_window)
    step = lambda c: zoo.decode_step(cfg, params, c, tokens[:, T:])

    keys = [k for k in SEQ_KEYS if k in cache]
    assert keys
    jaxpr = jax.make_jaxpr(step)(cache)

    def scan_outputs(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                yield from (o.aval.shape for o in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scan_outputs(sub)

    shapes = list(scan_outputs(jaxpr.jaxpr))
    assert shapes, "decode_step walks its layers with lax.scan"
    for key in keys:
        S, width = cache[key].shape[2:]
        assert not [s for s in shapes if tuple(s[-2:]) == (S, width)], (key, shapes)

    new, _ = step(cache)
    for key in keys:
        S = cache[key].shape[2]
        slot = np.asarray(cache["length"]) % S
        old, got = np.asarray(cache[key]), np.asarray(new[key])
        keep = np.ones(old.shape[1:3], bool)
        keep[np.arange(B), slot] = False
        np.testing.assert_array_equal(got[:, keep], old[:, keep])
        assert not np.array_equal(got[:, ~keep], old[:, ~keep])
