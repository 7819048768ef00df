"""The latent-attention, mixture-of-experts serving cell's generator
(``traffic/serve_closed_ref.py``), its reference and its work counts, on
the CPU at a tiny size: the float8 control fails the cell's limit and the
program passes it, planted faults read ``correct: false``, the sample
holds one request per slot, and ``work_mla_moe.py`` counts what a hand
count gives."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import harness
import work_mla_moe

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
CELL = "serve_decode.mla_tiny"
#: the tiny mix: prompt tokens, served tokens
PROMPT, NEW = 16, 4

#: Moonlight's block at a CPU size: 3 layers (1 dense), 4 of 8 experts
TINY_MLA = {
    "name": "moonlight_tiny", "kind": "mla_moe_decoder",
    "source": "test-only reduction of configs/moonlight_16b_a3b_e16.json",
    "program_arch": "moonlight_16b_a3b", "reference": "reference/moonlight.py",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 4, "router_experts": 8,
    "first_held_expert": 0, "num_experts_per_tok": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 50000, "rms_norm_eps": 1e-5,
    "routed_scaling_factor": 2.446, "vocab_size": 256,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "q_lora_rank": None, "tie_word_embeddings": False,
}


@pytest.fixture
def mla_bench(tiny_bench):
    """``(here, root)`` of the tiny benchmark copy with a tiny cell of the
    new generator added: new files and entries only."""
    here, root = tiny_bench
    (here / "configs" / "moonlight_tiny.json").write_text(
        json.dumps(TINY_MLA))
    shutil.copy(here / "configs" / "moonlight_16b_a3b_e16.py",
                here / "configs" / "moonlight_tiny.py")
    (here / "traffic" / "serve_ref_tiny.json").write_text(json.dumps(
        {"generator": "serve_closed_ref", "prompt_len": PROMPT,
         "new_tokens": NEW}))
    (here / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"config": "moonlight_tiny", "traffic": "serve_ref_tiny",
         "clients": 2, "check_requests": 2, "far_gap": 0.5,
         "limits": {"served_slot_gap": 0.01, "served_far_share": 5.0}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "moonlight_tiny", "source": "test",
                             "file": "benchmarks/tpu/configs/"
                                     "moonlight_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "moonlight_tiny",
                               "traffic": "serve_ref_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "output_tokens_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return here, root


def _run(bench, cpu, monkeypatch, capsys, seed=2**33 + 1):
    import run

    here, root = bench
    monkeypatch.setattr(harness, "peaks",
                        lambda kind, here=None: {"hbm_bytes_per_s": 819e9})
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.2", "--trace", "0"], devices=cpu, here=here,
                    root=root) == 0
    out, _ = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1])


def test_tiny_cell_is_correct_and_keeps_the_counters(mla_bench, cpu,
                                                     monkeypatch, capsys):
    r = _run(mla_bench, cpu, monkeypatch, capsys)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert set(r["checks"]) == {"served_slot_gap", "served_far_share"}


def test_the_generator_keeps_each_calls_counters(mla_bench, cpu):
    here, root = mla_bench
    cell = harness.find_cell(CELL, here=here, root=root)
    ctx = harness.Context(cell=cell, seed=5, devices=cpu, peaks={})
    t = cell.traffic.Traffic(ctx)
    t.setup()
    t.window(0.0)
    assert len(t.infos) == len(t.batches) == 1
    info = t.infos[0]
    # 2 MoE layers; a prefill of 2 x 16 tokens and 3 decode steps of 2,
    # top-2 routes each: 76 rows a layer, of which the held ones landed
    assert info["moe_rows"].tolist() == [76, 76]
    assert all(0 < n < 76 for n in info["moe_routed"].tolist())
    reader = harness.reader("moe_pad_share", here)
    share = reader.read(None, t, ctx)
    want = 100 * (1 - info["moe_routed"].sum() / 152)
    assert share == pytest.approx(want)


def _alter_one_token(monkeypatch):
    import repro.train.steps as steps

    real = steps.make_serve_step

    def make(cfg):
        step = real(cfg)

        def broken(params, cache, tokens):
            cache, nxt, logits = step(params, cache, tokens)
            return cache, nxt.at[0, 0].add(1) % cfg.vocab_size, logits
        return broken

    monkeypatch.setattr(steps, "make_serve_step", make)


def _alter_the_last_token(monkeypatch):
    """One wrong token in every request: the last step's."""
    import jax.numpy as jnp

    import repro.train.steps as steps

    real = steps.make_serve_step

    def make(cfg):
        step = real(cfg)

        def broken(params, cache, tokens):
            last = cache["length"][:, None] == PROMPT + NEW - 2
            cache, nxt, logits = step(params, cache, tokens)
            return cache, jnp.where(last, (nxt + 1) % cfg.vocab_size,
                                    nxt), logits
        return broken

    monkeypatch.setattr(steps, "make_serve_step", make)


def _drop_half_the_batch(monkeypatch):
    import repro.serve.loop as loop

    real = loop.generate

    def broken(cfg, params, prompts, max_new_tokens=16, **kw):
        tokens, info = real(cfg, params, prompts, max_new_tokens, **kw)
        return tokens[: tokens.shape[0] // 2], info

    monkeypatch.setattr(loop, "generate", broken)


@pytest.mark.parametrize("fault", [_alter_one_token, _drop_half_the_batch,
                                   _alter_the_last_token],
                         ids=lambda f: f.__name__)
def test_planted_faults_are_not_correct(mla_bench, cpu, monkeypatch, capsys,
                                        fault):
    fault(monkeypatch)
    assert _run(mla_bench, cpu, monkeypatch, capsys)["correct"] is False


def test_one_wrong_token_a_request_trips_the_far_share(mla_bench, cpu,
                                                       monkeypatch, capsys):
    """The sparse fault puts one of each request's ``NEW`` tokens past
    ``far_gap``: the far share reads about 1/NEW, over its limit, where
    the program's reads 0."""
    clean = _run(mla_bench, cpu, monkeypatch, capsys)["checks"]
    assert clean["served_far_share"]["value"] == 0.0
    _alter_the_last_token(monkeypatch)
    far = _run(mla_bench, cpu, monkeypatch, capsys)["checks"][
        "served_far_share"]
    assert far["value"] > far["limit"]
    assert far["value"] <= 100.0 / NEW


def test_control_fails_and_the_program_passes(mla_bench, cpu, monkeypatch):
    import readings

    here, root = mla_bench
    monkeypatch.setattr(harness, "peaks", lambda kind, here=None: {})
    recs = list(readings.readings(CELL, [1, 2, 3], 3, devices=cpu, here=here,
                                  root=root))
    limit = harness.find_cell(CELL, here=here, root=root).params[
        "limits"]["served_slot_gap"]
    for r in recs:
        over = r["checks"]["served_slot_gap"] > limit
        assert over == (r["side"] == "control"), r
        if r["side"] == "program":
            assert r["checks"]["served_far_share"] == 0.0, r


@pytest.mark.parametrize("k,unanswered", [(8, ()), (3, ()), (8, (5,))])
def test_the_sample_takes_each_request_from_a_slot_of_its_own(
        mla_bench, cpu, k, unanswered):
    import dataclasses

    here, root = mla_bench
    cell = harness.find_cell(CELL, here=here, root=root)
    cell = dataclasses.replace(cell, params={**cell.params, "clients": 8,
                                             "check_requests": k})
    ctx = harness.Context(cell=cell, seed=2**33 + 5, devices=cpu, peaks={})
    t = cell.traffic.Traffic(ctx)
    for _ in range(3):
        tokens = np.ones((8, 4), np.int32)
        tokens[list(unanswered)] = -1
        t.batches.append({"prompts": None, "tokens": tokens})
    slots = [r for _, r in t._sample()]
    assert len(set(slots)) == len(slots) == min(k, 8 - len(unanswered))
    assert not set(slots) & set(unanswered)


def test_work_counts_match_a_hand_count():
    """Moonlight's published sizes, 16 of 64 experts held, counted by
    hand."""
    c = json.loads((HERE / "configs" / "moonlight_16b_a3b_e16.json")
                   .read_text())
    # q 2048x3072, kv_a 2048x576, kv_norm 512, kv_b 512x4096, o 2048x2048
    mla = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert work_mla_moe.mla_params(c) == mla == 13_763_072
    expert = 3 * 2048 * 1408                       # w1, w3, w2 of one expert
    assert work_mla_moe.expert_bytes(c) == 26 * 16 * expert * 2
    assert work_mla_moe.expert_bytes(c) == pytest.approx(7.20e9, rel=1e-3)
    per_token = 27 * (512 + 64) * 2                # latents, all layers
    assert per_token == 31_104
    assert work_mla_moe.latent_bytes(c, 32, 1000) == 32 * 1000 * per_token
    dense = mla + 3 * 2048 * 11264 + 2 * 2048
    moe = mla + 3 * 2048 * 2816 + 2 * 2048
    router = (64 * 2048 + 64) * 4
    top = (163840 * 2048 + 2048 + 32 * 2048) * 2
    want = (dense + 26 * moe) * 2 + 26 * router + 26 * 16 * expert * 2 + top
    assert work_mla_moe.decode_bytes(c, 32, 1000) == want + 32 * 1000 * \
        per_token
    # with the whole embedding: the chip's 10.33 GB of weights
    assert want + 163840 * 2048 * 2 == pytest.approx(10.33e9, rel=1e-3)
    w = work_mla_moe.generate_work(c, 32, 1024, 128)
    assert w["decode_steps"] == 127
    assert w["expert_bytes"] == 127 * work_mla_moe.expert_bytes(c)
    assert w["mla_bytes"] == sum(27 * mla * 2 + work_mla_moe.latent_bytes(
        c, 32, 1024 + i + 1) for i in range(127))


def test_reference_weights_are_the_served_weights(cpu):
    """The config module serves exactly the leaves the reference remakes, each
    routed expert keyed by its own index."""
    import jax

    from reference import moonlight as ref
    import weights

    cm = harness.load_module(HERE / "configs" / "moonlight_16b_a3b_e16.py")
    c = dict(TINY_MLA, first_held_expert=4)
    params = cm.make_params(c, cm.model_config(c), 7)
    lay = ref._layer_params(weights._words(7), jax.numpy.int32(1),
                            weights._items(c), "moe")
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["moe"]["w2"][1], np.float32),
        np.asarray(lay["moe/w2"]))
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["moe"]["router_bias"][1]),
        np.asarray(lay["moe/router_bias"]))
    assert np.abs(np.asarray(lay["moe/router_bias"])).max() < ref.BIAS
    whole = cm.make_params(dict(TINY_MLA, n_routed_experts=8), cm.model_config(
        dict(TINY_MLA, n_routed_experts=8)), 7)
    np.testing.assert_array_equal(
        np.asarray(whole["layers"]["moe"]["w1"][:, 4:]),
        np.asarray(params["layers"]["moe"]["w1"]))


SCOPES = {"mla", "moe/route", "moe/experts", "moe/shared", "moe/combine"}


def test_both_steps_name_their_scopes(mla_bench, cpu):
    """The compiled ``serve_step`` at the window's shapes, and the
    ``prefill_step``, carry every named scope in their ops' metadata."""
    import jax
    import jax.numpy as jnp

    import serve_scopes
    from repro.train import steps

    here, root = mla_bench
    cell = harness.find_cell(CELL, here=here, root=root)
    t = cell.traffic.Traffic(harness.Context(cell=cell, seed=3, devices=cpu,
                                             peaks={}))
    t.setup()
    found = {serve_scopes.scope_of(v)
             for v in serve_scopes.op_scopes(t).values()}
    assert SCOPES <= found
    prompts = jax.ShapeDtypeStruct((t.B, t.T), jnp.int32)
    text = jax.jit(steps.make_prefill_step(t.cfg)).lower(
        t.params, {"tokens": prompts}).compile().as_text()
    found = {serve_scopes.scope_of(v)
             for v in serve_scopes._INSTR.findall(text) for v in v[1:]}
    assert SCOPES <= found


def test_the_expert_weight_slices_sit_under_the_experts_scope(mla_bench,
                                                              cpu):
    """Each layer's slice of the held experts' weights, which the TPU
    copies out of the layer stack for the grouped matmul, carries
    ``moe/experts`` in both steps, so the experts' readers count it."""
    import re

    import jax
    import jax.numpy as jnp

    import serve_scopes
    from repro.serve.kvcache import grow_cache
    from repro.train import steps

    here, root = mla_bench
    cell = harness.find_cell(CELL, here=here, root=root)
    t = cell.traffic.Traffic(harness.Context(cell=cell, seed=3, devices=cpu,
                                             peaks={}))
    t.setup()
    cfg, params = t.cfg, t.params
    prompts = jax.ShapeDtypeStruct((t.B, t.T), jnp.int32)

    def cache_of(p, x):
        cache, _ = steps.make_prefill_step(cfg)(p, {"tokens": x})
        return grow_cache(cache, t.new, window=cfg.sliding_window)

    cache = jax.eval_shape(cache_of, params, prompts)
    texts = [
        jax.jit(steps.make_prefill_step(cfg)).lower(
            params, {"tokens": prompts}).compile().as_text(),
        jax.jit(steps.make_serve_step(cfg)).lower(
            params, cache, jax.ShapeDtypeStruct((t.B, 1), jnp.int32)
        ).compile().as_text(),
    ]
    G, Dm, F = (TINY_MLA["n_routed_experts"], TINY_MLA["hidden_size"],
                TINY_MLA["moe_intermediate_size"])
    one = re.compile(r"= \w+\[1,%d,(\d+),(\d+)\]\S* dynamic-slice\(.*"
                     r'op_name="([^"]*)"' % G)
    for text in texts:
        found = [m for m in one.findall(text)
                 if {int(m[0]), int(m[1])} == {Dm, F}]
        assert len(found) == 3                  # w1, w3, w2
        assert {serve_scopes.scope_of(m[2]) for m in found} == {
            "moe/experts"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(serve_step)/while/body/closed_call/moe/experts/jit(silu)/mul",
     "moe/experts"),
    ("ragged-dot-none", "moe/experts"),
    ("jit(serve_step)/while/body/closed_call/moe/route/jit(_take)/gather",
     "moe/route"),
    ("jit(serve_step)/while/body/closed_call/mla/squeeze", "mla"),
    ("jit(serve_step)/while/body/closed_call/dot_general", "none"),
    ("", "none"),
])
def test_scope_of(op_name, scope):
    import serve_scopes

    assert serve_scopes.scope_of(op_name) == scope
