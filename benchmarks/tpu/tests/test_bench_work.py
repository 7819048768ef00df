"""The work counters the rooflines and MFUs divide by."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import bench_fixtures
import harness
import work

HERE = Path(__file__).resolve().parents[1]
POOL = HERE / "data" / "table2"


def brute_force_bytes(record: dict, iterations: int) -> int:
    """Walk every cycle of the mapping's horizon and count each firing's
    operand reads and value write, then the description's fields."""
    ii = record["ii"]
    time = {int(n): t for n, t in record["time"].items()}
    edges = record["dfg"]["edges"]
    horizon = record["makespan"] + ii * iterations + 2
    values = 0
    for t in range(horizon):
        for n, tn in time.items():
            if t >= tn and (t - tn) % ii == 0 and (t - tn) // ii < iterations:
                values += 1 + sum(1 for e in edges if int(e[1]) == n)
    fields = 0
    for _ in record["dfg"]["nodes"]:
        fields += 3                     # opcode, issue cycle, leaf
    for _ in edges:
        fields += 3                     # source, distance, operand slot
    for path in record["routes"].values():
        fields += 2 * len(path)         # resource, cycle
    return 4 * values + 4 * fields


def test_mapping_bytes_match_a_brute_force_count_on_a_tiny_bucket():
    files = sorted(POOL.glob("*.json"))[:3]
    records = [json.loads(p.read_text())["mappings"][0] for p in files]
    bucket = [records[0], records[1], records[2], records[0]]
    for iters in (1, 3):
        want = sum(brute_force_bytes(r, iters) for r in bucket)
        assert work.bucket_bytes(bucket, iters) == want


@pytest.fixture(params=["qwen3_14b_d10", "qwen3_tiny"])
def qwen(request):
    """``(config file, the program's ModelConfig at its sizes)``."""
    c = (json.loads((HERE / "configs" / "qwen3_14b_d10.json").read_text())
         if request.param == "qwen3_14b_d10" else bench_fixtures.TINY_QWEN)
    cm = harness.load_module(HERE / "configs" / "qwen3_14b_d10.py")
    return c, cm.model_config(c)


def test_qwen_counters_match_the_program_param_count(qwen):
    c, cfg = qwen
    D, L, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    norms = L * (2 * D + 2 * hd)
    # the program's count has no final norm
    assert work.param_count(c) == cfg.param_count() + D
    # a decode step over an empty cache: two FLOPs per matmul weight
    assert work.decode_flops(c, 1, 0) == 2 * (cfg.param_count() - norms)
    assert work.decode_bytes(c, 3, 0) == 2 * (cfg.param_count() + D)
    # a one-token prefill is the same matmuls plus one (q, k) pair a layer
    attn = 4 * L * c["num_attention_heads"] * hd
    assert work.prefill_flops(c, 1, 1) == 2 * (cfg.param_count() - norms) \
        + attn


def test_published_qwen3_14b_sizes():
    from repro.configs import get_config

    c = json.loads((HERE / "configs" / "qwen3_14b_d10.json").read_text())
    # 14.8 B published, with an output head of its own beside the embedding
    full = get_config("qwen3_14b")
    head = c["vocab_size"] * c["hidden_size"]
    assert full.param_count() + head == pytest.approx(14.77e9, rel=0.01)
    # 10 layers of 330.3 M and the 777.9 M embedding: 4.08 B, 8.16 GB
    assert work.layer_matmul_params(c) == pytest.approx(330.3e6, rel=1e-3)
    assert work.decode_bytes(c, 1, 0) == pytest.approx(8.16e9, rel=1e-3)
    # the KV cache takes 40 KiB a token over the 10 layers here
    per_token = work.decode_bytes(c, 1, 1) - work.decode_bytes(c, 1, 0)
    assert per_token == 10 * 2 * 8 * 128 * 2


def test_generate_work_sums_its_steps():
    c = bench_fixtures.TINY_QWEN
    w = work.generate_work(c, 2, 16, 4)
    assert w["decode_steps"] == 3
    assert w["decode_bytes"] == sum(work.decode_bytes(c, 2, 16 + i + 1)
                                    for i in range(3))
    assert w["prefill_flops"] == work.prefill_flops(c, 2, 16)
