"""Tiny configurations and cells the benchmark's tests run on the CPU."""
from __future__ import annotations

#: a Qwen3-layout config small enough for the CPU
TINY_QWEN = {
    "name": "qwen3_tiny", "kind": "dense_decoder",
    "source": "test-only reduction of configs/qwen3_14b_d10.json",
    "program_arch": "qwen3_14b", "reference": "reference/qwen3.py",
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": True, "vocab_size": 256,
}

#: tiny traffic mixes, each read by one of the real generators
TINY_TRAFFIC = {
    "verify_tiny": {"generator": "verify_sweep", "backend": "jnp",
                    "check_values": 1000},
    "serve_tiny": {"generator": "serve_closed", "prompt_len": 16,
                   "new_tokens": 4},
}

TINY_CELLS = {
    "verify_sweep.tiny": {
        "config": "table2", "traffic": "verify_tiny",
        "limits": {"verdicts_wrong": 0, "values_missing": 0,
                   "value_gap": 0.004, "answers_missing": 0}},
    "serve_decode.tiny": {
        "config": "qwen3_tiny", "traffic": "serve_tiny",
        "clients": 2, "check_requests": 2,
        "limits": {"served_logit_gap": 0.01}},
}

#: the metrics each tiny cell reports: an entry of BENCHMARK.json of the
#: same name gets the tiny cell added to its ``workloads``; one it lacks is
#: added whole
TINY_METRICS = {
    "end_to_end": [
        {"name": "verify_mappings_per_s", "unit": "mappings/s",
         "better": "higher", "bound": 0.03, "source": "host_clock",
         "workloads": ["verify_sweep.tiny"]},
        {"name": "output_tokens_per_s", "unit": "tokens/s",
         "better": "higher", "bound": 0.03, "source": "host_clock",
         "workloads": ["serve_decode.tiny"]},
    ],
    "per_layer": [
        {"name": n, "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "test", "moves": "output_tokens_per_s",
         "workloads": ["serve_decode.tiny"]}
        for n in ("prefill_mfu", "decode_mfu", "decode_roofline",
                  "device_idle.serve")
    ] + [
        {"name": n, "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "test", "moves": "verify_mappings_per_s",
         "workloads": ["verify_sweep.tiny"]}
        for n in ("verify_host_ms", "cycle_loop_ms", "cycle_loop_roofline",
                  "device_idle.verify")
    ],
}
