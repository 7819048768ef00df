"""The work that each roofline or MFU divides by, counted from shapes.

Nothing here reads the program: the CGRA counts come from the pool's
mapping records, the model counts from the configuration file's
published sizes (Hugging Face key names).  A later change to how the
program does the work leaves these numbers as they are.
"""
from __future__ import annotations

from typing import Dict, Iterable

#: bytes of one simulated value (the device path computes in float32)
VALUE_BYTES = 4
#: bytes of one field of a mapping's description (an int32 or a float32)
FIELD_BYTES = 4
#: bytes of one weight or cache element served in bfloat16
BF16 = 2


# -- CGRA mapping verification ----------------------------------------------


def mapping_bytes(record: dict, iterations: int) -> int:
    """Least bytes one simulation of ``record`` must move: every
    (node, iteration) execution reads each operand it has (at most
    three) and writes its value; and the mapping's description is read
    once: per node its opcode, issue cycle and leaf; per edge its source,
    distance and operand slot; per route step its resource and cycle."""
    timed = {int(n) for n in record["time"]}
    edges = record["dfg"]["edges"]
    reads = sum(1 for _, dst, _, _ in edges if int(dst) in timed)
    executions = len(timed) * iterations
    values = (executions + reads * iterations) * VALUE_BYTES
    steps = sum(len(p) for p in record["routes"].values())
    fields = 3 * len(record["dfg"]["nodes"]) + 3 * len(edges) + 2 * steps
    return values + fields * FIELD_BYTES


def bucket_bytes(records: Iterable[dict], iterations: int) -> int:
    return sum(mapping_bytes(r, iterations) for r in records)


# -- dense decoder (Qwen3 layout) --------------------------------------------


def sizes(c: Dict) -> Dict[str, int]:
    return {
        "D": c["hidden_size"], "H": c["num_attention_heads"],
        "KV": c["num_key_value_heads"], "hd": c["head_dim"],
        "F": c["intermediate_size"], "V": c["vocab_size"],
        "L": c["num_hidden_layers"],
    }


def layer_matmul_params(c: Dict) -> int:
    s = sizes(c)
    q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
    return s["D"] * (q + 2 * kv) + q * s["D"] + 3 * s["D"] * s["F"]


def param_count(c: Dict) -> int:
    """Every weight the chip holds: the layers, the embedding (which is
    also the output head) and the final norm."""
    s = sizes(c)
    norms = 2 * s["D"] + 2 * s["hd"]
    return (s["L"] * (layer_matmul_params(c) + norms) + s["V"] * s["D"]
            + s["D"])


def _attn_flops(c: Dict, query_keys: int) -> int:
    """Q.K and P.V over ``query_keys`` (query, key) pairs, every layer."""
    s = sizes(c)
    return 4 * s["L"] * s["H"] * s["hd"] * query_keys


def prefill_flops(c: Dict, batch: int, prompt: int) -> int:
    """One prefill call: every matmul of every layer over the prompt,
    causal attention (each query sees itself and what precedes it), and
    the output head on the last token only."""
    s = sizes(c)
    mm = 2 * batch * prompt * s["L"] * layer_matmul_params(c)
    attn = _attn_flops(c, batch * prompt * (prompt + 1) // 2)
    return mm + attn + 2 * batch * s["V"] * s["D"]


def decode_flops(c: Dict, batch: int, context: int) -> int:
    """One decode step in which each sequence attends ``context`` cached
    positions (its new one included)."""
    s = sizes(c)
    mm = 2 * batch * (s["L"] * layer_matmul_params(c) + s["V"] * s["D"])
    return mm + _attn_flops(c, batch * context)


def decode_bytes(c: Dict, batch: int, context: int) -> int:
    """Least bytes of one decode step: every weight once, in bf16, and
    the keys and values of ``context`` positions of every sequence in
    every layer, in bf16."""
    s = sizes(c)
    kv = s["L"] * batch * context * 2 * s["KV"] * s["hd"] * BF16
    return param_count(c) * BF16 + kv


def generate_work(c: Dict, batch: int, prompt: int, new: int) -> Dict:
    """Work of one ``generate`` call: one prefill and ``new - 1`` decode
    steps, step ``i`` attending ``prompt + i + 1`` positions."""
    ctx = [prompt + i + 1 for i in range(new - 1)]
    return {
        "prefill_flops": prefill_flops(c, batch, prompt),
        "decode_steps": len(ctx),
        "decode_flops": sum(decode_flops(c, batch, n) for n in ctx),
        "decode_bytes": sum(decode_bytes(c, batch, n) for n in ctx),
    }
