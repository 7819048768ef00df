"""Logical-axis sharding rules (the pod-scale 'provisioning' policy).

The paper's thesis is that communication should be provisioned to match what
the dataflow needs. At pod scale that decision *is* the logical→mesh axis
mapping below: which tensor dims ride the ICI (``data``/``model`` axes inside
a pod), which must cross the DCN (``pod`` axis), and which stay local.

Hierarchy (mirrors Plaid's local/global datapaths):
  * motif-internal edges  -> stay in VMEM (fused kernels; no mesh axis)
  * intra-pod edges       -> 'data' (batch/FSDP) and 'model' (TP/EP) ICI axes
  * inter-pod edges       -> 'pod' (pure data parallelism; gradient sync only)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import Spec, spec_map

Axis = Union[None, str, Tuple[str, ...]]


def logical_rules(cfg, *, multi_pod: bool = False) -> Dict[str, Axis]:
    """Map logical tensor-dim names to mesh axes for this architecture."""
    rules: Dict[str, Axis] = {
        # activations
        "batch": ("pod", "data") if multi_pod else ("data",),
        "seq": None,
        "cache_seq": ("data",),  # long-context (B=1) decode: shard the KV cache
        # params — tensor/expert parallel over the 'model' ICI axis
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "expert": ("model",),
        # params — FSDP over the 'data' ICI axis (never over the DCN 'pod' axis:
        # pods keep full replicas and sync gradients only — the 'global
        # datapath' carries inter-motif traffic only)
        "embed": ("data",) if cfg.fsdp else None,
        # never sharded
        "layers": None,
        "state": None,
        "conv": None,
        "dt": None,
    }
    return rules


# production mesh extents — used for divisibility fallbacks (odd vocab sizes
# like whisper's 51865 or granite's 49155 fall back to replicated)
PROD_AXIS_SIZES = {"pod": 2, "data": 16, "model": 16}


def _pspec_for(
    axes: Tuple[Optional[str], ...],
    rules: Dict[str, Axis],
    shape,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> P:
    sizes = axis_sizes or PROD_AXIS_SIZES
    parts = []
    used = set()  # a mesh axis may shard at most one dim; first dim wins
    for dim, name in zip(shape, axes):
        if name is None:
            parts.append(None)
            continue
        mapped = rules.get(name)
        if mapped is None:
            parts.append(None)
            continue
        if isinstance(mapped, str):
            mapped = (mapped,)
        if any(a in used for a in mapped):
            parts.append(None)
            continue
        extent = 1
        for a in mapped:
            extent *= sizes.get(a, 1)
        if dim % extent != 0:
            parts.append(None)  # replicate rather than pad unevenly
            continue
        used.update(mapped)
        parts.append(mapped if len(mapped) > 1 else mapped[0])
    return P(*parts)


def shardings_for(spec_tree, mesh: Mesh, cfg, *, multi_pod: bool = False):
    """Spec tree -> NamedSharding tree (divisibility-safe).

    If a dim is not divisible by its mesh-axis extent we keep GSPMD's padded
    sharding *only* for weight matrices (2D+); 1D scales fall back to
    replicated to avoid pathological layouts.
    """
    rules = logical_rules(cfg, multi_pod=multi_pod)
    sizes = {a: mesh.shape[a] for a in mesh.axis_names}

    def one(s: Spec):
        ps = _pspec_for(s.axes, rules, s.shape, sizes)
        return NamedSharding(mesh, ps)

    return spec_map(one, spec_tree)


def pspecs_for(spec_tree, cfg, *, multi_pod: bool = False, axis_sizes=None):
    rules = logical_rules(cfg, multi_pod=multi_pod)
    return spec_map(lambda s: _pspec_for(s.axes, rules, s.shape, axis_sizes), spec_tree)


def batch_pspec(global_batch: int, mesh: Mesh, multi_pod: bool) -> P:
    """Batch-dim spec; falls back to replicated if batch doesn't divide."""
    axes = ("pod", "data") if multi_pod else ("data",)
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    if global_batch % total == 0:
        return P(axes if len(axes) > 1 else axes[0])
    if global_batch % mesh.shape["data"] == 0:
        return P("data")
    return P(None)

