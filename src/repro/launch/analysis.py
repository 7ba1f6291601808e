"""Shared analysis helpers for the dry-run and the roofline benchmark."""

from __future__ import annotations

import numpy as np

import jax

# TPU v5e hardware constants (per chip) — the assignment's roofline basis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def tree_param_count(tree) -> int:
    return int(sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)))


def tree_param_bytes(tree) -> int:
    return int(sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def active_param_count(params_tree, model) -> int:
    """MoE-aware active parameter count (routed experts scaled by top_k/E)."""
    from repro.utils.tree import named_leaves

    moe = getattr(getattr(model, "cfg", None), "moe", None)
    total = 0.0
    for path, leaf in named_leaves(params_tree):
        n = float(np.prod(leaf.shape))
        if moe is not None and "experts/" in path:
            n *= moe.top_k / moe.n_experts
        total += n
    return int(total)


def model_flops_reference(n_params_active: int, n_tokens: int, kind: str) -> float:
    """MODEL_FLOPS yardstick: 6·N·D for training, 2·N·D for fwd-only.

    (For DFA the backward differs structurally from BP — the ratio
    HLO_FLOPs / MODEL_FLOPS in the report surfaces exactly that.)"""
    if kind == "train":
        return 6.0 * n_params_active * n_tokens
    return 2.0 * n_params_active * n_tokens


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float, chips: int) -> dict:
    t_compute = flops / (chips * PEAK_FLOPS_BF16)
    t_memory = hbm_bytes / (chips * HBM_BW)
    t_coll = coll_bytes / (chips * ICI_BW)
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    bound = max(t_compute, t_memory, t_coll)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "bound_s": bound,
        # roofline fraction: how much of the bound is useful compute
        "compute_fraction": (t_compute / bound) if bound > 0 else 0.0,
    }


def cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca) if ca else {}


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out
