"""What ``ops.gqa_attention``'s kernels have to move at the least where K
and V heads are of unequal width and the kinds of layer hold different
numbers of them (``mimo-v2.5``), from shapes: the numerators of the
``gqa_uneven_*_roofline_pct`` metrics, beside ``kernel_costs_gqa.py`` and
under its rule: count only what MUST be read or multiplied, so that no
share can pass 100%. A layout that pads a stored row shows as lost share:
the columns counted are the published ``head_dim + v_head_dim`` a head."""

from __future__ import annotations

from typing import Any, Dict


def gqa_uneven_read_bytes(full_rows: float, ring_rows: float,
                          config: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes ``gqa_attention_read`` must bring in for one decode step. A
    row is a token's K and its V of one layer: ``num_key_value_heads x
    (head_dim + v_head_dim)`` columns in a full layer (4 x 320 x 2 B =
    2,560 B), ``swa_num_key_value_heads x (swa_head_dim + swa_v_head_dim)``
    in a ring (8 x 320 x 2 B = 5,120 B). ``full_rows`` and ``ring_rows`` are
    the rows the live slots hold, already summed over the layers of each
    kind (the program's ``kv_full_rows`` and ``kv_ring_rows``: a slot of
    length n reads n + 1 in a full layer, at most ``sliding_window`` in a
    ring). The rest of a block that a slot's last copy brings in, the
    queries, the sinks and the outputs are the kernel's own overhead and
    are not counted."""
    full = config["num_key_value_heads"] * (
        config["head_dim"] + config["v_head_dim"])
    ring = config["swa_num_key_value_heads"] * (
        config["swa_head_dim"] + config["swa_v_head_dim"])
    return (full_rows * full + ring_rows * ring) * itemsize


def gqa_uneven_prefill_flops(tokens: int, config: Dict[str, Any]) -> float:
    """FLOPs ``gqa_attention_prefill`` must spend on a prompt of ``tokens``
    real tokens: the kernel attends the FULL layers (a window layer's band
    is attended in ``jax.numpy``), where a query at position p sees p + 1
    keys: the causal half, diagonal included; ``head_dim`` columns of
    scores and ``v_head_dim`` of values a query head, two FLOPs a
    multiply-add. The padding to the bucket, the rest of a block above the
    diagonal and whatever a product spends on columns that are not there
    are the kernel's own overhead and are not counted."""
    n_full = sum(1 for w in config["hybrid_layer_pattern"] if not w)
    pairs = n_full * (tokens * (tokens + 1) // 2)
    return 2.0 * pairs * config["num_attention_heads"] * (
        config["head_dim"] + config["v_head_dim"])
