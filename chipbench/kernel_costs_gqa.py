"""What the kernels of the cache of two depths (``ops.gqa_attention``,
``serving.window_cache``) have to move at the least, from shapes: the
numerators of their ``<kernel>_roofline_pct`` metrics, beside
``kernel_costs.py`` and under its rule: count only what MUST be read, so
that no share can pass 100%."""

from __future__ import annotations

from typing import Any, Dict


def gqa_read_bytes(full_rows: float, ring_rows: float,
                   config: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes ``gqa_attention_read`` must bring in for one decode step. A
    row is a token's K and its V of one layer, ``num_key_value_heads *
    head_dim`` wide each (8 x 128 x 2 B x 2 = 4,096 B). ``full_rows`` are
    the rows the live slots hold in the full layers (a slot of length n
    reads n + 1: its new row is read back from the cache), ``ring_rows``
    those in the rings (at most ``sliding_window`` a slot a layer), both
    already summed over their layers (the program's ``kv_full_rows`` and
    ``kv_ring_rows``). The rest of a 512-row block that a slot's last copy
    brings in, the queries and the outputs are the kernel's own overhead
    and are not counted."""
    row = 2 * config["num_key_value_heads"] * config["head_dim"] * itemsize
    return (full_rows + ring_rows) * row


def gqa_prefill_flops(tokens: int, config: Dict[str, Any]) -> float:
    """FLOPs ``gqa_attention_prefill`` must spend on a prompt of ``tokens``
    real tokens: the kernel attends the FULL layers (a window layer's band
    is attended in ``jax.numpy``), where a query at position p sees p + 1
    keys: the causal half, diagonal included; scores and values, two FLOPs
    a multiply-add, ``num_attention_heads * head_dim`` columns. The padding
    to the bucket and the rest of a block above the diagonal are the
    kernel's own overhead and are not counted."""
    n_full = sum(t == "full_attention" for t in config["layer_types"])
    pairs = n_full * (tokens * (tokens + 1) // 2)
    return 4.0 * pairs * config["num_attention_heads"] * config["head_dim"]
