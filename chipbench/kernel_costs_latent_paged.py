"""What latent (MLA) attention over a paged pool (``ops.latent_paged_
attention``, ``serving.paging.PagedLatentCache``) has to move and to compute
at the least, from shapes: the numerators of
``latent_paged_read_roofline_pct`` and ``latent_prefill_roofline_pct``,
beside ``kernel_costs.py`` and under its rule. They count THE WORK, not the
implementation, and only what MUST be moved or multiplied, so that no share
can pass 100%."""

from __future__ import annotations

from typing import Any, Dict


def latent_paged_read_bytes(rows: float, config: Dict[str, Any],
                            itemsize: int = 2) -> float:
    """Bytes ``latent_paged_read`` must bring in for the decode steps
    counted, where ``rows`` are the rows the live chains hold, summed over
    the layers (the program's ``latent_rows``: a slot of length n reads n +
    1 in each): ``kv_lora_rank + qk_rope_head_dim`` = 576 columns a row,
    1,152 B. The 64 columns of padding a stored row has, the rest of a
    chain's last page, the queries, the block table and the outputs are the
    kernel's own overhead and are not counted."""
    return rows * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * itemsize


def latent_prefill_flops(tokens: int, config: Dict[str, Any]) -> float:
    """FLOPs the attention of a COLD prompt of ``tokens`` real tokens must
    spend in the expanded form, over all layers (every layer attends): a
    query at position p sees p + 1 keys (the causal half, diagonal
    included), ``qk_nope_head_dim + qk_rope_head_dim`` columns of scores and
    ``v_head_dim`` of values a query head, two FLOPs a multiply-add. The
    expansion of K and V from the latents (once a layer: 2 x tokens x
    ``kv_lora_rank`` x heads x (``qk_nope_head_dim + v_head_dim``)) runs
    outside the kernel and is not the kernel's work; the padding to the
    bucket and the rest of a block above the diagonal are the kernel's own
    overhead: neither is counted."""
    pairs = config["num_hidden_layers"] * (tokens * (tokens + 1) // 2)
    return 2.0 * pairs * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
