"""What the KDA layers of a hybrid stack (``ops.kda``,
``serving.state_cache``) have to move and to compute at the least, from
shapes: the numerators of ``kda_decode_roofline_pct`` and
``kda_prefill_roofline_pct``, beside ``kernel_costs.py`` and under its rule.
They count THE WORK, not the implementation: the same bytes and FLOPs
whether XLA fusions or a Pallas kernel run under the scope, and only what
MUST be moved or multiplied, so that no share can pass 100%."""

from __future__ import annotations

from typing import Any, Dict


def _kda(config: Dict[str, Any]):
    linear = config["linear_attn_config"]
    return (len(linear["kda_layers"]), linear["num_heads"],
            linear["head_dim"], linear["short_conv_kernel_size"])


def kda_slot_bytes(config: Dict[str, Any], tail_itemsize: int = 2) -> int:
    """Bytes a decode step must move for ONE live slot: every KDA layer's
    float32 state ``[H, d, d]`` read and written (a rank-one update changes
    every element), and the convolution's tail (``K - 1`` rows of the ``3 *
    H * d`` channels of q, k and v) read. 6 x (2 x 32 x 128 x 128 x 4 + 3 x
    12,288 x 2) = 25.6 MB at Kimi-Linear's sizes. The tail's write, the
    idle slots' states (``serving.state_cache`` moves those too) and the
    step's activations are overhead and are not counted."""
    layers, H, d, K = _kda(config)
    return layers * (2 * H * d * d * 4 + (K - 1) * 3 * H * d * tail_itemsize)


def kda_decode_bytes(live_slots: float, config: Dict[str, Any]) -> float:
    """``kda_slot_bytes`` for the live slots of the decode steps counted
    (the program's ``live_slots`` on ``pdt.engine.decode``, summed)."""
    return live_slots * kda_slot_bytes(config)


def kda_prefill_flops(tokens: int, config: Dict[str, Any]) -> float:
    """FLOPs the CHUNKED form of the gated delta rule must spend on a
    prompt of ``tokens`` real tokens, two a multiply-add, a token a head
    with chunks of C tokens (``assumed.kda_chunk``, which ``families
    /kimi_linear.py`` holds to the program's ``ops.kda.CHUNK``) and d = d_k
    = d_v: the two C x C matrices of pairwise products (``A_kk`` and
    ``A_qk``, half of each below the diagonal: 2 x C d), the solve applied
    to ``[beta V | beta K e^g]`` as a triangular product (C x 2d / 2 x 2 =
    2 C d), ``W_k S``, ``(Q e^g) S`` and ``K^T U`` against the state (3 x 2
    d d), and ``A_qk U`` (half: C d). The inverse of the unit triangular
    matrix, the exponentials, the convolution, the pad to the bucket and to
    whole chunks are the implementation's own and are not counted."""
    layers, H, d, _ = _kda(config)
    C = config["assumed"]["kda_chunk"]
    a_token_a_head = 2 * C * d + 2 * C * d + 6 * d * d + C * d
    return float(tokens) * layers * H * a_token_a_head


def latent_rows_bytes(rows: float, config: Dict[str, Any],
                      itemsize: int = 2) -> float:
    """Bytes ``latent_attention_read`` must bring in for the decode steps
    counted, where ``rows`` are the rows the live slots hold in the MLA
    layers, already summed over THOSE layers (the program's ``latent_rows``:
    a slot of length n reads n + 1 in each): ``kernel_costs
    .latent_read_bytes`` with the layers that read counted and not every
    layer of the stack."""
    return rows * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * itemsize
