"""Attention over the preallocated slotted KV cache — the decode-path op.

The serving engine's attention (pytorch_distributed_tpu.serving): queries
for the T newly arrived tokens of each sequence attend over that sequence's
cache slot. The cache is the WHOLE resident array ``[L, S, Tmax, H*D]``
(``serving.kv_cache``) and the op is told which layer it serves: the new
rows are scattered into it at ``[layer, slot, position]`` and K/V are read
from it as they lie, so under jit with the cache donated the write is in
place and no layer's slab is sliced out, re-laid-out or rebuilt.

Why the heads are folded into the minor dimension. The TPU tiles the two
minor-most dimensions and pads the last to 128 lanes; a ``[..., H, D]``
cache with D = 64 would pad to twice its bytes, so the compiler stores it
positions-minor instead and every step then copies each layer's slab to a
head-dim-minor layout to scatter one row into it, and back (PERF.md,
PR 25: 37.8 of a 51 ms decode step). ``H*D`` = 768 needs no padding, a
token's K (or V) is one contiguous row, and the row write and the
attention read use the same layout.

Attending against folded rows. Splitting the minor dimension back into
``[H, D]`` would be that re-layout again, so the per-head contraction is
done on the 768-wide rows as stored: the T queries of a sequence become
``H*T`` block-diagonal rows (row ``(h, t)`` keeps head h's D columns of
token t and is zero elsewhere), scores are ONE matmul of those rows
against the stored K, and probabilities x V is one matmul against the
stored V whose result keeps, for row ``(h, t)``, only head h's columns.
Both contract in the compute dtype with float32 accumulation on the MXU;
the other heads' columns cost H times the useful FLOPs, which at decode
(T = 1) and speculative verify (T = k+1) is far below the time the read of
K and V takes. It would be wrong at a prefill bucket (T = 512: a
``[S, H*T, H*D]`` operand), and a fresh prefill does not need it: with no
``position_offset`` every sequence starts at position 0, the T new tokens
can only see each other, and attention is the plain causal T x T program
over ``k_new`` / ``v_new`` with the cache written and never read.

Masking invariant: a query at global position p attends exactly the cache
positions <= p. Positions beyond a sequence's current length are never
attended because every attended position was either written by this
request's prefill or by one of its earlier decode steps (slots are reused
without zeroing — the mask, not memset, is the isolation boundary).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["cached_attention"]


def _softmax_pv(scores, visible, v, dtype, spec):
    """Masked float32 softmax of ``scores`` over its last axis, then the
    contraction ``spec`` of the probabilities (in ``dtype``) with ``v``,
    accumulated in float32."""
    scores = jnp.where(visible, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum(spec, probs, v, preferred_element_type=jnp.float32)


def cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    position_offset: Optional[jax.Array],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write ``k_new``/``v_new`` into layer ``layer`` of the cache, attend.

    Args:
      q, k_new, v_new: ``[B, T, H, D]`` projections for the T new tokens.
      k_cache, v_cache: ``[L, S, Tmax, H*D]`` — the whole slotted cache;
        batch row b is slot b, so ``B == S``.
      layer: which layer's rows to write and read (static).
      position_offset: ``[B]`` int32 — global position of each sequence's
        first new token (the slot's current length at decode). ``None``
        states statically that every sequence is fresh (a prefill into an
        empty slot): the new tokens sit at positions ``0..T-1`` and attend
        only each other.

    Returns:
      ``(out [B, T, H, D], k_cache, v_cache)`` with layer ``layer`` updated
      at positions ``offset .. offset+T-1`` of every slot.
    """
    B, T, H, D = q.shape
    C = H * D
    if k_cache.shape[1] != B or k_cache.shape[3] != C:
        raise ValueError(
            f"cache {k_cache.shape} does not hold {B} slots of {H} x {D} "
            f"wide rows: batch row b is slot b of a [L, S, Tmax, H*D] cache"
        )
    S = k_cache.shape[2]
    dtype = q.dtype
    k_rows = k_new.reshape(B, T, C).astype(k_cache.dtype)
    v_rows = v_new.reshape(B, T, C).astype(v_cache.dtype)
    scale = D ** -0.5

    if position_offset is None:
        k_cache = k_cache.at[layer, :, :T].set(k_rows)
        v_cache = v_cache.at[layer, :, :T].set(v_rows)
        # what a later decode step will read back: the rows as stored
        k = k_rows.astype(dtype).reshape(B, T, H, D)
        v = v_rows.astype(dtype).reshape(B, T, H, D)
        scores = jnp.einsum(
            "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
        ) * scale
        causal = jnp.tril(jnp.ones((T, T), bool))
        out = _softmax_pv(scores, causal, v, dtype, "bhts,bshd->bthd")
        return out.astype(dtype), k_cache, v_cache

    # per-sequence write positions [B, T]
    pos = position_offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    k_cache = k_cache.at[layer, b_idx, pos].set(k_rows)
    v_cache = v_cache.at[layer, b_idx, pos].set(v_rows)

    # own[h, c]: column c of a folded row belongs to head h
    own = (jnp.arange(C, dtype=jnp.int32) // D)[None] == jnp.arange(
        H, dtype=jnp.int32
    )[:, None]
    q_rows = jnp.where(
        own[None, :, None], q.reshape(B, 1, T, C), 0
    ).reshape(B, H * T, C)
    scores = jnp.einsum(
        "bnc,bsc->bns", q_rows, k_cache[layer].astype(dtype),
        preferred_element_type=jnp.float32,
    ).reshape(B, H, T, S) * scale
    # causal over global positions: key s visible iff s <= query position
    visible = (
        jnp.arange(S, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
    )  # [B, T, S]
    # [B, H, T, C]: row (h, t) against every head's columns of V
    out = _softmax_pv(
        scores, visible[:, None], v_cache[layer].astype(dtype), dtype,
        "bhts,bsc->bhtc",
    )
    out = jnp.where(own[None, :, None], out, 0).sum(axis=1)
    return out.astype(dtype).reshape(B, T, H, D), k_cache, v_cache
