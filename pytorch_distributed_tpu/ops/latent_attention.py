"""Attention over a latent (MLA) cache — DeepSeek-V2 section 2.1.

A token's cached state in one layer is ONE row shared by all heads: the
compressed key/value latent ``c_kv`` (after its RMS norm, ``d_c`` wide)
followed by the rotary key ``k_r`` (after rotation, ``d_r`` wide), padded
with zeros to a whole number of 128 lanes: ``[c_kv | k_r | 0]``. 512 + 64 =
576 is no multiple of 128: stored 576 wide, the TPU compiler re-lays the
whole cache out around every one-row write (a 3.0 GB temporary beside a
2.7 GB cache; compile result for the described v5e, PERF.md PR 33), and the
tiled layout pads the minor dimension to 640 in HBM anyway, so the row is
640 wide and the padding is the cache's own.

Two paths, the same mathematics (``W_kvb`` maps the latent to a head's
``[k_n | v]``):

  * fresh prefill (``position_offset=None``): nothing of the cache is read.
    K and V are EXPANDED from the new latents (``[k_n | v] = c_kv W_kvb``
    a head, ``k = [k_n | k_r]``) and the T new tokens attend each other,
    causally, the queries in blocks of ``_QUERY_BLOCK`` against the keys at
    or before the block's last position (at 8,192 positions 32 heads' T x T
    float32 scores would be 8.6 GB; a block's are at most 1.07 GB).
  * decode and verify (``position_offset [B]``): ABSORBED. ``q~ = q_n
    W_kvb[K, h]^T`` (``d_c`` a head), scores ``q~ . c_kv + q_r . k_r`` are
    one contraction of the row ``[q~ | q_r | 0]`` with the stored row, and
    the value is the stored row's first ``d_c`` columns: ``o = (P c_kv)
    W_kvb[V, h]``. A multi-query attention of ``H * T`` query rows over one
    shared 640-wide row a position: no K or V is ever expanded.

The absorbed read has two forms with one result, separated by where they
can run (``serving.kv_cache.LatentCache`` decides, nobody selects): the
dense contraction against every position of every slot, and the
lengths-aware Pallas TPU kernel ``latent_attention_read`` with the
scaffolding of ``ops.decode_attention``'s: the cache stays in HBM, offsets
and layer are scalar-prefetched, one grid step a slot copies in two deep
only the ``_BLOCK``-position blocks that hold a row below the slot's
offset, under one running softmax. Here the 32 query rows of a token
contract against the block as it lies (no block diagonal: the row is every
head's key), and probabilities x the block's first 512 columns is the
value. Masking invariant as in ``ops.decode_attention``: a query at global
position p sees exactly the positions <= p of its own slot.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.ops import decode_attention as _slotted

__all__ = [
    "latent_attention", "expanded_attention", "row_width", "yarn_inv_freq",
    "yarn_softmax_scale", "rotate",
]

_BLOCK = _slotted._BLOCK
#: queries a fresh prefill attends at a time
_QUERY_BLOCK = 1024
_LANES = 128


def row_width(d_c: int, d_r: int) -> int:
    """Width of a stored row: ``d_c + d_r`` padded to whole lanes."""
    return -(-(d_c + d_r) // _LANES) * _LANES


# -------------------------------------------------------------------------
# Rotary positions with YaRN's blended frequencies
# -------------------------------------------------------------------------
def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``dim / 2`` inverse frequencies: ``base^(-2i/dim)`` where a pair
    turns more than ``beta_fast`` times over the ``original`` positions,
    that over ``factor`` where it turns fewer than ``beta_slow`` times, and
    the linear ramp between the two correction dimensions in between (YaRN,
    arXiv:2309.00071, as DeepSeek-V3's released code computes it)."""
    freqs = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (freqs / factor * ramp + freqs * (1 - ramp)).astype(np.float32)


def yarn_softmax_scale(d_qk: int, factor: float, mscale_all_dim: float
                       ) -> float:
    """``d_qk^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return d_qk ** -0.5 * m * m


def rotate(x: jax.Array, positions: jax.Array, inv_freq) -> jax.Array:
    """Rotary embedding of ``x [B, T, ..., d_r]`` at ``positions [B, T]``:
    pair i is columns ``(i, i + d_r / 2)`` (the half-split convention),
    computed in float32, returned in x's dtype."""
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# -------------------------------------------------------------------------
# The op
# -------------------------------------------------------------------------
def latent_attention(
    q: jax.Array,
    latent: jax.Array,
    kv_b: jax.Array,
    rows: jax.Array,
    layer: int,
    position_offset: Optional[jax.Array],
    *,
    d_c: int,
    d_n: int,
    scale: float,
    kernel: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Write the T new tokens' rows into ``layer`` of the cache, attend.

    Args:
      q: ``[B, T, H, d_n + d_r]`` queries ``[q_n | q_r]``, ``q_r`` rotated.
      latent: ``[B, T, d_c + d_r]``: ``[c_kv | k_r]``, normed and rotated.
      kv_b: ``[d_c, H, d_n + d_v]``: the latent's map to ``[k_n | v]``.
      rows: ``[L, S, Tmax, row_width]``, the whole cache; ``B == S``.
      layer: static. ``position_offset``: ``[B]`` or None (fresh prefill).
      kernel / interpret: as ``ops.decode_attention.cached_attention``
        (``ops.decode_attention.kernel_reads`` says whether the kernel can
        serve a cache of this shape on this backend: the same conditions).

    Returns ``(y [B, T, H, d_v], rows)``.
    """
    B, T, H, _ = q.shape
    W = rows.shape[3]
    if rows.shape[1] != B or W != row_width(d_c, latent.shape[-1] - d_c):
        raise ValueError(
            f"cache {rows.shape} does not hold {B} slots of "
            f"{latent.shape[-1]}-wide latents padded to whole lanes")
    dtype = q.dtype
    new = jnp.pad(latent, ((0, 0), (0, 0), (0, W - latent.shape[-1]))
                  ).astype(rows.dtype)

    if position_offset is None:
        rows = rows.at[layer, :, :T].set(new)
        # what a later decode step will read back: the rows as stored
        return expanded_attention(q, new.astype(dtype), kv_b, d_c=d_c,
                                  d_n=d_n, scale=scale), rows

    pos = position_offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    before = rows
    rows = rows.at[layer, jnp.arange(B, dtype=jnp.int32)[:, None], pos].set(
        new)
    # absorb W_kvb's key half into the queries: [B, T, H, d_c]
    q_abs = jnp.einsum("bthn,chn->bthc", q[..., :d_n], kv_b[..., :d_n],
                       preferred_element_type=jnp.float32).astype(dtype)
    q_rows = jnp.concatenate([q_abs, q[..., d_n:]], axis=-1)
    q_rows = jnp.pad(q_rows, ((0, 0),) * 3 + ((0, W - q_rows.shape[-1]),))
    q_rows = q_rows.reshape(B, T * H, W)
    if kernel:
        # positions below the offset from the cache as it was, the T new
        # ones from the rows as stored: the read waits on no write
        o = _kernel_read(q_rows, new, before, position_offset, layer,
                         n_head=H, d_c=d_c, scale=scale, interpret=interpret)
    else:
        held = rows[layer].astype(dtype)                     # [S, Tmax, W]
        scores = jnp.einsum("bnw,bsw->bns", q_rows, held,
                            preferred_element_type=jnp.float32) * scale
        visible = (jnp.arange(held.shape[1], dtype=jnp.int32)[None, None]
                   <= pos[:, :, None])                       # [B, T, Tmax]
        visible = jnp.repeat(visible, H, axis=1)             # row t * H + h
        o = _slotted._softmax_pv(scores, visible, held[..., :d_c], dtype,
                                 "bns,bsc->bnc").astype(dtype)
    y = jnp.einsum("bthc,chv->bthv", o.reshape(B, T, H, d_c), kv_b[..., d_n:],
                   preferred_element_type=jnp.float32)
    return y.astype(dtype), rows


def expanded_attention(q, new, kv_b, *, d_c, d_n, scale):
    """K and V expanded from the latents ``new [B, T, >= d_c + d_r]``
    (``[c_kv | k_r | ...]``), causal attention among the T tokens, the
    queries in blocks: the fresh prefill, and the forward without a cache."""
    B, T, H, _ = q.shape
    dtype = q.dtype
    kv = jnp.einsum("btc,chn->bthn", new[..., :d_c], kv_b,
                    preferred_element_type=jnp.float32).astype(dtype)
    k_r = jnp.broadcast_to(new[:, :, None, d_c:d_c + q.shape[-1] - d_n],
                           (B, T, H, q.shape[-1] - d_n))
    # heads first: [B, H, T, D], so that both contractions are plain
    # matmuls batched over the heads (with the heads third the TPU compiler
    # reaches them through a dilated convolution)
    k = jnp.concatenate([kv[..., :d_n], k_r], axis=-1).transpose(0, 2, 1, 3)
    v = kv[..., d_n:].transpose(0, 2, 1, 3)
    q = q.transpose(0, 2, 1, 3)
    out = []
    for start in range(0, T, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, T)
        scores = jnp.einsum("bhtd,bhsd->bhts", q[:, :, start:stop],
                            k[:, :, :stop],
                            preferred_element_type=jnp.float32) * scale
        causal = (jnp.arange(stop)[None, :]
                  <= jnp.arange(start, stop)[:, None])
        scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
        # float32 softmax, normalised after the product with V: one pass
        # over the block's scores fewer
        weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        pv = jnp.einsum("bhts,bhsd->bhtd", weights.astype(dtype),
                        v[:, :, :stop], preferred_element_type=jnp.float32)
        out.append((pv / weights.sum(axis=-1, keepdims=True)).astype(dtype))
    out = jnp.concatenate(out, axis=2) if len(out) > 1 else out[0]
    return out.transpose(0, 2, 1, 3)


# -------------------------------------------------------------------------
# The lengths-aware read: a Pallas TPU kernel over the cache as stored
# -------------------------------------------------------------------------
def _read_kernel(layer_ref, off_ref, q_ref, new_ref, rows_hbm, o_ref, buf,
                 sems, *, n_head, d_c, block, scale):
    """One grid step = one slot: its T new rows from ``new_ref``, then the
    ``ceil(offset / block)`` blocks of its earlier rows, copied from HBM
    two deep, under one running softmax. Row ``t * n_head + h`` of the
    queries is head h of token t."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, N, W = q_ref.shape
    T = N // n_head
    dtype = q_ref.dtype
    f32 = jnp.float32
    s = pl.program_id(0)
    layer = layer_ref[0]
    n_held = jnp.minimum(off_ref[s], rows_hbm.shape[2])
    n_blocks = (n_held + block - 1) // block

    def copy(i):
        """Block ``i`` of the slot's rows into buffer ``i % 2``."""
        at = pl.ds(pl.multiple_of(i * block, block), block)
        return pltpu.make_async_copy(rows_hbm.at[layer, s, at],
                                     buf.at[i % 2], sems.at[i % 2])

    def start(i):
        @pl.when(i < n_blocks)
        def _():
            copy(i).start()

    start(0)
    q_rows = q_ref[0]
    q_f32 = q_rows.astype(f32)
    token = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0) // n_head

    # the T new rows, one position at a time on the VPU; new position j is
    # seen by tokens t >= j; j = 0 by all, so the running max is finite
    m = l = acc = None
    for j in range(T):
        r_j = new_ref[0, j:j + 1, :].astype(dtype).astype(f32)
        s_j = jnp.sum(q_f32 * r_j, axis=-1, keepdims=True) * scale
        v_j = r_j[:, :d_c]
        if j == 0:
            m, l = s_j, jnp.ones_like(s_j)
            acc = jnp.broadcast_to(v_j, (N, d_c))
            continue
        seen = token >= j
        m_new = jnp.where(seen, jnp.maximum(m, s_j), m)
        p_j = jnp.where(seen, jnp.exp(s_j - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p_j
        acc = alpha * acc + p_j.astype(dtype).astype(f32) * v_j
        m = m_new

    def block_of_rows(i, carry):
        m, l, acc = carry
        start(i + 1)
        copy(i).wait()
        held_rows = buf[i % 2].astype(dtype)
        scores = jax.lax.dot_general(
            q_rows, held_rows, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale             # [N, block]
        held = (i * block + jax.lax.broadcasted_iota(
            jnp.int32, (N, block), 1)) < n_held
        scores = jnp.where(held, scores, jnp.finfo(f32).min)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # masked in the exponentials too: a slot's stale rows weigh 0.0
        p = jnp.where(held, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(dtype), held_rows[:, :d_c],
                                    preferred_element_type=f32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, block_of_rows, (m, l, acc))
    o_ref[0] = (acc * (1.0 / l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_head", "d_c", "scale",
                                             "interpret"))
def _kernel_read(q_rows, new, rows, position_offset, layer, *, n_head, d_c,
                 scale, interpret):
    """``q_rows [S, T * H, W]`` over slot s's cache positions ``<
    offset[s]`` of ``layer`` and its T new rows ``new [S, T, W]``:
    ``[S, T * H, d_c]`` in the queries' dtype. ``layer`` is an operand and
    the function a ``jit`` of its own, so that a model's layers share ONE
    traced and lowered kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, N, W = q_rows.shape
    T = new.shape[1]
    max_len = rows.shape[2]
    block = min(_BLOCK, max_len)   # shorter than a block: the tests' alone
    if max_len % block:
        raise ValueError(
            f"the kernel reads whole blocks of {block} positions: got "
            f"max_len {max_len}")

    def per_slot(s, layer, off):
        return (s, 0, 0)

    return pl.pallas_call(
        functools.partial(_read_kernel, n_head=n_head, d_c=d_c, block=block,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, N, W), per_slot),
                      pl.BlockSpec((1, T, W), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, N, d_c), per_slot),
            scratch_shapes=[pltpu.VMEM((2, block, W), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((S, N, d_c), q_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention_read",
    )(jnp.asarray(layer, jnp.int32)[None], position_offset.astype(jnp.int32),
      q_rows, new, rows)
