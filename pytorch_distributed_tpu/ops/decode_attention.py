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

Two reads of a sequence's earlier rows (``position_offset`` given), one
result. The dense read contracts against ``k_cache[layer]`` whole, every
position of every slot: at the serving cell's 11% occupancy and 260 of
1,024 positions a slot, 97% of the bytes it moves are masked (PERF.md,
PR 32). ``kernel=True`` is a Pallas TPU kernel over the SAME stored cache:
the cache stays in HBM, ``position_offset`` is scalar-prefetched, and slot
``s`` copies in only the blocks of ``_BLOCK`` positions that hold a row
below ``offset[s]`` (none for an idle slot riding at offset 0); the T new
rows come from ``k_new`` / ``v_new`` as they were stored, not back out of
the cache. Inside a block the arithmetic is the dense read's (block-diagonal
rows against K on the MXU with float32 accumulation, float32 scores and
statistics, probabilities in the compute dtype against V); only the sum
over positions is taken block by block (online softmax). Mosaic kernels run
on a TPU and cannot be split by the partitioner, so which read serves a
cache is ``serving.kv_cache.KVCache``'s to decide from where the cache
lies; nobody else knows there are two.

Masking invariant: a query at global position p attends exactly the cache
positions <= p. Positions beyond a sequence's current length are never
attended because every attended position was either written by this
request's prefill or by one of its earlier decode steps (slots are reused
without zeroing — the mask, not memset, is the isolation boundary).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["cached_attention", "kernel_reads"]

#: positions a copy from the cache brings in: 128 rows of a 768-wide bf16
#: cache are 196 KB, enough for the copy to run at HBM speed and small
#: enough that a 260-position sequence wastes under a block
_BLOCK = 128
#: a token's query rows in the kernel are one per head, padded to a whole
#: number of these: a bf16 tile's 16 sublanes, so that the tokens' row
#: groups stack aligned for any head count (12 heads take 16, 20 take 32)
_ROW_TILE = 16


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
    *,
    kernel: bool = False,
    interpret: bool = False,
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
      kernel: read the earlier rows with the lengths-aware Pallas kernel
        instead of the dense contraction (module docstring; the caller has
        asked ``kernel_reads``). Ignored by the fresh prefill, which reads
        nothing. ``interpret`` runs it in the Pallas interpreter (the CPU
        tests' way in).

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
    k_before, v_before = k_cache, v_cache
    k_cache = k_cache.at[layer, b_idx, pos].set(k_rows)
    v_cache = v_cache.at[layer, b_idx, pos].set(v_rows)
    if kernel:
        # positions below the offset from the cache as it was, the T new
        # ones from the rows as stored: the read waits on no write
        out = _kernel_read(
            q.reshape(B, T, C), k_rows, v_rows, k_before, v_before,
            position_offset, layer, n_head=H, interpret=interpret,
        )
        return out.reshape(B, T, H, D), k_cache, v_cache

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


# -------------------------------------------------------------------------
# The lengths-aware read: a Pallas TPU kernel over the cache as stored
# -------------------------------------------------------------------------
def kernel_reads(k_cache: jax.Array) -> bool:
    """Whether the lengths-aware kernel can serve a cache of this shape on
    this backend: Mosaic runs on a TPU, copies whole ``_BLOCK``-position
    blocks and wants lane-aligned rows; the head count is adapted, not
    gated (``_ROW_TILE``). (A cache laid out over several devices is the
    caller's to rule out: the partitioner cannot split a custom call.)"""
    _, _, max_len, width = k_cache.shape
    return (_platform() == "tpu" and width % 128 == 0
            and max_len % _BLOCK == 0)


def _platform() -> str:
    # a backend that fails to initialise raises here: it must never turn
    # the kernel into the dense read
    return jax.devices()[0].platform


def _read_kernel(layer_ref, off_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm,
                 o_ref, k_buf, v_buf, sems, *, n_head, block, scale):
    """One grid step = one slot: its T new rows from ``kn_ref`` /
    ``vn_ref``, then the ``ceil(offset / block)`` blocks of its earlier
    rows, copied from HBM two deep, under one running softmax."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, T, C = q_ref.shape
    D = C // n_head
    n_rows = -(-n_head // _ROW_TILE) * _ROW_TILE
    N = T * n_rows
    dtype = q_ref.dtype
    f32 = jnp.float32
    s = pl.program_id(0)
    layer = layer_ref[0]
    n_held = jnp.minimum(off_ref[s], k_hbm.shape[2])
    n_blocks = (n_held + block - 1) // block

    def copies(i):
        """Block ``i`` of the slot's rows into buffer ``i % 2``."""
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        return (
            pltpu.make_async_copy(k_hbm.at[layer, s, rows], k_buf.at[i % 2],
                                  sems.at[0, i % 2]),
            pltpu.make_async_copy(v_hbm.at[layer, s, rows], v_buf.at[i % 2],
                                  sems.at[1, i % 2]),
        )

    def start(i):
        @pl.when(i < n_blocks)
        def _():
            for copy in copies(i):
                copy.start()

    start(0)

    # row t * n_rows + h keeps head h's D columns of token t: the
    # block-diagonal query rows of the dense read, built here from [T, C]
    row = jax.lax.broadcasted_iota(jnp.int32, (N, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, C), 1)
    own = (row % n_rows) == (col // D)
    stacked = [jnp.broadcast_to(q_ref[0, t:t + 1, :].astype(f32), (n_rows, C))
               for t in range(T)]
    q_f32 = jnp.where(own, jnp.concatenate(stacked, axis=0), 0.0)
    q_rows = q_f32.astype(dtype)
    token = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0) // n_rows

    # the T new rows, one position at a time on the VPU (a [T, C] operand
    # is no MXU shape): bf16 products are exact in float32 and summed
    # there, as the MXU would. New position j is seen by tokens t >= j;
    # j = 0 by all, so the running max is finite from here on
    m = l = acc = None
    for j in range(T):
        k_j = kn_ref[0, j:j + 1, :].astype(dtype).astype(f32)
        v_j = vn_ref[0, j:j + 1, :].astype(dtype).astype(f32)
        s_j = jnp.sum(q_f32 * k_j, axis=-1, keepdims=True) * scale
        if j == 0:
            m, l = s_j, jnp.ones_like(s_j)
            acc = jnp.broadcast_to(v_j, (N, C))
            continue
        seen = token >= j
        m_new = jnp.where(seen, jnp.maximum(m, s_j), m)
        p_j = jnp.where(seen, jnp.exp(s_j - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p_j
        acc = alpha * acc + p_j.astype(dtype).astype(f32) * v_j
        m = m_new

    def block_of_rows(i, carry):
        """Positions ``i * block ..`` of the slot: one step of the running
        softmax, the next block on its way meanwhile."""
        m, l, acc = carry
        start(i + 1)
        for copy in copies(i):
            copy.wait()
        k = k_buf[i % 2].astype(dtype)
        v = v_buf[i % 2].astype(dtype)
        scores = jax.lax.dot_general(
            q_rows, k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale             # [N, block]
        held = (i * block + jax.lax.broadcasted_iota(
            jnp.int32, (N, block), 1)) < n_held
        scores = jnp.where(held, scores, jnp.finfo(f32).min)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # masked in the exponentials too: a slot's stale rows weigh 0.0
        p = jnp.where(held, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(dtype), v, preferred_element_type=f32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, block_of_rows, (m, l, acc))

    # row (t, h) keeps head h's columns; the heads' rows of a token add up
    # to its [C] output
    out = jnp.where(own, acc * (1.0 / l), 0.0)
    for t in range(T):
        o_ref[0, t:t + 1, :] = jnp.sum(
            out[t * n_rows:(t + 1) * n_rows], axis=0, keepdims=True
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_head", "interpret"))
def _kernel_read(q, k_rows, v_rows, k_cache, v_cache, position_offset, layer,
                 *, n_head, interpret):
    """``q [S, T, C]`` over slot s's cache positions ``< offset[s]`` of
    ``layer`` and its T new rows ``k_rows`` / ``v_rows [S, T, C]`` (new
    position j visible to tokens ``t >= j``): ``[S, T, C]`` in q's dtype.

    ``layer`` is an operand (scalar-prefetched beside the offsets) and the
    function a ``jit`` of its own, so that a model's layers share ONE
    traced and lowered kernel: a program with twelve of them would
    otherwise spend seconds of every start, compile cache or not, lowering
    twelve kernels that differ in a constant."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, C = q.shape
    max_len = k_cache.shape[2]
    # a cache shorter than a block is the interpret-mode tests' alone
    # (``kernel_reads`` admits none): it is one block
    block = min(_BLOCK, max_len)
    if max_len % block:
        raise ValueError(
            f"the kernel reads whole blocks of {block} positions: got "
            f"max_len {max_len}"
        )

    def per_slot(s, layer, off):
        return (s, 0, 0)

    rows = pl.BlockSpec((1, T, C), per_slot)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _read_kernel, n_head=n_head, block=block,
            scale=(C // n_head) ** -0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[rows, rows, rows, whole, whole],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((2, block, C), k_cache.dtype),
                pltpu.VMEM((2, block, C), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, T, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="decode_attention_read",
    )(jnp.asarray(layer, jnp.int32)[None], position_offset.astype(jnp.int32),
      q, k_rows, v_rows, k_cache, v_cache)
