"""Paged attention over a block-table KV cache — reference + Pallas kernel.

The paged analogue of ops.decode_attention: K/V live in a pool of
fixed-size pages ``[n_pages, page_size, H, D]`` shared by every sequence,
and each sequence owns an ordered chain of page ids in a ``block table``
row ``[max_pages]`` (table position ``m`` holds the page for global token
positions ``m*page_size .. (m+1)*page_size - 1``). Admission attaches
radix-shared prefix pages by reference; writes only ever land in pages the
sequence owns privately (serving.paging's COW discipline), so the op
itself never forks.

Two implementations share one contract:

* ``paged_cached_attention`` — pure jnp. Scatters the T new tokens through
  the block table, gathers the referenced pages into a dense ``[B, S, H,
  D]`` view and runs exactly the slotted op's einsum/mask/softmax, so the
  paged path is bit-identical to ``cached_attention`` whenever the page
  chain covers the same positions. Import-light (no Pallas). This is what
  the serving path runs today on EVERY platform, prefill and decode alike:
  ``serving.paging.PagedKVCache.attend`` is its one caller.
* ``paged_decode_attention`` — Pallas TPU kernel for the T=1 decode step
  that gathers pages *in-kernel* via scalar-prefetched block tables (one
  grid step per table entry, online softmax across pages), so decode never
  materializes the dense gather in HBM. NOT wired into the model or the
  engine yet: only tests/test_paging.py (interpret-mode parity) and
  tests/test_chip_compile.py (Mosaic compile for a described v5e) call
  it. Lazy-exported from ops like the flash kernels; Pallas imports happen
  inside the function.

Trash-page invariant: page id 0 is reserved by serving.paging and never
allocated. Evicted / inactive slots have an all-zero table row, so their
(discarded) decode writes land in page 0 and their gathers read page 0 —
masked to zero weight by the same ``position <= query`` visibility rule as
the slotted cache. Stale bytes in recycled pages are unreachable for the
same reason: every visible position of a live sequence was written by that
sequence's own prefill/decode/COW-fork.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["paged_cached_attention", "paged_decode_attention"]

_NEG_INF = -1e30


def _scatter_new(
    pages: jax.Array,
    new: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """Write ``new [B, T, H, D]`` at global positions ``pos [B, T]`` through
    the block table. Positions past the table (padded prefill tails) and
    zeroed table rows (inactive slots) route to page 0 — the trash page —
    so out-of-range lanes can never alias a live page."""
    page_size = pages.shape[1]
    max_pages = block_tables.shape[1]
    m_raw = pos // page_size                                  # [B, T]
    m = jnp.clip(m_raw, 0, max_pages - 1)
    page_id = jnp.take_along_axis(block_tables, m, axis=1)    # [B, T]
    page_id = jnp.where(m_raw < max_pages, page_id, 0)
    off = pos % page_size
    return pages.at[page_id, off].set(new.astype(pages.dtype))


def paged_cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    position_offset: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write the new K/V through the block table, attend over the chain.

    Args:
      q, k_new, v_new: ``[B, T, H, D]`` projections for the T new tokens.
      k_pages, v_pages: ``[n_pages, page_size, H, D]`` shared page pool
        (one layer's worth — the model loops layers like the slotted path).
      block_tables: ``[B, max_pages]`` int32 page ids per sequence.
      position_offset: ``[B]`` int32 global position of each sequence's
        first new token.

    Returns:
      ``(out [B, T, H, D], k_pages, v_pages)`` with the pools updated.
    """
    B, T, H, D = q.shape
    page_size = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    S = max_pages * page_size

    pos = position_offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    k_pages = _scatter_new(k_pages, k_new, block_tables, pos)
    v_pages = _scatter_new(v_pages, v_new, block_tables, pos)

    # dense read-only gather of each sequence's chain: [B, S, H, D]
    k_seq = k_pages[block_tables].reshape(B, S, H, D)
    v_seq = v_pages[block_tables].reshape(B, S, H, D)

    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    scores = jnp.einsum("bthd,bshd->bhts", q, k_seq.astype(q.dtype)) * scale
    visible = (
        jnp.arange(S, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
    )  # [B, T, S]
    scores = jnp.where(
        visible[:, None], scores, jnp.finfo(scores.dtype).min
    )
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
        q.dtype
    )
    out = jnp.einsum("bhts,bshd->bthd", probs, v_seq.astype(q.dtype))
    return out, k_pages, v_pages


# -------------------------------------------------------------------------
# Pallas decode kernel: in-kernel gather through the block table
# -------------------------------------------------------------------------
def _interpret_default() -> bool:
    # a backend that fails to initialise raises here: it must never turn
    # the kernel into the interpreter
    return jax.devices()[0].platform != "tpu"


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, n_tables):
    """One grid step = one (sequence, table-entry) pair; online softmax
    accumulates across the sequence's page chain (the inner grid dim)."""
    import jax.experimental.pallas as pl  # resolved: kernel is traced lazily

    s = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_pos = len_ref[s]  # the decode query's global position

    # pages whose first position is already past the query are fully
    # masked — skip their arithmetic (their DMA still happens; the block
    # spec fetched the trash page for unallocated entries)
    @pl.when(m * page_size <= q_pos)
    def _page():
        q = q_ref[0].astype(jnp.float32)         # [H, D]
        k = k_ref[0].astype(jnp.float32)         # [H, page, D]
        v = v_ref[0].astype(jnp.float32)
        s_hp = jnp.sum(q[:, None, :] * k, axis=-1) * scale  # [H, page]

        kv_pos = m * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )                                         # [1, page]
        keep = kv_pos <= q_pos
        s_hp = jnp.where(keep, s_hp, _NEG_INF)

        m_prev = m_ref[:]                         # [H, 1]
        l_prev = l_ref[:]
        m_cur = jnp.max(s_hp, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp of masked entries must be exactly 0 even on all-masked rows
        p = jnp.exp(s_hp - m_new)
        p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.sum(
            p[:, :, None] * v, axis=1
        )
        m_ref[:] = m_new

    @pl.when(m == n_tables - 1)
    def _finish():
        # l >= 1 always: position 0 of the chain is visible to every query
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode-step attention gathering K/V through the block table in-kernel.

    The new token's K/V must already be scattered into the pools (the
    serving step fuses ``_scatter_new`` ahead of this call under jit);
    the kernel is read-only over the pools.

    Args:
      q: ``[B, 1, H, D]`` decode queries.
      k_pages, v_pages: ``[n_pages, page_size, H, D]`` page pools.
      block_tables: ``[B, max_pages]`` int32 page ids.
      lengths: ``[B]`` int32 — each query's global position (its K/V was
        written at position ``lengths[b]``; it attends positions
        ``<= lengths[b]``).

    Returns:
      ``out [B, 1, H, D]``.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"paged_decode_attention is decode-only (T=1), got T={T}")
    page_size = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    if interpret is None:
        interpret = _interpret_default()

    # kernel layouts: q [B, H, D]; pages [P, H, page, D] (blocked dims are
    # the trailing two — Mosaic's requirement, same trick as flash)
    q3 = q[:, 0]
    kp = jnp.swapaxes(k_pages, 1, 2)
    vp = jnp.swapaxes(v_pages, 1, 2)

    grid = (B, max_pages)
    kernel = functools.partial(
        _decode_kernel,
        scale=1.0 / float(D) ** 0.5,
        page_size=page_size,
        n_tables=max_pages,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, D), lambda s, m, tbl, lens: (s, 0, 0)),
                pl.BlockSpec(
                    (1, H, page_size, D),
                    lambda s, m, tbl, lens: (tbl[s, m], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, H, page_size, D),
                    lambda s, m, tbl, lens: (tbl[s, m], 0, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, H, D), lambda s, m, tbl, lens: (s, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q3, kp, vp)
    return out[:, None]
