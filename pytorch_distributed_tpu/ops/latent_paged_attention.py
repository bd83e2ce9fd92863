"""Latent (MLA) attention over a PAGED pool: ``ops.latent_attention``'s row
``[c_kv | k_r | 0]`` (640 wide for 512 + 64; that module says why) kept in
pages ``pool [L, P, page, W]`` that a sequence reaches through its row of a
block table (``serving.paging.PagedLatentCache``; page 0 is the trash page,
as in ``ops.paged_attention``). Three paths, the same mathematics:

  * ``paged_read`` (a decode or verify step, every slot a row of the batch):
    the ABSORBED read of ``ops.latent_attention`` through the table. Two
    forms, one result, separated by where they can run: the dense
    contraction against every page of every chain, and the Pallas TPU kernel
    ``latent_paged_read``, which is that module's read kernel
    with one indirection: it already copies 128 positions at a time from
    HBM by hand, so a page is a block and the table, scalar-prefetched
    beside the offsets, names the block to copy.
  * ``cold_prefill`` (a prompt from position 0, nothing cached): K and V
    EXPANDED from the new latents once, then causal attention with the keys
    in blocks under a running softmax: 64 heads x 1,024 queries x 32,768
    keys of float32 scores would be 8.6 GB, a block's are 268 MB
    (``ops.gqa_attention``'s prefill forms at one query head a K/V head,
    K 192 and V 128 wide: its Pallas kernel on a TPU, its ``jax.numpy`` loop
    elsewhere).
  * ``tail_prefill`` (the uncached tail of a prompt whose prefix lies in
    shared pages, T new tokens at ``start``): the new rows are written, then
    the chain is walked in blocks of ``_KEY_BLOCK`` positions as far as the
    tail's last REAL token: a block's pages are gathered, its K and V
    expanded, and the T queries meet them under a running softmax. Expanded,
    not absorbed: T x H absorbed query rows against 576 + 512 columns cost
    more than expanding a block once for all T (at T = 512 over 28k rows 2.0
    against 1.1 TFLOP a layer), and ``latent_attention``'s read kernel keeps
    a slot's T x H query rows whole in VMEM (42 MB at 512 x 64).

Masking invariant as everywhere: a query at global position p sees exactly
the positions <= p of its own chain.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops import decode_attention as _slotted
from pytorch_distributed_tpu.ops import gqa_attention

__all__ = ["write_rows", "paged_read", "kernel_reads", "cold_prefill",
           "tail_prefill"]

#: chain positions a step of the tail's running softmax meets
_KEY_BLOCK = 1024
#: heads a cold prompt attends at a time
_HEAD_GROUP = 4
_MIN = float(jnp.finfo(jnp.float32).min)


def kernel_reads(pool: jax.Array) -> bool:
    """Whether the paged read kernel can serve a pool of this shape on this
    backend: Mosaic runs on a TPU, copies whole pages and wants them in
    whole sublane tiles of lane-aligned rows."""
    _, _, page, width = pool.shape
    return (_slotted._platform() == "tpu" and width % 128 == 0
            and page % 16 == 0)


def write_rows(pool, new, tables, pos, layer):
    """``new [B, T, W]`` written at global positions ``pos [B, T]`` of
    ``layer`` through ``tables [B, M]``. Positions past a table and the rows
    of a zeroed table land in page 0, the trash page."""
    page = pool.shape[2]
    max_pages = tables.shape[1]
    m_raw = pos // page
    page_id = jnp.take_along_axis(tables, jnp.clip(m_raw, 0, max_pages - 1),
                                  axis=1)
    page_id = jnp.where(m_raw < max_pages, page_id, 0)
    return pool.at[layer, page_id, pos % page].set(new.astype(pool.dtype))


def _absorbed(q, kv_b, d_n, width):
    """The queries with ``W_kvb``'s key half absorbed, ``[B, T * H, W]``:
    row ``t * H + h`` is ``[q~ | q_r | 0]`` of head h of token t."""
    B, T, H, _ = q.shape
    q_abs = jnp.einsum("bthn,chn->bthc", q[..., :d_n], kv_b[..., :d_n],
                       preferred_element_type=jnp.float32).astype(q.dtype)
    rows = jnp.concatenate([q_abs, q[..., d_n:]], axis=-1)
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, width - rows.shape[-1]),))
    return rows.reshape(B, T * H, width)


def paged_read(q, latent, kv_b, pool, tables, layer: int, position_offset, *,
               d_c: int, d_n: int, scale: float, kernel: bool = False,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Write the T new tokens' rows at ``position_offset [B] ..`` of each
    chain and attend, absorbed: ``(y [B, T, H, d_v], pool)``. ``q [B, T, H,
    d_n + d_r]``, ``latent [B, T, d_c + d_r]``, ``kv_b [d_c, H, d_n + d_v]``,
    ``tables [B, M]``; batch row b is slot b."""
    B, T, H, _ = q.shape
    W = pool.shape[3]
    dtype = q.dtype
    new = jnp.pad(latent, ((0, 0), (0, 0), (0, W - latent.shape[-1]))
                  ).astype(pool.dtype)
    pos = position_offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    before = pool
    pool = write_rows(pool, new, tables, pos, layer)
    q_rows = _absorbed(q, kv_b, d_n, W)
    if kernel:
        # positions below the offset from the pool as it was, the T new
        # ones from the rows in hand: the read waits on no write
        o = _kernel_read(q_rows, new, before, tables, position_offset, layer,
                         n_head=H, d_c=d_c, scale=scale, interpret=interpret)
    else:
        held = pool[layer][tables]                  # [B, M, page, W]
        held = held.reshape(B, -1, W).astype(dtype)
        scores = jnp.einsum("bnw,bsw->bns", q_rows, held,
                            preferred_element_type=jnp.float32) * scale
        visible = (jnp.arange(held.shape[1], dtype=jnp.int32)[None, None]
                   <= pos[:, :, None])
        visible = jnp.repeat(visible, H, axis=1)    # row t * H + h
        o = _slotted._softmax_pv(scores, visible, held[..., :d_c], dtype,
                                 "bns,bsc->bnc").astype(dtype)
    y = jnp.einsum("bthc,chv->bthv", o.reshape(B, T, H, d_c), kv_b[..., d_n:],
                   preferred_element_type=jnp.float32)
    return y.astype(dtype), pool


def cold_prefill(q, latent, kv_b, *, d_c: int, d_n: int, scale: float,
                 n_real=None, interpret: bool = False) -> jax.Array:
    """Causal attention among the T tokens of ONE fresh prompt, K and V
    expanded from ``latent [B, T, >= d_c + d_r]`` (the rows as stored):
    ``[B, T, H, d_v]``. ``n_real``: as ``gqa_attention.blockwise_attention``
    (the kernel's grid is the causal half of all T positions whatever it
    is). The heads go ``_HEAD_GROUP`` at a time, one after the other: 64
    heads' expanded K, V and Q of 32,768 positions and their copies heads
    first are 4.6 GB (192 columns lie in 256 lanes), an eighth of them fit
    beside a pool (compile result, PR 54). ``ops.gqa_attention``'s forms
    scale by the key width alone, so YaRN's ``m^2`` (``scale`` over
    ``D^-1/2``) goes into the keys as they are put together."""
    B, T, H, D = q.shape
    dtype = q.dtype
    c = latent[..., :d_c].astype(dtype)
    k_r = latent[:, :, None, d_c:d_c + D - d_n].astype(dtype)
    ratio = scale * D ** 0.5
    if abs(ratio - 1.0) > 1e-9:
        k_r = (k_r.astype(jnp.float32) * ratio).astype(dtype)
    group = min(H, _HEAD_GROUP)
    out = None
    for first in range(0, H, group):
        heads = slice(first, first + group)
        if out is not None:
            # one group after the other: the next waits for the last
            c, q = jax.lax.optimization_barrier((c, q, out))[:2]
        kv = jnp.einsum("btc,chn->bthn", c, kv_b[:, heads],
                        preferred_element_type=jnp.float32)
        k = jnp.concatenate(
            [(kv[..., :d_n] * ratio).astype(dtype),
             jnp.broadcast_to(k_r, (B, T, group, D - d_n))], axis=-1)
        v, q_g = kv[..., d_n:].astype(dtype), q[:, :, heads]
        y = gqa_attention.prefill_attention(
            q_g, k, v, n_real=n_real, interpret=interpret,
            kernel=interpret or gqa_attention.kernel_prefills(q_g, k, v))
        out = y if out is None else jnp.concatenate([out, y], axis=2)
    return out


def tail_prefill(q, kv_b, pool, table, layer: int, start, n_new, *,
                 d_c: int, d_n: int, scale: float) -> jax.Array:
    """``q [1, T, H, d_n + d_r]``, the queries of T new tokens at positions
    ``start ..`` of the chain ``table [M]``, whose rows (the new ones among
    them) lie in ``pool``; ``n_new`` of the T are real. ``[1, T, H, d_v]``;
    what a query past the last real one gives is finite and unread."""
    _, T, H, D = q.shape
    d_v = kv_b.shape[2] - d_n
    dtype = q.dtype
    page = pool.shape[2]
    pages = max(1, min(table.shape[0], _KEY_BLOCK // page))
    block = pages * page
    table = jnp.pad(table, (0, -table.shape[0] % pages))    # the trash page
    q = q[0].transpose(1, 0, 2)                              # [H, T, D]
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    p_at = start + jnp.arange(T, dtype=jnp.int32)[:, None]   # [T, 1]
    s_at = jnp.arange(block, dtype=jnp.int32)[None, :]

    def keys_of_block(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, j * pages, pages)
        rows = pool[layer, ids].reshape(block, -1).astype(dtype)
        kv = jnp.einsum("sc,chn->hsn", rows[:, :d_c], kv_b,
                        preferred_element_type=jnp.float32).astype(dtype)
        scores = (jnp.einsum("htd,hsd->hts", q_n, kv[..., :d_n],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("htr,sr->hts", q_r,
                               rows[:, d_c:d_c + D - d_n],
                               preferred_element_type=jnp.float32)) * scale
        visible = (j * block + s_at <= p_at)[None]
        scores = jnp.where(visible, scores, _MIN)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.where(visible, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hts,hsv->htv", p.astype(dtype), kv[..., d_n:],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # block 0 holds position 0, which every query sees: no sum stays zero
    n_blocks = jnp.minimum((start + n_new + block - 1) // block,
                           table.shape[0] // pages)
    _, l, acc = jax.lax.fori_loop(0, jnp.maximum(n_blocks, 1), keys_of_block, (
        jnp.full((H, T, 1), _MIN, jnp.float32),
        jnp.zeros((H, T, 1), jnp.float32),
        jnp.zeros((H, T, d_v), jnp.float32)))
    return (acc / l).astype(dtype).transpose(1, 0, 2)[None]


# -------------------------------------------------------------------------
# The paged read: ``ops.latent_attention._read_kernel`` through a table
# -------------------------------------------------------------------------
def _read_kernel(layer_ref, off_ref, table_ref, q_ref, new_ref, pool_hbm,
                 o_ref, buf, sems, *, n_head, d_c, max_pages, scale):
    """One grid step = one slot: its T new rows from ``new_ref``, then the
    ``ceil(offset / page)`` pages of its chain, copied from HBM two deep,
    under one running softmax. Row ``t * n_head + h`` of the queries is
    head h of token t; ``table_ref`` is the block table in a row."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, N, W = q_ref.shape
    page = buf.shape[1]
    T = N // n_head
    dtype = q_ref.dtype
    f32 = jnp.float32
    s = pl.program_id(0)
    layer = layer_ref[0]
    n_held = jnp.minimum(off_ref[s], page * max_pages)
    n_pages = (n_held + page - 1) // page

    def copy(i):
        """Page ``i`` of the slot's chain into buffer ``i % 2``."""
        return pltpu.make_async_copy(
            pool_hbm.at[layer, table_ref[s * max_pages + i]],
            buf.at[i % 2], sems.at[i % 2])

    def start(i):
        @pl.when(i < n_pages)
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

    def page_of_rows(i, carry):
        m, l, acc = carry
        start(i + 1)
        copy(i).wait()
        held_rows = buf[i % 2].astype(dtype)
        scores = jax.lax.dot_general(
            q_rows, held_rows, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale              # [N, page]
        held = (i * page + jax.lax.broadcasted_iota(
            jnp.int32, (N, page), 1)) < n_held
        scores = jnp.where(held, scores, jnp.finfo(f32).min)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # masked in the exponentials too: a recycled page's rows weigh 0.0
        p = jnp.where(held, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(dtype), held_rows[:, :d_c],
                                    preferred_element_type=f32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_pages, page_of_rows, (m, l, acc))
    o_ref[0] = (acc * (1.0 / l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_head", "d_c", "scale",
                                             "interpret"))
def _kernel_read(q_rows, new, pool, tables, position_offset, layer, *, n_head,
                 d_c, scale, interpret):
    """``q_rows [S, T * H, W]`` over the chain positions ``< offset[s]`` of
    slot s in ``layer`` and its T new rows ``new [S, T, W]``: ``[S, T * H,
    d_c]`` in the queries' dtype. ``layer`` is an operand and the function a
    ``jit`` of its own, so that a model's layers share ONE traced and
    lowered kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, N, W = q_rows.shape
    T = new.shape[1]
    page = pool.shape[2]
    max_pages = tables.shape[1]

    def per_slot(s, layer, off, table):
        return (s, 0, 0)

    return pl.pallas_call(
        functools.partial(_read_kernel, n_head=n_head, d_c=d_c,
                          max_pages=max_pages, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, N, W), per_slot),
                      pl.BlockSpec((1, T, W), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, N, d_c), per_slot),
            scratch_shapes=[pltpu.VMEM((2, page, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((S, N, d_c), q_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_read",
    )(jnp.asarray(layer, jnp.int32)[None], position_offset.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), q_rows, new, pool)
