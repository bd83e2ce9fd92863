"""Attention with fewer K/V heads than query heads (GQA), over whole rows
or over a window: the prefill's blockwise form and the decode step's read of
a slotted cache of ``H_kv * D``-wide rows (``serving.window_cache``).

Query head ``j`` attends K/V head ``j // G``, ``G = H_q / H_kv``. A key at
position ``s`` is visible to a query at ``p`` when ``s <= p`` and, under a
``window`` W, also ``s > p - W`` (W keys, the query's own among them).

PREFILL (``blockwise_attention``): a ``T x T`` score block cannot exist at
the lengths this serves (64 heads x 32,768^2 float32 scores are 275 GB), so
the queries go in blocks, each under one float32 softmax over the keys it
can see:

  * a window layer's block of ``_WINDOW_QUERY_BLOCK`` queries starting at
    ``a`` sees positions ``a - W .. a + block - 1`` and nothing else: ONE
    slice of the keys, ``block + W`` long, so the layer's work grows with T
    and not with T^2 (at 32,768 positions 0.41 TFLOP, where the causal half
    of the full product is 17.6);
  * a full layer's block of ``_QUERY_BLOCK`` queries walks the key blocks at
    or before its own under a running softmax (a loop whose trip count is
    the block's index: the blocks after the diagonal are never touched).

The G query heads of a K/V head are folded into the rows of one matmul
(``[G * block, D] x [D, keys]``), batched over the K/V heads. That is the
form in ``jax.numpy``, for any backend; it writes every block's float32
scores to memory and reads them back (on the v5e a full layer of 32,768
tokens takes 612 ms, 15% of the bf16 peak: my chip run, PR 40). On a TPU
``prefill_attention(..., kernel=True)`` runs a full layer's walk as a
Pallas kernel (``gqa_attention_prefill``) whose scores never leave VMEM:
the grid is the list of (query block, key block) pairs of the causal half,
scalar-prefetched, each step one ``[G * 128, D] x [D, keys]`` product
under the running softmax of its query block.

DECODE (``cached_read``): one new token a slot against the rows its slot
holds, ``n_rows[s]`` of them, wherever they lie in the slot (a ring's order
does not matter to a softmax: keys are stored after their norm and
rotation). Two forms, one result, as ``ops.decode_attention``: the dense
contraction against every row of every slot, and a Pallas TPU kernel
(``gqa_attention_read``) with that module's scaffolding: the cache stays in
HBM, layer and row counts are scalar-prefetched, a grid step is a slot and
copies in, two deep, only the blocks of ``_BLOCK`` rows below its count,
under one running softmax. D = 128 is a whole lane tile, so K/V head h is
the columns ``h * D ..`` of a stored row as it lies and needs no block
diagonal; its G query rows ride in a 16-row tile (a bf16 tile's sublanes).

K AND V HEADS OF UNEQUAL WIDTH, AND A SINK. Values may be narrower than
keys (``D_v`` 128 under ``D`` 192: every form takes ``v [..., D_v]`` and
gives ``[..., H_q, D_v]``). A key head that is a tile and a half wide has no
lane-aligned place of its own in a stored row, so a stored K row is PACKED
(``pack_keys``): the whole tiles of every head first (``h * body ..``),
then the heads' remaining ``tail`` columns side by side, ``128 // tail``
heads sharing a tile; no column is padding. The read kernel meets a head's
tail with a query whose tail lies in its head's part of a tile between
zeros, so every slice in it is lane-aligned; the dense twin unpacks. A
``sink [H_q]`` is a learned scalar a query head that joins the softmax's
denominator and carries no value: ``sum exp(s - m) v / (sum exp(s - m) +
exp(b - m))`` with ``m = max(max s, b)``; under a running softmax that is
a start of ``m = b``, a denominator of 1 and an empty sum.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["blockwise_attention", "prefill_attention", "cached_read",
           "kernel_reads", "kernel_prefills", "rope_inv_freq", "pack_keys",
           "map_upto"]

#: queries of a full layer attended at a time, and the keys of one step of
#: their running softmax: 8 K/V heads x 8,192 x 1,024 float32 scores, 268 MB
_QUERY_BLOCK = 1024
#: queries of a window layer attended at a time, against ``block + W`` keys
_WINDOW_QUERY_BLOCK = 256
#: the prefill kernel's blocks: queries of a K/V head's G query heads a
#: step (G x 128 rows of one product), and the keys they meet (4 MB of
#: float32 scores in VMEM). At 32,768 tokens, 64 heads on 8, on the v5e:
#: (128, 1024) 132 ms = 67% of the bf16 peak, (256, 512) 205, (128, 512)
#: 253, (256, 256) 358; (256, 1024) and (512, 512) do not fit VMEM (my chip
#: run and compile results, PR 40)
_KERNEL_QUERY_BLOCK = 128
_KERNEL_KEY_BLOCK = 1024
#: rows a copy from the cache brings in: 512 rows of a 1,024-wide bf16
#: cache are 1 MB each of K and V (4 MB of VMEM two deep); a ring shallower
#: than this is one block
_BLOCK = 512
#: a K/V head's query rows in the kernel, padded to a bf16 tile's sublanes
_ROW_TILE = 16

_MIN = float(jnp.finfo(jnp.float32).min)


def rope_inv_freq(dim: int, theta: float) -> np.ndarray:
    """``dim / 2`` inverse frequencies ``theta^(-2i/dim)`` (``rope_type``
    default: no scaling)."""
    return (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def _body(D: int) -> int:
    """The columns of a ``D``-wide key head that are whole lane tiles (all
    of a head no wider than one); the rest is its ``tail``."""
    return D - D % 128 if D > 128 else D


def pack_keys(k: jax.Array) -> jax.Array:
    """``k [..., H_kv, D]`` as stored rows ``[..., H_kv * D]`` (module
    docstring): every head's whole tiles, then every head's tail. Heads of
    whole tiles lie as they are."""
    *lead, H, D = k.shape
    body = _body(D)
    if body == D:
        return k.reshape(*lead, H * D)
    return jnp.concatenate([k[..., :body].reshape(*lead, H * body),
                            k[..., body:].reshape(*lead, H * (D - body))],
                           axis=-1)


def _unpack_keys(rows: jax.Array, D: int) -> jax.Array:
    """``pack_keys`` undone: ``[..., H_kv * D] -> [..., H_kv, D]``."""
    *lead, C = rows.shape
    H, body = C // D, _body(D)
    if body == D:
        return rows.reshape(*lead, H, D)
    return jnp.concatenate([rows[..., :H * body].reshape(*lead, H, body),
                            rows[..., H * body:].reshape(*lead, H, D - body)],
                           axis=-1)


# -------------------------------------------------------------------------
# Prefill: queries in blocks, scores never T x T
# -------------------------------------------------------------------------
def map_upto(fn, xs, upto=None):
    """``jax.lax.map(fn, xs)`` over the first ``upto`` entries of the
    leading axis only (a traced int32 scalar; None: all of them), ZEROS in
    the place of the others' results. One loop whose bound is a value, not
    a shape: a padded prompt's blocks past its last real token cost nothing
    and no program is compiled for the length. The results are the carry,
    each written in place at its index."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]

    def entry(i):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False), xs)

    def step(i, out):
        return jax.tree_util.tree_map(
            lambda o, a: jax.lax.dynamic_update_index_in_dim(o, a, i, 0),
            out, fn(entry(i)))

    out = jax.tree_util.tree_map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype),
        jax.eval_shape(fn, entry(0)))
    return jax.lax.fori_loop(0, n if upto is None else upto, step, out)


def _softmax_pv(scores, visible, v, dtype, sink=None):
    """One block's masked float32 softmax times ``v``, normalised after the
    product: ``scores [B, H, G, q, s]``, ``v [B, H, s, D_v]``, ``sink [H,
    G]`` or None."""
    scores = jnp.where(visible, scores, _MIN)
    top = scores.max(axis=-1, keepdims=True)
    if sink is not None:
        sink = sink[None, :, :, None, None]
        top = jnp.maximum(top, sink)
    weights = jnp.where(visible, jnp.exp(scores - top), 0.0)
    pv = jnp.einsum("bhgqs,bhsd->bhgqd", weights.astype(dtype), v,
                    preferred_element_type=jnp.float32)
    total = weights.sum(axis=-1, keepdims=True)
    return pv / (total if sink is None else total + jnp.exp(sink - top))


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        window: Optional[int] = None,
                        sink: Optional[jax.Array] = None,
                        n_real: Optional[jax.Array] = None) -> jax.Array:
    """Causal attention among T tokens at positions ``0..T-1``: ``q [B, T,
    H_q, D]``, ``k [B, T, H_kv, D]``, ``v [B, T, H_kv, D_v]`` -> ``[B, T,
    H_q, D_v]`` in q's dtype. ``window``: a query sees its last ``window``
    positions only. ``sink [H_q]``: module docstring. ``n_real`` (a traced
    int32 scalar): only the first ``n_real`` positions hold real tokens;
    the query blocks past them are not attended and give zeros."""
    B, T, Hq, D = q.shape
    Hkv, Dv = v.shape[2:]
    G = Hq // Hkv
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hkv, G)
    dtype = q.dtype
    scale = D ** -0.5
    block = min(T, _WINDOW_QUERY_BLOCK if window else _QUERY_BLOCK)
    pad = -T % block                      # keys past T - 1 are seen by none
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n_blocks = (T + pad) // block
    # heads first, a K/V head's G query heads beside it: both contractions
    # are plain matmuls batched over [B, H_kv]
    q = q.reshape(B, n_blocks, block, Hkv, G, D).transpose(1, 0, 3, 4, 2, 5)
    k = k.transpose(0, 2, 1, 3).astype(dtype)
    v = v.transpose(0, 2, 1, 3).astype(dtype)
    at = jnp.arange(block, dtype=jnp.int32)

    def scores_of(q_block, keys):
        return jnp.einsum("bhgqd,bhsd->bhgqs", q_block, keys,
                          preferred_element_type=jnp.float32) * scale

    if window:
        # position s sits at row s + window: every block's band is one slice
        front = ((0, 0), (0, 0), (window, 0), (0, 0))
        k, v = jnp.pad(k, front), jnp.pad(v, front)
        band = jnp.arange(block + window, dtype=jnp.int32) - window

        def one(args):
            i, q_block = args
            keys = jax.lax.dynamic_slice_in_dim(k, i * block, block + window,
                                                axis=2)
            values = jax.lax.dynamic_slice_in_dim(v, i * block,
                                                  block + window, axis=2)
            s = i * block + band[None, :]
            p = i * block + at[:, None]
            visible = (s <= p) & (s > p - window) & (s >= 0)
            return _softmax_pv(scores_of(q_block, keys), visible, values,
                               dtype, sink).astype(dtype)
    else:
        def one(args):
            i, q_block = args

            def keys_of_block(j, carry):
                m, l, acc = carry
                keys = jax.lax.dynamic_slice_in_dim(k, j * block, block,
                                                    axis=2)
                values = jax.lax.dynamic_slice_in_dim(v, j * block, block,
                                                      axis=2)
                visible = (j * block + at[None, :]) <= (i * block
                                                        + at[:, None])
                scores = jnp.where(visible, scores_of(q_block, keys), _MIN)
                m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
                p = jnp.where(visible, jnp.exp(scores - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + p.sum(axis=-1, keepdims=True)
                acc = alpha * acc + jnp.einsum(
                    "bhgqs,bhsd->bhgqd", p.astype(dtype), values,
                    preferred_element_type=jnp.float32)
                return m_new, l, acc

            rows = (B, Hkv, G, block)
            # key block 0 holds position 0, which every query sees: the
            # running maximum is finite after the first step
            m0 = jnp.full(rows + (1,), _MIN, jnp.float32)
            l0 = jnp.zeros(rows + (1,), jnp.float32)
            if sink is not None:
                m0 = jnp.broadcast_to(sink[None, :, :, None, None], m0.shape)
                l0 = l0 + 1.0
            _, l, acc = jax.lax.fori_loop(0, i + 1, keys_of_block, (
                m0, l0, jnp.zeros(rows + (Dv,), jnp.float32)))
            return (acc / l).astype(dtype)

    blocks = jnp.arange(n_blocks, dtype=jnp.int32)
    out = one((blocks[0], q[0]))[None] if n_blocks == 1 else map_upto(
        one, (blocks, q), None if n_real is None else -(-n_real // block))
    # [n_blocks, B, H_kv, G, block, D_v] -> [B, T, H_q, D_v]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, T + pad, Hq, Dv)
    return out[:, :T]


def _kernel_query_block(G: int) -> int:
    """Positions a step of the prefill kernel attends: ``G`` query heads of
    them are the rows of one product, 1,024 rows at the most (8 heads x 128
    positions, 16 x 64)."""
    return min(_KERNEL_QUERY_BLOCK, _KERNEL_QUERY_BLOCK * 8 // G)


def kernel_prefills(q: jax.Array, k: Optional[jax.Array] = None,
                    v: Optional[jax.Array] = None) -> bool:
    """Whether the Pallas prefill kernel can attend queries of this shape
    (over such ``k`` and ``v``, where they are not q's heads and width) on
    this backend: Mosaic runs on a TPU and wants whole lane tiles a value
    head, whole or half tiles a key head, whole sublane tiles a block."""
    from pytorch_distributed_tpu.ops.decode_attention import _platform

    _, T, Hq, D = q.shape
    G = Hq // k.shape[2] if k is not None else 1
    Dv = v.shape[3] if v is not None else D
    bq, bk = _kernel_query_block(G), _KERNEL_KEY_BLOCK
    whole_blocks = (T & (T - 1) == 0 if T <= bq       # one block: 2^n rows
                    else T % bq == 0 and (T <= bk or T % bk == 0))
    return (_platform() == "tpu" and D % 64 == 0 and Dv % 128 == 0
            and G <= 16 and T % 16 == 0 and whole_blocks)


def prefill_attention(q, k, v, *, window: Optional[int] = None,
                      sink: Optional[jax.Array] = None,
                      n_real: Optional[jax.Array] = None,
                      kernel: bool = False, interpret: bool = False):
    """``blockwise_attention``; a full layer's by the Pallas kernel where
    ``kernel`` (the caller has asked ``kernel_prefills``; ``interpret`` runs
    it in the Pallas interpreter; its grid is the causal half of all T
    positions whatever ``n_real``). A window layer's band stays in
    ``jax.numpy``: its scores are small enough to cost little in memory,
    and a kernel walking the band pair by pair was slower (14.2 against
    8.0 ms at 32,768 tokens: my chip run, PR 40)."""
    if window or sink is not None or not kernel:
        return blockwise_attention(q, k, v, window=window, sink=sink,
                                   n_real=n_real)
    B, T, Hq, D = q.shape
    Hkv, Dv = v.shape[2:]
    G = Hq // Hkv
    bq = min(T, _kernel_query_block(G))
    nq = T // bq
    # [B * H_kv, nq, G * bq, D]: a K/V head's G query heads of one block
    # of positions are the rows of one product
    rows = q.reshape(B, nq, bq, Hkv, G, D).transpose(0, 3, 1, 4, 2, 5)
    rows = rows.reshape(B * Hkv, nq, G * bq, D)
    keys, values = (a.astype(q.dtype).transpose(0, 2, 1, 3).reshape(
        B * Hkv, T, a.shape[3]) for a in (k, v))
    out = _kernel_prefill(rows, keys, values,
                          bk=min(T, _KERNEL_KEY_BLOCK), interpret=interpret)
    out = out.reshape(B, Hkv, nq, G, bq, Dv).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, T, Hq, Dv)


def _prefill_pairs(nq, bq, bk):
    """The (query block, key block) pairs of the causal half, query-major,
    and for each whether it is its query block's last."""
    pairs = [(qi, ki, ki == (qi * bq + bq - 1) // bk)
             for qi in range(nq) for ki in range((qi * bq + bq - 1) // bk + 1)]
    return [np.asarray(column, np.int32) for column in zip(*pairs)]


def _prefill_kernel(qi_ref, ki_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, bq, bk, scale):
    """One grid step = one K/V head's (query block, key block) pair: the
    block's ``G * bq`` query rows (row ``g * bq + i`` is query head g at
    position ``qi * bq + i``) against ``bk`` keys, under the running
    softmax of the query block. Only a key block that reaches past the
    query block's first position is masked."""
    import jax.experimental.pallas as pl

    t = pl.program_id(1)
    qi, ki = qi_ref[t], ki_ref[t]
    f32 = jnp.float32

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MIN)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked):
        v = v_ref[0]
        scores = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale
        if masked:
            shape = scores.shape
            p_at = qi * bq + jnp.bitwise_and(
                jax.lax.broadcasted_iota(jnp.int32, shape, 0), bq - 1)
            seen = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1) <= p_at
            scores = jnp.where(seen, scores, _MIN)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        if masked:
            p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=f32)
        m_ref[...] = m_new

    on_diagonal = ki * bk + bk - 1 > qi * bq
    pl.when(on_diagonal)(lambda: step(True))
    pl.when(jnp.logical_not(on_diagonal))(lambda: step(False))

    @pl.when(last_ref[t] == 1)
    def _():
        # position 0 is seen by every query: no sum is zero
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def _kernel_prefill(rows, keys, values, *, bk, interpret):
    """``rows [N, nq, G * bq, D]`` (N = B * H_kv) against ``keys [N, T,
    D]``, ``values [N, T, D_v]`` in blocks of ``bk``: ``[N, nq, G * bq,
    D_v]``. A ``jit`` of its own, so that a model's layers share ONE traced
    and lowered kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, nq, R, D = rows.shape
    T, Dv = values.shape[1:]
    bq = T // nq
    if bq & (bq - 1) or T % bk:
        raise ValueError(
            f"the kernel walks blocks of a power of two of queries and "
            f"whole blocks of {bk} keys: got {T} tokens in blocks of {bq}")
    pairs = _prefill_pairs(nq, bq, bk)

    def query_block(n, t, qi, ki, last):
        return (n, qi[t], 0, 0)

    def key_block(n, t, qi, ki, last):
        return (n, ki[t], 0)

    of_queries = pl.BlockSpec((1, 1, R, D), query_block)
    of_keys = pl.BlockSpec((1, bk, D), key_block)
    of_values = pl.BlockSpec((1, bk, Dv), key_block)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bk=bk, scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N, len(pairs[0])),
            in_specs=[of_queries, of_keys, of_values],
            out_specs=pl.BlockSpec((1, 1, R, Dv), query_block),
            scratch_shapes=[pltpu.VMEM((R, Dv), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, nq, R, Dv), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gqa_attention_prefill",
    )(*(jnp.asarray(column) for column in pairs), rows, keys, values)

# -------------------------------------------------------------------------
# Decode: one token a slot over the rows the slot holds
# -------------------------------------------------------------------------
def cached_read(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                layer, n_rows: jax.Array, *,
                sink: Optional[jax.Array] = None, kernel: bool = False,
                interpret: bool = False) -> jax.Array:
    """``q [S, H_q, D]``, one token a slot, over rows ``< n_rows[s]`` of
    slot s in ``k_cache[layer]`` (``[L, S, R, H_kv * D]``, rows as
    ``pack_keys`` lays them) and ``v_cache[layer]`` (``[L, S, R, H_kv *
    D_v]``), the new token's row among them: ``[S, H_q, D_v]`` in q's
    dtype, zeros for a slot that holds no row. ``sink [H_q]``: module
    docstring. ``kernel`` reads with the lengths-aware Pallas kernel (the
    caller has asked ``kernel_reads``), ``interpret`` runs it in the Pallas
    interpreter."""
    S, Hq, D = q.shape
    _, _, R, C = k_cache.shape
    Hkv = C // D
    G = Hq // Hkv
    Dv = v_cache.shape[3] // Hkv
    if (k_cache.shape[1] != S or Hkv * D != C or G * Hkv != Hq
            or G > _ROW_TILE or Hkv * Dv != v_cache.shape[3]):
        raise ValueError(
            f"caches {k_cache.shape} and {v_cache.shape} do not hold {S} "
            f"slots of rows of whole {D}-wide K heads under {Hq} query heads")
    dtype = q.dtype
    n_rows = jnp.minimum(n_rows.astype(jnp.int32), R)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hkv, G)
    if kernel:
        # a K/V head's G query rows in one 16-row tile, zeros below them
        rows = jnp.pad(q.reshape(S, Hkv, G, D),
                       ((0, 0), (0, 0), (0, _ROW_TILE - G), (0, 0)))
        rows = _tail_in_tiles(rows).reshape(S, Hkv * _ROW_TILE, -1)
        if sink is not None:
            sink = jnp.pad(sink, ((0, 0), (0, _ROW_TILE - G))).reshape(-1, 1)
        out = _kernel_read(rows, k_cache, v_cache, n_rows, layer, sink,
                           interpret=interpret)
        return out.reshape(S, Hkv, _ROW_TILE, Dv)[:, :, :G].reshape(S, Hq, Dv)
    keys = _unpack_keys(k_cache[layer].astype(dtype), D)
    values = v_cache[layer].astype(dtype).reshape(S, R, Hkv, Dv)
    scores = jnp.einsum("shgd,srhd->shgr", q.reshape(S, Hkv, G, D), keys,
                        preferred_element_type=jnp.float32) * D ** -0.5
    held = (jnp.arange(R, dtype=jnp.int32)[None] < n_rows[:, None]
            )[:, None, None]
    scores = jnp.where(held, scores, _MIN)
    top = scores.max(axis=-1, keepdims=True)
    if sink is not None:
        top = jnp.maximum(top, sink[None, :, :, None])
    weights = jnp.where(held, jnp.exp(scores - top), 0.0)
    pv = jnp.einsum("shgr,srhd->shgd", weights.astype(dtype), values,
                    preferred_element_type=jnp.float32)
    total = weights.sum(axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink[None, :, :, None] - top)
    return (pv / jnp.where(total > 0, total, 1.0)).astype(dtype).reshape(
        S, Hq, Dv)


def _tail_in_tiles(rows: jax.Array) -> jax.Array:
    """Query rows ``[S, H_kv, R, D]`` as the read kernel meets packed keys:
    a head's whole tiles, then ONE tile that holds its tail where its keys'
    tail lies in the tile it shares (``pack_keys``), zeros beside it. Heads
    of whole tiles come back as they are."""
    S, Hkv, R, D = rows.shape
    body = _body(D)
    if body == D:
        return rows
    tail = D - body
    share = 128 // tail                      # heads that share a tile
    place = jax.nn.one_hot(jnp.arange(Hkv) % share, share, dtype=rows.dtype)
    tile = rows[..., None, body:] * place[None, :, None, :, None]
    return jnp.concatenate(
        [rows[..., :body], tile.reshape(S, Hkv, R, 128)], axis=-1)


def kernel_reads(k_cache: jax.Array, head_dim: int,
                 v_cache: Optional[jax.Array] = None) -> bool:
    """Whether the Pallas kernel can serve caches of this shape on this
    backend: Mosaic runs on a TPU, copies whole blocks of rows, a V head
    must be whole lane tiles of a stored row and a K head whole tiles and
    a tail that divides one, among heads enough to fill it."""
    from pytorch_distributed_tpu.ops.decode_attention import _platform

    depth, width = k_cache.shape[2:]
    heads, body = width // head_dim, _body(head_dim)
    tail = head_dim - body
    tails_fill_tiles = not tail or (128 % tail == 0
                                    and heads % (128 // tail) == 0)
    v_width = width if v_cache is None else v_cache.shape[3]
    return (_platform() == "tpu" and body % 128 == 0
            and width % head_dim == 0 and depth % 16 == 0
            and depth % min(_BLOCK, depth) == 0 and tails_fill_tiles
            and v_width % (heads * 128) == 0)


def _read_kernel(layer_ref, n_ref, q_ref, *refs, block, scale, sink):
    """One grid step = one slot: the ``ceil(n / block)`` blocks of its rows,
    copied from HBM two deep, under one running softmax. Rows ``h *
    _ROW_TILE ..`` of the queries belong to K/V head h, which is columns
    ``h * D_v ..`` of a stored V row and, of a stored K row, columns ``h *
    body ..`` and (a head with a tail: ``pack_keys``) the tile of tails its
    tail lies in, which the queries' last tile meets (``_tail_in_tiles``).
    ``sink``: a ``[N, 1]`` operand after the queries starts the softmax."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sink_ref = refs[0] if sink else None
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs[sink:]
    _, N, D = q_ref.shape
    n_kv_head = N // _ROW_TILE
    Dv = o_ref.shape[2]
    body = _body(k_buf.shape[2] // n_kv_head)      # of a stored K head
    tail = k_buf.shape[2] // n_kv_head - body
    dtype = q_ref.dtype
    f32 = jnp.float32
    s = pl.program_id(0)
    layer = layer_ref[0]
    n_held = n_ref[s]
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
    q = q_ref[0]

    def heads(fn, width):
        """``fn(h, rows, columns)`` of every K/V head, heads ``width``
        columns each, stacked along the rows."""
        return jnp.concatenate([
            fn(h, slice(h * _ROW_TILE, (h + 1) * _ROW_TILE),
               slice(h * width, (h + 1) * width))
            for h in range(n_kv_head)], axis=0)

    def meet(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def block_of_rows(i, carry):
        m, l, acc = carry
        start(i + 1)
        for copy in copies(i):
            copy.wait()
        k = k_buf[i % 2].astype(dtype)
        v = v_buf[i % 2].astype(dtype)
        if tail:
            def score(h, rows, cols):
                at = n_kv_head * body + h * tail // 128 * 128
                return (meet(q[rows, :body], k[:, cols])
                        + meet(q[rows, body:], k[:, at:at + 128]))
        else:
            def score(h, rows, cols):
                return meet(q[rows], k[:, cols])
        scores = heads(score, body) * scale                  # [N, block]
        held = (i * block + jax.lax.broadcasted_iota(
            jnp.int32, (N, block), 1)) < n_held
        scores = jnp.where(held, scores, _MIN)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # masked in the exponentials too: a slot's stale rows weigh 0.0
        p = jnp.where(held, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        weights = p.astype(dtype)
        acc = alpha * acc + heads(lambda h, rows, cols: jnp.dot(
            weights[rows], v[:, cols], preferred_element_type=f32), Dv)
        return m_new, l, acc

    start_at = ((sink_ref[...], jnp.ones((N, 1), f32)) if sink else
                (jnp.full((N, 1), _MIN, f32), jnp.zeros((N, 1), f32)))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block_of_rows, (
        *start_at, jnp.zeros((N, Dv), f32)))
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_read(q_rows, k_cache, v_cache, n_rows, layer, sink=None, *,
                 interpret):
    """``q_rows [S, H_kv * _ROW_TILE, D]`` (``_tail_in_tiles``) over slot
    s's rows ``< n_rows[s]`` of ``layer``: ``[S, H_kv * _ROW_TILE, D_v]``
    in the queries' dtype. ``sink``: ``[H_kv * _ROW_TILE, 1]`` float32 or
    None. ``layer`` is an operand and the function a ``jit`` of its own, so
    that a model's layers of one depth share ONE traced and lowered
    kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, N, D = q_rows.shape
    depth, C = k_cache.shape[2:]
    Cv = v_cache.shape[3]
    n_kv_head = N // _ROW_TILE
    block = min(_BLOCK, depth)
    if depth % block:
        raise ValueError(
            f"the kernel reads whole blocks of {block} rows: got a cache "
            f"{depth} deep")

    def per_slot(s, layer, n):
        return (s, 0, 0)

    rows = pl.BlockSpec((1, N, D), per_slot)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    sinks = [] if sink is None else [sink]
    return pl.pallas_call(
        functools.partial(_read_kernel, block=block, sink=len(sinks),
                          scale=(C // n_kv_head) ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[rows] + [pl.BlockSpec(
                (N, 1), lambda s, layer, n: (0, 0))] * len(sinks)
            + [whole, whole],
            out_specs=pl.BlockSpec((1, N, Cv // n_kv_head), per_slot),
            scratch_shapes=[
                pltpu.VMEM((2, block, C), k_cache.dtype),
                pltpu.VMEM((2, block, Cv), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, N, Cv // n_kv_head),
                                       q_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gqa_attention_read",
    )(jnp.asarray(layer, jnp.int32)[None], n_rows.astype(jnp.int32), q_rows,
      *sinks, k_cache, v_cache)
