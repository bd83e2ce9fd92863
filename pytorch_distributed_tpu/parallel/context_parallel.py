"""Context parallelism — ring attention + Ulysses over mesh axes.

Capability parity (SURVEY.md §2.2 "CP", §5.7): torch
``_context_parallel/_attention.py`` — sequence sharded across ranks, KV
blocks rotating around the ring (``_RingRotater``), partial attention merged
with online softmax (``_SDPAMerger``), causal load balancing
(``_load_balancer.py``), differentiable backward (``:488``); plus
DeepSpeed-Ulysses-style head-wise all-to-all (absent in torch — SURVEY
flags it as a cheap add on TPU).

TPU-first:
  * the ring is ``lax.ppermute`` over an ICI mesh axis inside ``shard_map``
    — the canonical TPU ring-attention pattern; each hop overlaps with the
    local block attention under XLA's scheduler.
  * the DEFAULT local op is the Pallas flash kernel
    (``ops/flash_attention.py``): O(T_local·D) activation memory, per-hop
    (out, logsumexp) partials merged exactly, and a ring-level custom VJP
    whose backward re-rotates KV with dK/dV accumulators traveling
    alongside their chunk (``_ring_flash_fn``). ``impl="einsum"`` keeps
    the reference math (materialized scores) as the oracle; its backward
    is derived by AD through ``lax.scan`` + ``ppermute`` with
    ``jax.checkpoint`` bounding per-hop activation storage.
  * causal masking with sequence sharding uses per-chunk global offsets; the
    zigzag load balancer (``zigzag_reorder``) equalizes causal work across
    ranks like torch's ``_load_balancer``.

Use :func:`make_ring_attention` / :func:`make_ulysses_attention` to get an
``attn_impl`` pluggable into ``GPT2Config.attn_impl`` — the model tree stays
untouched (SURVEY's SDPA-interception role).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from pytorch_distributed_tpu.mesh import DeviceMesh

P = PartitionSpec

__all__ = [
    "ring_attention",
    "make_ring_attention",
    "ulysses_attention",
    "make_ulysses_attention",
    "zigzag_reorder",
    "zigzag_restore",
]

_NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One Q-block × KV-block partial attention.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; mask: [Tq, Tk] bool or None.
    Returns (unnormalized out [B, Tq, H, D] fp32, logsumexp-ish pieces):
    scores in fp32, per-row max m and sum s for online-softmax merging.
    """
    D = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B, H, Tq, 1]
    # guard fully-masked rows (exp of -inf rows)
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)  # [B, H, Tq, 1]
    out = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return out, m_safe, denom


def _merge(acc_out, acc_m, acc_den, out, m, den):
    """Online-softmax combine of two partial attention results
    (the _SDPAMerger role)."""
    new_m = jnp.maximum(acc_m, m)
    a = jnp.exp(acc_m - new_m)
    b = jnp.exp(m - new_m)
    new_den = acc_den * a + den * b
    # out tensors are [B, T, H, D]; m/den are [B, H, T, 1] -> move axes
    a_t = jnp.moveaxis(a, 1, 2)  # [B, T, H, 1]
    b_t = jnp.moveaxis(b, 1, 2)
    new_out = acc_out * a_t + out * b_t
    return new_out, new_m, new_den


def ring_attention(
    q, k, v, *, axis_name: str, causal: bool = True, zigzag: bool = False
):
    """Ring attention over a mesh axis (call INSIDE shard_map).

    q/k/v: the LOCAL sequence chunk [B, T_local, H, D]; sequence dim is
    sharded over ``axis_name``. Returns [B, T_local, H, D] in q.dtype.

    Each of the n ring steps attends the local Q chunk to the KV chunk
    currently held, then rotates KV one hop (ppermute). Causal masking uses
    global chunk offsets; with ``zigzag`` the chunks are assumed reordered by
    :func:`zigzag_reorder` (rank r holds chunks r and 2n-1-r) so causal work
    is balanced.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, T, H, D = q.shape

    def chunk_positions(owner):
        """Global positions [T] of the chunk owned by ``owner``."""
        if not zigzag:
            return owner * T + jnp.arange(T)
        # zigzag: owner holds sub-chunks owner and 2n-1-owner, each T//2
        half = T // 2
        lo = owner * half + jnp.arange(half)
        hi = (2 * n - 1 - owner) * half + jnp.arange(half)
        return jnp.concatenate([lo, hi])

    q_pos = chunk_positions(idx)

    acc_out = jnp.zeros((B, T, H, D), jnp.float32)
    acc_m = jnp.full((B, H, T, 1), _NEG_INF, jnp.float32)
    acc_den = jnp.zeros((B, H, T, 1), jnp.float32)

    def step(carry, hop):
        kv, acc_out, acc_m, acc_den = carry
        k_cur, v_cur = kv
        owner = (idx - hop) % n  # whose chunk we hold at this hop
        if causal:
            kv_pos = chunk_positions(owner)
            mask = q_pos[:, None] >= kv_pos[None, :]
        else:
            mask = None
        out, m, den = _block_attn(q, k_cur, v_cur, mask)
        acc_out, acc_m, acc_den = _merge(acc_out, acc_m, acc_den, out, m, den)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return ((k_nxt, v_nxt), acc_out, acc_m, acc_den), None

    (_, acc_out, acc_m, acc_den), _ = lax.scan(
        step, ((k, v), acc_out, acc_m, acc_den), jnp.arange(n)
    )
    den_t = jnp.moveaxis(acc_den, 1, 2)  # [B, T, H, 1]
    out = acc_out / jnp.maximum(den_t, 1e-20)
    return out.astype(q.dtype)


def _chunk_positions_fn(n: int, T: int, zigzag: bool):
    def chunk_positions(owner):
        if not zigzag:
            return owner * T + jnp.arange(T)
        half = T // 2
        lo = owner * half + jnp.arange(half)
        hi = (2 * n - 1 - owner) * half + jnp.arange(half)
        return jnp.concatenate([lo, hi])

    return chunk_positions


@functools.lru_cache(maxsize=None)
def _ring_flash_fn(axis_name: str, causal: bool, zigzag: bool,
                   block_q: int, block_k: int, interpret):
    """Ring attention with the Pallas flash kernel as the local op
    (call INSIDE shard_map). Peak memory is O(T_local·D) — the [B,H,T,T]
    score tensor of the einsum path never exists (r2 weak #4).

    Differentiable via a ring-level custom_vjp (the torch ``:488`` ring
    backward): the forward merges per-hop (out, logsumexp) partials; the
    backward re-rotates KV around the ring, calling the flash backward
    kernels per hop with the FINAL logsumexp/delta — dK/dV accumulators
    travel WITH their chunk and arrive home after n hops.
    """
    from pytorch_distributed_tpu.ops.flash_attention import _bwd, _fwd

    def _merge_lse(out_acc, lse_acc, out_h, lse_h):
        new_lse = jnp.logaddexp(lse_acc, lse_h)            # [B, H, T]
        w_old = jnp.exp(lse_acc - new_lse)
        w_new = jnp.exp(lse_h - new_lse)
        out_acc = (
            out_acc * jnp.moveaxis(w_old, 1, 2)[..., None]
            + out_h.astype(jnp.float32)
            * jnp.moveaxis(w_new, 1, 2)[..., None]
        )
        return out_acc, new_lse

    def _hop_positions(chunk_positions, idx, n, hop):
        owner = (idx - hop) % n
        return chunk_positions(idx), chunk_positions(owner)

    @jax.custom_vjp
    def ring_flash(q, k, v):
        out, lse = _ring_fwd(q, k, v)
        return out

    def _ring_fwd(q, k, v):
        n = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        B, T, H, D = q.shape
        chunk_positions = _chunk_positions_fn(n, T, zigzag)

        out_acc = jnp.zeros((B, T, H, D), jnp.float32)
        lse_acc = jnp.full((B, H, T), -1e30, jnp.float32)

        def step(carry, hop):
            k_cur, v_cur, out_acc, lse_acc = carry
            if causal:
                q_pos, kv_pos = _hop_positions(
                    chunk_positions, idx, n, hop
                )
            else:
                q_pos = kv_pos = None
            out_h, lse_h = _fwd(
                q, k_cur, v_cur, q_pos, kv_pos,
                block_q=block_q, block_k=block_k, interpret=interpret,
                out_dtype=jnp.float32,  # partials merge unquantized
            )
            out_acc, lse_acc = _merge_lse(out_acc, lse_acc, out_h, lse_h)
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            return (k_nxt, v_nxt, out_acc, lse_acc), None

        (_, _, out_acc, lse_acc), _ = lax.scan(
            step, (k, v, out_acc, lse_acc), jnp.arange(n)
        )
        return out_acc.astype(q.dtype), lse_acc

    def ring_flash_fwd(q, k, v):
        out, lse = _ring_fwd(q, k, v)
        return out, (q, k, v, out, lse)

    def ring_flash_bwd(res, do):
        q, k, v, out, lse = res
        n = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        T = q.shape[1]
        chunk_positions = _chunk_positions_fn(n, T, zigzag)

        dq_acc = jnp.zeros(q.shape, jnp.float32)
        dk0 = jnp.zeros(k.shape, jnp.float32)
        dv0 = jnp.zeros(v.shape, jnp.float32)

        def step(carry, hop):
            k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
            if causal:
                q_pos, kv_pos = _hop_positions(
                    chunk_positions, idx, n, hop
                )
            else:
                q_pos = kv_pos = None
            dq_h, dk_h, dv_h = _bwd(
                q, k_cur.astype(q.dtype), v_cur.astype(q.dtype),
                q_pos, kv_pos, out, lse, do,
                block_q=block_q, block_k=block_k, interpret=interpret,
            )
            dq_acc = dq_acc + dq_h.astype(jnp.float32)
            dk_cur = dk_cur + dk_h.astype(jnp.float32)
            dv_cur = dv_cur + dv_h.astype(jnp.float32)
            perm = [(i, (i + 1) % n) for i in range(n)]
            rot = lambda x: lax.ppermute(x, axis_name, perm)
            return (
                rot(k_cur), rot(v_cur), rot(dk_cur), rot(dv_cur), dq_acc
            ), None

        (k_fin, v_fin, dk_fin, dv_fin, dq_acc), _ = lax.scan(
            step, (k.astype(jnp.float32), v.astype(jnp.float32),
                   dk0, dv0, dq_acc),
            jnp.arange(n),
        )
        # after n rotations every chunk (and its grad accumulator) is home
        return (
            dq_acc.astype(q.dtype),
            dk_fin.astype(k.dtype),
            dv_fin.astype(v.dtype),
        )

    ring_flash.defvjp(ring_flash_fwd, ring_flash_bwd)
    return ring_flash


def make_ring_attention(
    mesh: DeviceMesh,
    axis: str = "cp",
    *,
    causal: bool = True,
    zigzag: bool = False,
    remat: bool = True,
    impl: str = "flash",
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Build an ``attn_impl(q, k, v, causal=...)`` over GLOBAL [B, T, H, D]
    arrays: shard_map shards the sequence dim over ``axis`` and runs ring
    attention per device. Plug into ``GPT2Config.attn_impl``.

    ``impl="flash"`` (default) uses the Pallas flash kernel as the local op
    — O(T_local·D) activation memory; ``impl="einsum"`` keeps the original
    reference math (materializes per-hop [B,H,T_local,T_local] scores) as
    the oracle path.
    """
    jmesh = mesh.jax_mesh if isinstance(mesh, DeviceMesh) else mesh
    spec = P(None, axis, None, None)
    if impl == "flash":
        from pytorch_distributed_tpu.ops.flash_attention import (
            _interpret_default,
        )

        if interpret is None:
            interpret = _interpret_default()

        @functools.partial(jax.jit, static_argnames=("causal",))
        def attn(q, k, v, causal: bool = causal):
            fn = _ring_flash_fn(
                axis, causal, zigzag, block_q, block_k, interpret
            )
            return jax.shard_map(
                fn, mesh=jmesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )(q, k, v)

        return attn

    @functools.partial(jax.jit, static_argnames=("causal",))
    def attn(q, k, v, causal: bool = causal):
        fn = functools.partial(
            ring_attention, axis_name=axis, causal=causal, zigzag=zigzag
        )
        if remat:
            fn = jax.checkpoint(fn)
        # jit wrapper: remat's closed_call can't be eagerly evaluated inside
        # shard_map; nested jit is free when already under an outer jit
        return jax.shard_map(
            fn, mesh=jmesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attn


# -- Ulysses (head-wise all-to-all) ----------------------------------------
def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = True,
                      impl: str = "einsum", interpret=None,
                      block_q: int = 128, block_k: int = 128):
    """DeepSpeed-Ulysses sequence parallelism (call INSIDE shard_map):
    all-to-all swaps the sharded dim from sequence to heads, each device
    runs FULL-sequence attention on H/n heads, and a second all-to-all
    swaps back. Two cheap ICI all-to-alls instead of n-1 ring hops; needs
    n_heads % axis_size == 0.

    ``impl="flash"`` runs the local full-sequence attention as the Pallas
    flash kernel — O(T·D) memory instead of the [B, H/n, T, T] scores the
    einsum path materializes (r2 weak #4)."""
    n = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(f"ulysses: heads {H} not divisible by axis size {n}")

    def seq_to_heads(x):  # [B, T/n, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # [B, T, H/n, D] -> [B, T/n, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if impl == "flash":
        from pytorch_distributed_tpu.ops.flash_attention import (
            flash_attention,
        )

        outh = flash_attention(
            qh, kh, vh, causal=causal, interpret=interpret,
            block_q=block_q, block_k=block_k,
        )
        return heads_to_seq(outh)
    T = qh.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool)) if causal else None
    out, _, den = _block_attn(qh, kh, vh, mask)
    den_t = jnp.moveaxis(den, 1, 2)
    outh = (out / jnp.maximum(den_t, 1e-20)).astype(q.dtype)
    return heads_to_seq(outh)


def make_ulysses_attention(
    mesh: DeviceMesh, axis: str = "cp", *, causal: bool = True,
    impl: str = "flash", interpret=None,
    block_q: int = 128, block_k: int = 128,
):
    """Global-array wrapper for :func:`ulysses_attention` (see
    make_ring_attention)."""
    jmesh = mesh.jax_mesh if isinstance(mesh, DeviceMesh) else mesh
    spec = P(None, axis, None, None)
    if impl == "flash":
        from pytorch_distributed_tpu.ops.flash_attention import (
            _interpret_default,
        )

        if interpret is None:
            interpret = _interpret_default()

    def attn(q, k, v, causal: bool = causal):
        fn = functools.partial(
            ulysses_attention, axis_name=axis, causal=causal, impl=impl,
            interpret=interpret, block_q=block_q, block_k=block_k,
        )
        return jax.shard_map(
            fn, mesh=jmesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attn


# -- causal load balancing (zigzag) ----------------------------------------
def zigzag_reorder(x, n_shards: int, seq_dim: int = 1):
    """Reorder the GLOBAL sequence so shard r gets chunks (r, 2n-1-r) — the
    round-trip causal load balancer (torch ``_load_balancer.py`` role).
    Apply to tokens/activations BEFORE sharding; undo with
    :func:`zigzag_restore`."""
    T = x.shape[seq_dim]
    if T % (2 * n_shards):
        raise ValueError(f"seq len {T} not divisible by 2*{n_shards}")
    chunks = jnp.split(x, 2 * n_shards, axis=seq_dim)
    order = []
    for r in range(n_shards):
        order += [r, 2 * n_shards - 1 - r]
    return jnp.concatenate([chunks[i] for i in order], axis=seq_dim)


def zigzag_restore(x, n_shards: int, seq_dim: int = 1):
    """Inverse of :func:`zigzag_reorder`."""
    order = []
    for r in range(n_shards):
        order += [r, 2 * n_shards - 1 - r]
    inv = [0] * (2 * n_shards)
    for pos, src in enumerate(order):
        inv[src] = pos
    chunks = jnp.split(x, 2 * n_shards, axis=seq_dim)
    return jnp.concatenate([chunks[i] for i in inv], axis=seq_dim)
