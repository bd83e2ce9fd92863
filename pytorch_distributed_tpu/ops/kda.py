"""Kimi Delta Attention (KDA): the gated delta rule with a decay a channel,
behind a short causal convolution. The recurrent half of a hybrid stack:
a sequence's state in one layer is ONE float32 matrix a head, ``S [d_k,
d_v]``, overwritten every token, and the last ``K - 1`` inputs of the
convolution (its "tail").

For one head, with ``a_t`` in ``(0, 1)^d_k`` (``log_a`` its logarithm),
``beta_t`` in ``(0, 1)``, ``k_t`` of unit length and ``q_t`` already scaled::

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``gated_delta_rule`` computes it in two forms with one result:

  * recurrent (``chunk=None``; a decode step): ``S' = diag(a_t) S_{t-1}``,
    ``u_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' + k_t u_t^T``: one
    rank-one update a head a sequence, every byte of the state read and
    written once more than the reduction over ``k`` needs.
  * chunked (``chunk=64``; a prefill): inside a chunk of C tokens, with
    ``g_t = sum_{s <= t} log a_s`` (from the chunk's start) and ``S_0`` the
    state the chunk receives, the same ``u`` solve ``(I + A) U = beta (V -
    (K e^g) S_0)`` with ``A[t, j] = beta_t sum_d k_t[d] k_j[d] e^(g_t[d] -
    g_j[d])`` for ``j < t`` (the within-chunk triangular solve: ``A`` is
    strictly lower, so ``(I + A)^-1`` is the product ``(I - A)(I + A^2)(I +
    A^4)...`` of ``log2 C`` factors), then ``O = (Q e^g) S_0 + A_qk U`` with
    ``A_qk[t, j] = sum_d q_t[d] k_j[d] e^(g_t[d] - g_j[d])`` for ``j <= t``,
    and the carry ``S_C = diag(e^(g_C)) S_0 + (K e^(g_C - g))^T U``: between
    chunks only the state moves, in a ``lax.scan``.

No exponent is ever positive. ``e^(g_t - g_j)`` with ``j <= t`` is at most
one, but ``e^(g_t) e^(-g_j)`` is not computable apart: a head whose
channels decay by 1.6 a token (``A_log = log 16``, ``dt = 0.1``) has
``e^(-g_j) = e^102`` at the end of a chunk, past float32. So a chunk is cut
into sub-blocks of ``_SUB`` rows: between two sub-blocks the decay is split
at the later one's first row, ``e^(g_t - r) e^(r - g_j)`` with both factors
at most one (two matrix products), and inside a sub-block the differences
are taken a pair at a time (``[_SUB, _SUB, d_k]`` a head: a sixteenth of the
pairs of a chunk).

A position that is not ``valid`` (the pad of a bucketed prefill) gets
``beta = 0`` and ``a = 1``: the state passes through it untouched, whatever
its ``k`` and ``v`` are. Everything here is float32 at
``Precision.HIGHEST``: on a TPU a float32 product otherwise rounds its
operands to bfloat16, and a state that is added to six thousand times keeps
that.

``short_conv`` is the causal depthwise convolution before it (``K`` taps
over ``W`` channels, ``y_t = sum_i w[i] x_{t - K + 1 + i}``), from a tail or
from zeros; ``kda_mix`` puts the two together as a layer's mixer uses them,
for a prompt (chunked, from a zero state, the tail taken at each sequence's
LAST REAL position) and for a decode step (recurrent, one token a sequence,
from the state and tail a cache holds).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule", "short_conv", "kda_mix", "CHUNK"]

#: tokens of a chunk of the chunked form (the triangular solve is C x C)
CHUNK = 64
#: rows of a sub-block, whose decays are taken a pair at a time
_SUB = 16
_HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=f32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular:
    ``sum_m (-a)^m = (I - a)(I + a^2)(I + a^4)...`` (``a^C = 0``)."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    inv, power, n = eye - a, a, 2
    while n < C:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = _mm("...ij,...jk->...ik", inv, eye + power)
        n *= 2
    return inv


def _pair_sums(rows, k, g, sub):
    """``out[t, j] = sum_d rows[t, d] k[j, d] e^(g[t, d] - g[j, d])`` for
    ``j <= t``, zero above the diagonal. ``rows, k, g [..., C, d]``; no
    exponent positive (module docstring)."""
    *lead, C, d = g.shape
    n = C // sub
    blocks = tuple(lead) + (n, sub)
    gb, rb, kb = (x.reshape(blocks + (d,)) for x in (g, rows, k))
    first = gb[..., :1, :]                        # r_I: [..., n, 1, d]
    # between sub-blocks: e^(g_t - r_I) for t in I, e^(r_I - g_j) for j < I
    left = rb * jnp.exp(gb - first)               # [..., n, sub, d]
    right = k[..., None, :, :] * jnp.exp(
        jnp.minimum(first - g[..., None, :, :], 0.0))   # [..., n, C, d]
    off = _mm("...nid,...njd->...nij", left, right).reshape(
        tuple(lead) + (C, C))
    # inside a sub-block: a pair at a time
    i = jnp.arange(sub)
    lower = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(
        lower[..., None], gb[..., :, None, :] - gb[..., None, :, :],
        -jnp.inf))                                # [..., n, sub, sub, d]
    diag = (rb[..., :, None, :] * kb[..., None, :, :] * decay).sum(-1)
    diag = (diag[..., :, :, None, :]
            * jnp.eye(n, dtype=diag.dtype)[:, None, :, None]).reshape(
                tuple(lead) + (C, C))
    block_of = jnp.arange(C) // sub
    return jnp.where(block_of[:, None] > block_of[None, :], off, diag)


def _chunk(state, xs, sub):
    """One chunk of the chunked form: ``state [B, H, d_k, d_v]``, ``q, k,
    log_a [B, H, C, d_k]``, ``v [B, H, C, d_v]``, ``beta [B, H, C]``."""
    q, k, v, log_a, beta = xs
    g = jnp.cumsum(log_a, axis=-2)
    C = g.shape[-2]
    strictly = jnp.tril(jnp.ones((C, C), bool), -1)
    a_kk = jnp.where(strictly, _pair_sums(k, k, g, sub), 0.0) \
        * beta[..., None]
    a_qk = _pair_sums(q, k, g, sub)
    decayed = jnp.exp(g)
    solved = _mm("...ij,...jd->...id", _unit_lower_inverse(a_kk),
                 beta[..., None] * jnp.concatenate([v, k * decayed], -1))
    w_v, w_k = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    u = w_v - _mm("...ck,...kv->...cv", w_k, state)
    o = _mm("...ck,...kv->...cv", q * decayed, state) \
        + _mm("...ij,...jv->...iv", a_qk, u)
    last = g[..., -1:, :]
    state = jnp.exp(last)[..., 0, :, None] * state \
        + _mm("...ck,...cv->...kv", k * jnp.exp(last - g), u)
    return state, o


def _step(state, xs):
    """One token of the recurrent form: ``state [B, H, d_k, d_v]``, ``q, k,
    log_a [B, H, d_k]``, ``v [B, H, d_v]``, ``beta [B, H]``. Products and
    sums over the state as they lie (no matrix unit: a rank-one update)."""
    q, k, v, log_a, beta = xs
    state = jnp.exp(log_a)[..., None] * state
    u = beta[..., None] * (v - (k[..., None] * state).sum(-2))
    state = state + k[..., None] * u[..., None, :]
    return state, (q[..., None] * state).sum(-2)


def gated_delta_rule(q, k, v, log_a, beta, state, *,
                     chunk: Optional[int] = None, valid=None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The gated delta rule over ``T`` tokens (module docstring).

    Args:
      q, k: ``[B, T, H, d_k]``; ``k`` of unit length, ``q`` scaled.
      v: ``[B, T, H, d_v]``. ``log_a``: ``[B, T, H, d_k]``, at most 0.
      beta: ``[B, T, H]``. ``state``: ``[B, H, d_k, d_v]`` float32.
      chunk: None for the recurrent form (a token at a time), else the
        chunk length of the chunked form (fewer tokens than a chunk are
        one chunk; a last chunk is filled with positions not valid).
      valid: ``[B, T]`` bool or None: positions that are not leave the
        state as it was (their output is of no use).

    Returns ``(o [B, T, H, d_v] float32, state)``.
    """
    B, T, H, _ = q.shape
    q, k, v, log_a, beta = (x.astype(f32) for x in (q, k, v, log_a, beta))
    if valid is not None:
        beta = jnp.where(valid[..., None], beta, 0.0)
        log_a = jnp.where(valid[..., None, None], log_a, 0.0)
    state = state.astype(f32)
    if chunk is None:
        if T == 1:
            state, o = _step(state, (q[:, 0], k[:, 0], v[:, 0], log_a[:, 0],
                                     beta[:, 0]))
            return o[:, None], state
        state, o = jax.lax.scan(_step, state, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_a, beta)))
        return jnp.moveaxis(o, 0, 1), state
    C = min(chunk, T)
    if T % C:
        # whole chunks: the rest are positions that are not valid
        more = ((0, 0), (0, -T % C))
        o, state = gated_delta_rule(
            *(jnp.pad(x, more + ((0, 0),) * (x.ndim - 2))
              for x in (q, k, v, log_a, beta)), state, chunk=chunk,
            valid=jnp.pad(jnp.ones((B, T), bool), more))
        return o[:, :T], state
    sub = _SUB if C % _SUB == 0 else C

    def chunks(x):                  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((B, T // C, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    state, o = jax.lax.scan(
        lambda s, xs: _chunk(s, xs, sub), state,
        tuple(chunks(x) for x in (q, k, v, log_a, beta)))
    # [N, B, H, C, d_v] -> [B, T, H, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(B, T, H, o.shape[-1]), state


def short_conv(x, w, tail=None):
    """Causal depthwise convolution: ``y_t = sum_i w[i] x_{t - K + 1 + i}``
    over ``x [B, T, W]`` with taps ``w [K, W]``, the ``K - 1`` inputs before
    ``x`` from ``tail [B, K - 1, W]`` (zeros without one). float32 sums,
    returned in x's dtype, with the window ``[B, T + K - 1, W]`` (whose last
    ``K - 1`` rows are the next token's tail)."""
    K = w.shape[0]
    T = x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(window[:, i:i + T].astype(f32) * w[i].astype(f32)
            for i in range(K))
    return y.astype(x.dtype), window


def _unit(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def kda_mix(x, w_conv, log_a, beta, state, tail, *, n_heads: int,
            lengths=None, decode: bool = False, chunk: int = CHUNK):
    """A KDA layer's mixer between its projections and its output gate.

    Args:
      x: ``[B, T, 3 * H * d]``: the projections ``[W_q x | W_k x | W_v x]``
        BEFORE the convolution. ``w_conv``: ``[K, 3 * H * d]``.
      log_a: ``[B, T, H, d]`` float32. ``beta``: ``[B, T, H]`` float32.
      state: ``[B, H, d, d]`` float32, ``tail``: ``[B, K - 1, 3 * H * d]``:
        what a decode step starts from; a prompt starts from zeros whatever
        these hold.
      lengths: ``[B]``, the real tokens of each prompt (None: all of T).
      decode: one new token a sequence, recurrent; else a prompt, chunked.

    Returns ``(o [B, T, H, d] float32, state, tail)``: ``q = unit(silu(conv
    (W_q x))) / sqrt(d)``, ``k = unit(silu(conv(W_k x)))``, ``v = silu(conv
    (W_v x))`` through ``gated_delta_rule``; the tail is the last ``K - 1``
    REAL inputs of the convolution (zeros before a sequence's start).
    """
    B, T, W = x.shape
    K = w_conv.shape[0]
    d = W // (3 * n_heads)
    if decode:
        if T != 1:
            raise ValueError(
                f"a recurrent state takes one new token a sequence (got "
                f"{T}): it cannot roll back")
        y, window = short_conv(x, w_conv, tail)
        new_tail, valid = window[:, 1:], None
    else:
        y, window = short_conv(x, w_conv)
        if lengths is None:
            new_tail, valid = window[:, T:], None
        else:
            # window row r is input r - (K - 1): the K - 1 before ``length``
            at = lengths[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
            new_tail = jnp.take_along_axis(window, at[..., None], axis=1)
            valid = jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None]
        state = jnp.zeros_like(state)
    q, k, v = (a.reshape(B, T, n_heads, d) for a in jnp.split(
        jax.nn.silu(y.astype(f32)), 3, axis=-1))
    o, state = gated_delta_rule(
        _unit(q) * d ** -0.5, _unit(k), v, log_a, beta, state,
        chunk=None if decode else chunk, valid=valid)
    return o, state, new_tail.astype(tail.dtype)
