"""Plain reference of the ``sarvam_mla`` family (``sarvamai/sarvam-105b``):
the forward pass in ``jax.numpy``, float32,
``default_matmul_precision("highest")``. No cache, no page, no kernel, no
sorting: every layer attends with keys and values EXPANDED from the whole
sequence's latents under a mask (the queries in blocks, so that 28k
positions fit), and every held expert is applied to every token under its
gate or zero. It runs a layer at a time (``forward`` is a Python loop over
jitted layers), so that at the published widths and 29,440 positions it fits
beside the program's bfloat16 weights. It shares nothing with ``models/``
and ``ops/``, nor with another family's reference.

``config`` is the configuration file's dict (the source's keys). Sizes: d
``hidden_size``; H ``num_attention_heads``, d_n ``qk_nope_head_dim``, d_r
``qk_rope_head_dim``, d_v ``v_head_dim``, d_c ``kv_lora_rank``; F
``intermediate_size``, F_e ``moe_intermediate_size``, E ``router_width``
(the published ``num_experts``), k ``num_experts_per_tok``, eps
``rms_norm_eps``. ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. For layer
``l`` (from 0) with input ``h [T, d]``::

    x = RMSNorm(h)                          norms BEFORE the sublayers (ASSUMED)
    q = x W_q                               H heads of [q_n | q_r]; no query latent
    [c | k_r] = x W_kva;  c <- RMSNorm(c)   ``use_qk_norm`` read as the latent's
                                            norm, one gain of d_c (ASSUMED)
    q_r, k_r rotated by the position p      pair i = columns (i, i + d_r / 2)
                                            (ASSUMED), angle p * f_i, f from YaRN:
        f_i = theta^(-2i / d_r);  lo, hi = the dimensions that turn beta_fast and
        beta_slow times over the original positions (floor, ceil, clipped);
        ramp_i = clip((i - lo) / (hi - lo), 0, 1);  f_i <- f_i / factor * ramp_i
        + f_i * (1 - ramp_i)                (DeepSeek-V3's ``find_correction_*``)
    [k_n,j | v_j] = c W_kvb[j]              a head j;  k_j = [k_n,j | k_r]
    a = causal softmax(q k^T * scale) v     scale = (d_n + d_r)^-1/2 * m^2,
                                            m = 0.1 * mscale_all_dim * ln(factor) + 1
    h <- h + a W_o;   h <- h + mlp(RMSNorm(h))

    mlp, l < first_k_dense_replace:  (silu(x W_g) * x W_u) W_d, F wide
    mlp, the others:
        s = sigmoid(x W_r)                        [E], float32
        chosen = the k largest of s + b           (b: 0 at random weights)
        g_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
                                                  (sigmoid, renormalised: ASSUMED)
        y = sum over the chosen HELD experts g_i FFN_i(x) + FFN_shared(x)

THE SHARE: the file's ``num_experts`` experts from ``held_experts_first`` on
are held; a chosen expert that is not held adds nothing (it is another
chip's part), and the gates are normalised over all k chosen, held or not.
After the last layer ``RMSNorm``, then the untied head over the file's
``vocab_size`` rows.

Knobs exist for the readings a cell's limits are set from, and for nothing
else: ``round_to`` (both operands of every matrix product, and q, k, v,
rounded to a narrower dtype, by name), ``experts_per_token``,
``no_rotation`` (q_r and k_r as they come), ``no_yarn`` (plain frequencies
and scale), ``swap_page`` = (first, other, size): positions ``first ..
first + size`` of every layer's latents replaced by those of positions
``other ..`` (a chain whose one page is another's), ``drop_page`` = (first,
size): those positions hidden from every query after them (a read that
stops a page short, or skips one).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
HEAD_GROUP = 8
TOKEN_BLOCK = 1024
f32 = jnp.float32


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.matmul(a.astype(f32), b.astype(f32))


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(f32)


def _in_blocks(fn, xs, block):
    """``fn`` over the rows of the arrays ``xs``, ``block`` at a time
    (memory only: the result is that of ``fn(xs)``)."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if n <= block or n % block:
        return fn(xs)
    out = jax.lax.map(fn, jax.tree_util.tree_map(
        lambda a: a.reshape((n // block, block) + a.shape[1:]), xs))
    return out.reshape((n,) + out.shape[2:])


def inv_freq(s, plain: bool = False) -> np.ndarray:
    """The ``d_r / 2`` frequencies of the module docstring."""
    dim, theta = s["qk_rope_head_dim"], s["rope_theta"]
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if plain:
        return freqs.astype(np.float32)

    def turns_at(turns):
        return dim * math.log(s["rope_original"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(turns_at(s["rope_beta_fast"])), 0)
    hi = min(math.ceil(turns_at(s["rope_beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (freqs / s["rope_factor"] * ramp + freqs * (1 - ramp)
            ).astype(np.float32)


def _rotate(x, freqs):
    """x [T, H, D] at positions 0..T-1: pair i = columns (i, i + D/2)."""
    T, _, D = x.shape
    angle = jnp.arange(T, dtype=f32)[:, None, None] * jnp.asarray(freqs)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def latent_attention(p, x, s, *, round_to, no_rotation, no_yarn, swap_page,
                     drop_page):
    """x [T, d] (normed) -> [T, d]. The heads go ``HEAD_GROUP`` at a time
    (memory only: a head's output depends on no other head)."""
    T, d = x.shape
    H, d_n, d_r = (s["num_attention_heads"], s["qk_nope_head_dim"],
                   s["qk_rope_head_dim"])
    d_c, d_v = s["kv_lora_rank"], s["v_head_dim"]
    mm = functools.partial(_mm, round_to=round_to)
    kv = mm(x, p["kv_a"])
    c = _rms(kv[:, :d_c], p["kv_norm"], s["rms_norm_eps"])
    k_r = kv[:, None, d_c:]                                  # [T, 1, d_r]
    scale = (d_n + d_r) ** -0.5
    if not no_yarn:
        m = 0.1 * s["rope_mscale_all_dim"] * math.log(s["rope_factor"]) + 1.0
        scale = scale * m * m
    freqs = None if no_rotation else inv_freq(s, plain=no_yarn)
    if freqs is not None:
        k_r = _rotate(k_r, freqs)
    if swap_page is not None:
        first, other, size = swap_page
        c = c.at[first:first + size].set(c[other:other + size])
        k_r = k_r.at[first:first + size].set(k_r[other:other + size])
    keys_at = jnp.arange(T)
    hg = min(HEAD_GROUP, H)

    def heads(acc, weights):
        w_q, w_kvb, w_o = weights
        q = mm(x, w_q).reshape(T, hg, d_n + d_r)
        if freqs is not None:
            q = jnp.concatenate(
                [q[..., :d_n], _rotate(q[..., d_n:], freqs)], -1)
        expanded = mm(c, w_kvb).reshape(T, hg, d_n + d_v)
        k = jnp.concatenate(
            [expanded[..., :d_n], jnp.broadcast_to(k_r, (T, hg, d_r))], -1)
        v = expanded[..., d_n:]
        if round_to is not None:
            q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))

        def queries(args):
            at, q_block = args                   # [block], [block, hg, D]
            scores = jnp.einsum("thd,shd->hts", q_block, k) * scale
            seen = keys_at[None, :] <= at[:, None]
            if drop_page is not None:
                first, size = drop_page
                dropped = (keys_at >= first) & (keys_at < first + size)
                seen = seen & ~(dropped[None, :]
                                & (at[:, None] >= first + size))
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hts,shd->thd", probs, v)

        out = _in_blocks(queries, (keys_at, q), QUERY_BLOCK)
        return acc + mm(out.reshape(T, hg * d_v), w_o), None

    y, _ = jax.lax.scan(heads, jnp.zeros((T, d), f32), (
        p["q"].reshape(d, H // hg, -1).transpose(1, 0, 2),
        p["kv_b"].reshape(d_c, H // hg, -1).transpose(1, 0, 2),
        p["o"].reshape(H // hg, hg * d_v, d)))
    return y


def ffn(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def experts(p, x, s, *, round_to, experts_per_token):
    """x [T, d] (normed) -> (y [T, d], margin [T]): every held expert
    applied to every token, weighted by its gate or by zero. ``margin`` is
    how far the last chosen expert lies above the best one not chosen, in
    the router's LOGITS ``x W_r``."""
    mm = functools.partial(_mm, round_to=round_to)
    k = experts_per_token or s["num_experts_per_tok"]
    first, held = s["held_experts_first"], s["num_experts"]
    logits = jnp.matmul(x, p["router"].astype(f32))
    scores = jax.nn.sigmoid(logits)
    order = jnp.argsort(-(scores + p["router_bias"].astype(f32)), axis=-1)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    rank = jnp.argsort(order, axis=-1)            # each expert's place
    gates = jnp.where(rank < k, scores, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) \
        * s["routed_scaling_factor"]

    def one(acc, expert):
        gate, up, down, g = expert
        return acc + g[:, None] * _in_blocks(
            lambda x: ffn(x, {"gate": gate, "up": up, "down": down}, mm), x,
            4 * TOKEN_BLOCK), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        gates[:, first:first + held].T))
    return y + ffn(x, p["shared"], mm), ranked[:, k - 1] - ranked[:, k]


class _Sizes(dict):
    """The configuration's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=(
    "s", "round_to", "experts_per_token", "no_rotation", "no_yarn",
    "swap_page", "drop_page"))
def _layer(p, h, *, s, round_to, experts_per_token, no_rotation, no_yarn,
           swap_page, drop_page):
    """One layer; ``p`` holds its parameters without the ``layer_<i>_`` of
    their names, so that layers of one kind share one program."""
    eps = s["rms_norm_eps"]
    h = h + latent_attention(
        p["attn"], _rms(h, p["attn_norm"], eps), s, round_to=round_to,
        no_rotation=no_rotation, no_yarn=no_yarn, swap_page=swap_page,
        drop_page=drop_page)
    x = _rms(h, p["mlp_norm"], eps)
    if "mlp" in p:
        mm = functools.partial(_mm, round_to=round_to)
        m = _in_blocks(lambda x: ffn(x, p["mlp"], mm), x, TOKEN_BLOCK)
        margin = jnp.full((h.shape[0],), jnp.inf)
    else:
        m, margin = experts(p["moe"], x, s, round_to=round_to,
                            experts_per_token=experts_per_token)
    return h + m, margin


@functools.partial(jax.jit, static_argnames=("eps", "round_to"))
def _head(norm, head, h, *, eps, round_to):
    return _mm(_rms(h, norm, eps), head, round_to)


def forward(params, tokens, config: Dict[str, Any], *, logits_from: int = 0,
            logits_to: Optional[int] = None,
            experts_per_token: Optional[int] = None, round_to=None,
            no_rotation: bool = False, no_yarn: bool = False,
            swap_page=None, drop_page=None):
    """``tokens [T]`` -> ``(logits [logits_to - logits_from, V], margin
    [T])``: the logits of positions ``logits_from .. logits_to - 1`` and,
    for every position, the smallest router margin over the expert layers
    (``experts``)."""
    s = _Sizes({k: v for k, v in config.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)})
    scaling = config["rope_scaling"]
    s.update(rope_factor=scaling["factor"],
             rope_original=scaling["original_max_position_embeddings"],
             rope_beta_fast=scaling["beta_fast"],
             rope_beta_slow=scaling["beta_slow"],
             rope_mscale_all_dim=scaling["mscale_all_dim"])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(f32)
        margin = jnp.full((tokens.shape[0],), jnp.inf)
        for i in range(config["num_hidden_layers"]):
            prefix = f"layer_{i}_"
            layer = {k[len(prefix):]: v for k, v in params.items()
                     if k.startswith(prefix)}
            h, m = _layer(
                layer, h, s=s, round_to=round_to,
                experts_per_token=experts_per_token, no_rotation=no_rotation,
                no_yarn=no_yarn, swap_page=swap_page, drop_page=drop_page)
            margin = jnp.minimum(margin, m)
        logits = _head(params["norm"], params["head"],
                       h[logits_from:logits_to],
                       eps=config["rms_norm_eps"], round_to=round_to)
    return logits, margin
