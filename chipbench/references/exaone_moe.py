"""Plain reference of the ``exaone_moe`` family (``K-EXAONE-236B-A23B``): the
forward pass in ``jax.numpy``, float32, ``default_matmul_precision
("highest")``. No cache, no ring, no kernel, no sorting: every layer
attends from the keys and values of the whole sequence under a mask, and
every held expert is applied to every token under its gate or zero. It runs
a layer at a time (``forward`` is a Python loop over jitted layers, the
queries of attention and the tokens of an MLP in blocks), so that at the
published widths and 29,184 positions it fits beside the program's
bfloat16 weights.

``config`` is the configuration file's dict (the source's keys). Sizes: d
``hidden_size``, H_q ``num_attention_heads``, H_kv ``num_key_value_heads``,
D ``head_dim``, W ``sliding_window``, F ``intermediate_size``, F_e
``moe_intermediate_size``, E ``router_width`` (the published
``num_experts``), k ``num_experts_per_tok``, eps ``rms_norm_eps``.
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.

For layer ``l`` with input ``h [T, d]``::

    q = h W_q as H_q heads of D;  k = h W_k,  v = h W_v as H_kv heads of D
    q <- RMSNorm_D(q),  k <- RMSNorm_D(k)         one gain each, all heads
    if layer_types[l] == "sliding_attention":
        q, k rotated at the token's position: pair i = columns (i, i + D/2)
        turns by position * theta^(-2i/D)         (rope_type default)
    scores = q k^T / sqrt(D), query head j against K/V head j // (H_q/H_kv)
    key s visible to query p when s <= p, and in a sliding layer also
        s > p - W                                 (W keys, p's own among them)
    a = softmax(scores) v                         float32
    h <- h + RMSNorm(a W_o)                       the norm on the OUTPUT
    h <- h + RMSNorm(mlp(h))

    mlp, mlp_layer_types[l] == "dense":  (silu(x W_g) * x W_u) W_d, F wide
    mlp, "sparse":
        s = sigmoid(x W_r)                        [E], float32
        chosen = the k largest of s + b           (b: ASSUMED 0)
        g_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
        y = sum over the chosen HELD experts g_i FFN_i(x) + FFN_shared(x)

A full layer gets no positional encoding. THE SHARE: the file's
``num_experts`` experts from ``held_experts_first`` on are held; a chosen
expert that is not held adds nothing (it is another chip's part), and the
gates are normalised over all k chosen, held or not. After the last layer
``RMSNorm``, then the untied head over the file's ``vocab_size`` rows.

Knobs exist for the readings a cell's limits are set from, and for nothing
else: ``round_to`` (both operands of every matrix product rounded to a
narrower dtype, by name: ``"float8_e4m3fn"``), ``experts_per_token``,
``window`` (another W), ``window_layers_full`` (a sliding layer attends as a full one, its
rotation kept) and ``rotate_full`` (a full layer's q and k rotated too).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
TOKEN_BLOCK = 1024
f32 = jnp.float32


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.matmul(a.astype(f32), b.astype(f32))


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(f32)


def _rotate(x, theta):
    """x [T, H, D] at positions 0..T-1: pair i = columns (i, i + D/2)."""
    T, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=f32) / D)
    angle = jnp.arange(T, dtype=f32)[:, None, None] * inv_freq
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _in_blocks(fn, xs, block):
    """``fn`` over the rows of the arrays ``xs``, ``block`` at a time
    (memory only: the result is that of ``fn(xs)``)."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if n <= block or n % block:
        return fn(xs)
    out = jax.lax.map(fn, jax.tree_util.tree_map(
        lambda a: a.reshape((n // block, block) + a.shape[1:]), xs))
    return out.reshape((n,) + out.shape[2:])


def attention(p, x, s, *, sliding, window, rotated, round_to):
    """x [T, d] -> [T, d]; p: the ``layer_i_attn`` parameters."""
    T = x.shape[0]
    Hq, Hkv, D = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    mm = functools.partial(_mm, round_to=round_to)
    q = _rms(mm(x, p["q"]).reshape(T, Hq, D), p["q_norm"], s["rms_norm_eps"])
    k = _rms(mm(x, p["k"]).reshape(T, Hkv, D), p["k_norm"], s["rms_norm_eps"])
    v = mm(x, p["v"]).reshape(T, Hkv, D)
    if rotated:
        q, k = _rotate(q, s["rope_theta"]), _rotate(k, s["rope_theta"])
    if round_to is not None:
        q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
    q = q.reshape(T, Hkv, Hq // Hkv, D)
    keys_at = jnp.arange(T)

    def queries(args):
        at, q_block = args                     # [block], [block, Hkv, G, D]
        scores = jnp.einsum("thgd,shd->hgts", q_block, k) * D ** -0.5
        seen = keys_at[None, :] <= at[:, None]
        if sliding:
            seen &= keys_at[None, :] > at[:, None] - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hgts,shd->thgd", probs, v)

    out = _in_blocks(queries, (keys_at, q), QUERY_BLOCK)
    return mm(out.reshape(T, Hq * D), p["o"])


def ffn(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def experts(p, x, s, *, round_to, experts_per_token):
    """x [T, d] -> (y [T, d], margin [T]): every held expert applied to
    every token, weighted by its gate or by zero. ``margin`` is how far the
    last chosen expert lies above the best one not chosen, in the router's
    LOGITS ``x W_r``: the inputs of an expert layer are sums of normed
    sublayer outputs, not normed themselves, so with random weights the
    chosen experts' sigmoid scores lie within 0.01 of one and each other,
    and a difference of scores says nothing about how near a tie is."""
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
        return acc + g[:, None] * ffn(
            x, {"gate": gate, "up": up, "down": down}, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        gates[:, first:first + held].T))
    return y + ffn(x, p["shared"], mm), ranked[:, k - 1] - ranked[:, k]


class _Sizes(dict):
    """The configuration's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=(
    "s", "sliding", "window", "rotated", "round_to", "experts_per_token"))
def _layer(p, h, *, s, sliding, window, rotated, round_to,
           experts_per_token):
    """One layer; ``p`` holds its parameters without the ``layer_<i>_`` of
    their names, so that layers of one kind share one program."""
    eps = s["rms_norm_eps"]
    a = attention(p["attn"], h, s, sliding=sliding, window=window,
                  rotated=rotated, round_to=round_to)
    h = h + _rms(a, p["attn_norm"], eps)
    if "mlp" in p:
        mm = functools.partial(_mm, round_to=round_to)
        m = _in_blocks(lambda x: ffn(x, p["mlp"], mm), h, TOKEN_BLOCK)
        margin = jnp.full((h.shape[0],), jnp.inf)
    else:
        m, margin = experts(p["moe"], h, s, round_to=round_to,
                            experts_per_token=experts_per_token)
    return h + _rms(m, p["mlp_norm"], eps), margin


@functools.partial(jax.jit, static_argnames=("eps", "round_to"))
def _head(norm, head, h, *, eps, round_to):
    return _mm(_rms(h, norm, eps), head, round_to)


def forward(params, tokens, config: Dict[str, Any], *, logits_from: int = 0,
            logits_to: Optional[int] = None,
            experts_per_token: Optional[int] = None, round_to=None,
            window: Optional[int] = None, window_layers_full: bool = False,
            rotate_full: bool = False):
    """``tokens [T]`` -> ``(logits [logits_to - logits_from, V], margin
    [T])``: the logits of positions ``logits_from .. logits_to - 1`` and,
    for every position, the smallest router margin over the expert layers
    (``experts``)."""
    s = _Sizes({k: v for k, v in config.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)})
    s["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(f32)
        margin = jnp.full((tokens.shape[0],), jnp.inf)
        for i in range(config["num_hidden_layers"]):
            prefix = f"layer_{i}_"
            layer = {k[len(prefix):]: v for k, v in params.items()
                     if k.startswith(prefix)}
            sliding = config["layer_types"][i] == "sliding_attention"
            h, m = _layer(
                layer, h, s=s, sliding=sliding and not window_layers_full,
                window=window or config["sliding_window"],
                rotated=sliding or rotate_full, round_to=round_to,
                experts_per_token=experts_per_token)
            margin = jnp.minimum(margin, m)
        logits = _head(params["norm"], params["head"],
                       h[logits_from:logits_to],
                       eps=config["rms_norm_eps"], round_to=round_to)
    return logits, margin
