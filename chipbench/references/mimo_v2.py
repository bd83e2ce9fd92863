"""Plain reference of the ``mimo_v2`` family (``XiaomiMiMo/MiMo-V2.5``, the
language model): the forward pass in ``jax.numpy``, float32,
``default_matmul_precision("highest")``. No cache, no ring, no kernel, no
sorting, no packing of rows: every layer attends from the keys and values of
the whole sequence under a mask, the sink is one more column of the softmax
that is dropped after it, and every held expert is applied to every token
under its gate or zero. It runs a layer at a time (``forward`` is a Python
loop over jitted layers, the queries of attention and the tokens of an MLP
in blocks), so that at the published widths and 24,576 positions it fits
beside the program's bfloat16 weights. The small helpers (a product in a
stated precision, the RMS norm, the blocks, a gated MLP, the head) are
``references/exaone_moe.py``'s; every equation below is written here.

``config`` is the configuration file's dict (the source's keys). Sizes: d
``hidden_size``, H_q ``num_attention_heads``, D ``head_dim`` (=
``swa_head_dim``), D_v ``v_head_dim`` (= ``swa_v_head_dim``), W
``sliding_window``, F ``intermediate_size``, F_e ``moe_intermediate_size``,
E ``router_width`` (the published ``n_routed_experts``), k
``num_experts_per_tok``, eps ``layernorm_epsilon``, R = ``int(
partial_rotary_factor * D)`` (64 of 192). ``RMSNorm(x) = x / sqrt(mean(x^2)
+ eps) * g``. A layer ``l`` is FULL where ``hybrid_layer_pattern[l] == 0``
(H_kv ``num_key_value_heads``, base ``rope_theta``, no sink) and a WINDOW
layer where it is 1 (H_kv ``swa_num_key_value_heads``, base
``swa_rope_theta``, a sink where ``add_swa_attention_sink_bias``). With
input ``h [T, d]``::

    x = RMSNorm(h)                                the norm BEFORE the sublayer
    q = x W_q as H_q heads of D;  k = x W_k as H_kv heads of D
    v = attention_value_scale * (x W_v) as H_kv heads of D_v
    q, k: the first R columns of each head turn at the token's position,
        pair i = columns (i, i + R/2) by position * base^(-2i/R); the
        other D - R columns pass                  (ASSUMED: half-split pairs,
                                                  the turned columns first)
    s[p, t] = q_p . k_t / sqrt(D), query head j on K/V head j // (H_q/H_kv)
    key t visible to query p when t <= p, and in a window layer also
        t > p - W                                 (W keys, p's own among them)
    a_p = softmax over the visible t AND, in a window layer, one more
        column b_j (a learned scalar a query head, the sink), of which the
        values' sum takes the keys' columns only  float32
    h <- h + a W_o
    h <- h + mlp(RMSNorm(h))

    mlp, moe_layer_freq[l] == 0:  (silu(x W_g) * x W_u) W_d, F wide
    mlp, == 1:
        s = sigmoid(x W_r)                        [E], float32
        chosen = the k largest of s + b           (b: ASSUMED 0; noaux_tc
                                                  with one group: no groups)
        g_i = s_i / (sum of the chosen s + 1e-20) (norm_topk_prob; scale 1:
                                                  routed_scaling_factor null)
        y = sum over the chosen HELD experts g_i FFN_i(x)   no shared expert

THE SHARE: the file's ``n_routed_experts`` experts from
``held_experts_first`` on are held; a chosen expert that is not held adds
nothing (it is another chip's part), and the gates are normalised over all
k chosen, held or not. After the last layer ``RMSNorm``, then the untied
head over the file's ``vocab_size`` rows.

Knobs exist for the readings a cell's limits are set from, and for nothing
else: ``round_to`` (both operands of every matrix product rounded to a
narrower dtype, by name), ``experts_per_token``, ``window`` (another W),
``no_sink``, ``rotate_all`` (all D columns turn), ``swap_bases`` (each kind
of layer turns by the other's base), ``value_scale`` (another scale on V)
and ``window_heads_as_full`` (a window layer's query head j reads K/V head
``j // (H_q / num_key_value_heads)``, the full layers' mapping).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.references.exaone_moe import (TOKEN_BLOCK, _head, _in_blocks,
                                             _mm, _rms, _Sizes, ffn)

#: queries attended at a time: 64 heads x 128 x 24,576 float32 scores are
#: 0.8 GB, and the sink's column, the softmax and its slice each copy them
QUERY_BLOCK = 128
f32 = jnp.float32


def _rotate(x, theta, rotary):
    """x [T, H, D] at positions 0..T-1: the first ``rotary`` columns turn,
    pair i = columns (i, i + rotary/2); the others pass."""
    T = x.shape[0]
    inv_freq = theta ** (-jnp.arange(0, rotary, 2, dtype=f32) / rotary)
    angle = jnp.arange(T, dtype=f32)[:, None, None] * inv_freq
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle),
                            x[..., rotary:]], -1)


def attention(p, x, s, *, sliding, window, theta, rotary, value_scale, sink,
              group, round_to):
    """x [T, d] (normed) -> [T, d]; p: the ``layer_i_attn`` parameters.
    ``group``: query heads a K/V head (head j reads K/V head j // group)."""
    T = x.shape[0]
    Hq, D, Dv = s["num_attention_heads"], s["head_dim"], s["v_head_dim"]
    mm = functools.partial(_mm, round_to=round_to)
    q = mm(x, p["q"]).reshape(T, Hq, D)
    k = mm(x, p["k"]).reshape(T, -1, D)
    v = value_scale * mm(x, p["v"]).reshape(T, -1, Dv)
    q, k = _rotate(q, theta, rotary), _rotate(k, theta, rotary)
    if round_to is not None:
        q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
    n = Hq // group               # K/V heads read: head j reads j // group
    q, k, v = q.reshape(T, n, group, D), k[:, :n], v[:, :n]
    keys_at = jnp.arange(T)

    def queries(args):
        at, q_block = args                     # [block], [block, n, group, D]
        scores = jnp.einsum("thgd,shd->hgts", q_block, k) * D ** -0.5
        seen = keys_at[None, :] <= at[:, None]
        if sliding:
            seen &= keys_at[None, :] > at[:, None] - window
        scores = jnp.where(seen, scores, -jnp.inf)
        if sink:                               # one more column, no value
            b = jnp.broadcast_to(
                p["sink"].astype(f32).reshape(n, group, 1, 1),
                scores.shape[:3] + (1,))
            scores = jnp.concatenate([scores, b], -1)
        probs = jax.nn.softmax(scores, -1)[..., :T]
        return jnp.einsum("hgts,shd->thgd", probs, v)

    out = _in_blocks(queries, (keys_at, q), QUERY_BLOCK)
    return mm(out.reshape(T, Hq * Dv), p["o"])


def experts(p, x, s, *, round_to, experts_per_token):
    """x [T, d] (normed) -> (y [T, d], margin [T]): every held expert
    applied to every token, weighted by its gate or by zero. ``margin`` is
    how far the last chosen expert lies above the best one not chosen, in
    the router's LOGITS ``x W_r`` (``references/exaone_moe.py::experts``)."""
    mm = functools.partial(_mm, round_to=round_to)
    k = experts_per_token or s["num_experts_per_tok"]
    first, held = s["held_experts_first"], s["n_routed_experts"]
    logits = jnp.matmul(x, p["router"].astype(f32))
    scores = jax.nn.sigmoid(logits)
    order = jnp.argsort(-(scores + p["router_bias"].astype(f32)), axis=-1)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    rank = jnp.argsort(order, axis=-1)            # each expert's place
    gates = jnp.where(rank < k, scores, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)

    def one(acc, expert):
        gate, up, down, g = expert
        return acc + g[:, None] * ffn(
            x, {"gate": gate, "up": up, "down": down}, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        gates[:, first:first + held].T))
    return y, ranked[:, k - 1] - ranked[:, k]


@functools.partial(jax.jit, static_argnames=(
    "s", "sliding", "window", "theta", "rotary", "value_scale", "sink",
    "group", "round_to", "experts_per_token"))
def _layer(p, h, *, s, round_to, experts_per_token, **kind):
    """One layer; ``p`` holds its parameters without the ``layer_<i>_`` of
    their names, so that layers of one kind share one program."""
    eps = s["layernorm_epsilon"]
    h = h + attention(p["attn"], _rms(h, p["attn_norm"], eps), s,
                      round_to=round_to, **kind)
    x = _rms(h, p["mlp_norm"], eps)
    if "mlp" in p:
        mm = functools.partial(_mm, round_to=round_to)
        m = _in_blocks(lambda x: ffn(x, p["mlp"], mm), x, TOKEN_BLOCK)
        margin = jnp.full((h.shape[0],), jnp.inf)
    else:
        m, margin = experts(p["moe"], x, s, round_to=round_to,
                            experts_per_token=experts_per_token)
    return h + m, margin


def forward(params, tokens, config: Dict[str, Any], *, logits_from: int = 0,
            logits_to: Optional[int] = None,
            experts_per_token: Optional[int] = None, round_to=None,
            window: Optional[int] = None, no_sink: bool = False,
            rotate_all: bool = False, swap_bases: bool = False,
            value_scale: Optional[float] = None,
            window_heads_as_full: bool = False):
    """``tokens [T]`` -> ``(logits [logits_to - logits_from, V], margin
    [T])``: the logits of positions ``logits_from .. logits_to - 1`` and,
    for every position, the smallest router margin over the expert layers
    (``experts``)."""
    s = _Sizes({k: v for k, v in config.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)})
    Hq, D = config["num_attention_heads"], config["head_dim"]
    bases = (float(config["rope_theta"]), float(config["swa_rope_theta"]))
    heads = (config["num_key_value_heads"], config["swa_num_key_value_heads"])
    sinks = (config["add_full_attention_sink_bias"],
             config["add_swa_attention_sink_bias"])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(f32)
        margin = jnp.full((tokens.shape[0],), jnp.inf)
        for i in range(config["num_hidden_layers"]):
            prefix = f"layer_{i}_"
            layer = {k[len(prefix):]: v for k, v in params.items()
                     if k.startswith(prefix)}
            sliding = int(config["hybrid_layer_pattern"][i])
            h, m = _layer(
                layer, h, s=s, sliding=bool(sliding),
                window=window or config["sliding_window"],
                theta=bases[sliding != swap_bases],
                rotary=D if rotate_all
                else int(config["partial_rotary_factor"] * D),
                value_scale=config["attention_value_scale"]
                if value_scale is None else value_scale,
                sink=bool(sinks[sliding]) and not no_sink,
                group=Hq // heads[sliding and not window_heads_as_full],
                round_to=round_to, experts_per_token=experts_per_token)
            margin = jnp.minimum(margin, m)
        logits = _head(params["norm"], params["head"],
                       h[logits_from:logits_to],
                       eps=config["layernorm_epsilon"], round_to=round_to)
    return logits, margin
