"""Plain reference of the ``kimi_linear`` family (``Kimi-Linear-48B-A3B
-Instruct``): the forward pass in ``jax.numpy``, float32,
``default_matmul_precision("highest")``. No cache, no chunks, no kernel, no
sorting: a KDA layer is its recurrence, a token at a time in a
``lax.scan`` straight from the equation (so it shares nothing with the
chunked form of ``ops/kda.py``), an MLA layer a masked softmax over keys
and values expanded for the whole sequence, and every held expert is
applied to every token under its gate or zero. It runs a layer at a time
(``forward`` is a Python loop over jitted layers), so that at the published
widths and 6,144 positions it fits beside the program's bfloat16 weights.

``config`` is the configuration file's dict (the source's keys). Sizes: d
``hidden_size``; KDA: H ``linear_attn_config.num_heads`` heads of D
``linear_attn_config.head_dim``, K ``short_conv_kernel_size`` taps; MLA:
H_a ``num_attention_heads``, d_c ``kv_lora_rank``, d_n ``qk_nope_head_dim``,
d_r ``qk_rope_head_dim``, d_v ``v_head_dim``; F ``intermediate_size``, F_e
``moe_intermediate_size``, E ``router_width`` (the published
``num_experts``), k ``num_experts_per_token``, eps ``rms_norm_eps``.
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``; ``unit(x) = x / sqrt(sum(x^2)
+ 1e-6)``.

For layer ``l`` (numbered from 1 in ``linear_attn_config``) with input ``h
[T, d]``::

    x = RMSNorm(h);  h <- h + mixer(x);  h <- h + mlp(RMSNorm(h))

    l in kda_layers:
      conv(z)_t = sum_{i < K} w[i] z_{t - K + 1 + i}       z before 0 is 0
      q_t = unit(silu(conv(x W_q)_t)) / sqrt(D),  k_t = unit(silu(conv(x W_k)_t))
      v_t = silu(conv(x W_v)_t)                            H heads of D each
      a_t = exp(-exp(A_log[h]) softplus((x_t W_fa) W_fb + dt_bias))   [H, D]
      beta_t = sigmoid(x_t W_b)                                       [H]
      S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                                      S_0 = 0, a head
      mixer = (RMSNorm_D(o_t) * sigmoid((x_t W_ga) W_gb)) W_o
    l in full_attn_layers:
      q = x W_q as H_a heads of d_n + d_r;  [c | k_pe] = x W_kva
      c <- RMSNorm(c);  [k_n | v] = c W_kvb a head;  k = [k_n | k_pe]
      NO rotation;  causal softmax(q k^T (d_n + d_r)^-1/2) v;  W_o

    mlp, l <= first_k_dense_replace:  (silu(x W_g) * x W_u) W_d, F wide
    mlp, the others:
        s = sigmoid(x W_r)                        [E], float32
        chosen = the k largest of s + b           (b: ASSUMED 0)
        g_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
        y = sum over the chosen HELD experts g_i FFN_i(x) + FFN_shared(x)

THE SHARE: the file's ``num_experts`` experts from ``held_experts_first``
on are held; a chosen expert that is not held adds nothing (it is another
chip's part), and the gates are normalised over all k chosen, held or not.
After the last layer ``RMSNorm``, then the untied head over the file's
``vocab_size`` rows.

Knobs exist for the readings a cell's limits are set from, and for nothing
else: ``round_to`` (both operands of every matrix product rounded to a
narrower dtype, by name), ``experts_per_token``, ``no_decay`` (``a = 1``),
``beta_one`` (``beta = 1``), ``conv_taps`` (the newest that many taps
only), ``rotate_mla`` (``q``'s and ``k``'s last ``d_r`` columns rotated,
theta ``rope_theta``) and ``state_dtype`` (the state rounded to it after
every token's update).
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


def _unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _in_blocks(fn, xs, block):
    """``fn`` over the rows of the arrays ``xs``, ``block`` at a time
    (memory only: the result is that of ``fn(xs)``)."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if n <= block or n % block:
        return fn(xs)
    out = jax.lax.map(fn, jax.tree_util.tree_map(
        lambda a: a.reshape((n // block, block) + a.shape[1:]), xs))
    return out.reshape((n,) + out.shape[2:])


def _conv(z, w, taps):
    """``z [T, W]``, ``w [K, W]``: the causal depthwise convolution, zeros
    before the sequence; ``taps`` newest taps only (None: all K)."""
    K, T = w.shape[0], z.shape[0]
    padded = jnp.pad(z, ((K - 1, 0), (0, 0)))
    return sum(w[i].astype(f32) * padded[i:i + T]
               for i in range(K - (taps or K), K))


def _rotate(x, theta):
    """x [T, H, D] at positions 0..T-1: pair i = columns (i, i + D/2)."""
    T, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=f32) / D)
    angle = jnp.arange(T, dtype=f32)[:, None, None] * inv_freq
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def delta_attention(p, x, s, *, round_to, no_decay, beta_one, conv_taps,
                    state_dtype):
    """x [T, d] (normed) -> [T, d]; p: the ``layer_i_attn`` parameters."""
    T = x.shape[0]
    H, D = s["kda_num_heads"], s["kda_head_dim"]
    mm = functools.partial(_mm, round_to=round_to)

    def conved(name):
        return jax.nn.silu(_conv(mm(x, p[name]), p[f"{name}_conv"],
                                 conv_taps)).reshape(T, H, D)

    q, k, v = _unit(conved("q")) * D ** -0.5, _unit(conved("k")), conved("v")
    a = jnp.exp(-jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        mm(mm(x, p["f_a"]), p["f_b"]) + p["dt_bias"].astype(f32)
    ).reshape(T, H, D))
    beta = jax.nn.sigmoid(mm(x, p["b"]))                     # [T, H]
    if no_decay:
        a = jnp.ones_like(a)
    if beta_one:
        beta = jnp.ones_like(beta)
    eye = jnp.eye(D, dtype=f32)

    def token(S, at):                 # S [H, D, D]: keys by values, a head
        q_t, k_t, v_t, a_t, b_t = at
        kk = k_t[:, :, None] * k_t[:, None, :]               # k k^T, a head
        S = jnp.einsum("hij,hjv->hiv", eye - b_t[:, None, None] * kk,
                       a_t[:, :, None] * S) \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(f32)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), f32), (q, k, v, a, beta))
    gate = jax.nn.sigmoid(mm(mm(x, p["g_a"]), p["g_b"])).reshape(T, H, D)
    y = _rms(o, p["o_norm"], s["rms_norm_eps"]) * gate
    return mm(y.reshape(T, H * D), p["o"])


def latent_attention(p, x, s, *, round_to, rotate_mla):
    """x [T, d] (normed) -> [T, d]: keys and values expanded for every
    position, a masked softmax, queries in blocks."""
    T = x.shape[0]
    H, d_n, d_r = (s["num_attention_heads"], s["qk_nope_head_dim"],
                   s["qk_rope_head_dim"])
    d_c, d_v = s["kv_lora_rank"], s["v_head_dim"]
    mm = functools.partial(_mm, round_to=round_to)
    q = mm(x, p["q"]).reshape(T, H, d_n + d_r)
    kv = mm(x, p["kv_a"])
    c = _rms(kv[:, :d_c], p["kv_norm"], s["rms_norm_eps"])
    k_pe = kv[:, None, d_c:]                                 # [T, 1, d_r]
    if rotate_mla:
        q = jnp.concatenate(
            [q[..., :d_n], _rotate(q[..., d_n:], s["rope_theta"])], -1)
        k_pe = _rotate(k_pe, s["rope_theta"])
    expanded = mm(c, p["kv_b"]).reshape(T, H, d_n + d_v)
    k = jnp.concatenate(
        [expanded[..., :d_n], jnp.broadcast_to(k_pe, (T, H, d_r))], -1)
    v = expanded[..., d_n:]
    if round_to is not None:
        q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
    keys_at = jnp.arange(T)

    def queries(args):
        at, q_block = args                       # [block], [block, H, D]
        scores = jnp.einsum("thd,shd->hts", q_block, k) \
            * (d_n + d_r) ** -0.5
        seen = keys_at[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, v)

    out = _in_blocks(queries, (keys_at, q), QUERY_BLOCK)
    return mm(out.reshape(T, H * d_v), p["o"])


def ffn(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def experts(p, x, s, *, round_to, experts_per_token):
    """x [T, d] (normed) -> (y [T, d], margin [T]): every held expert
    applied to every token, weighted by its gate or by zero. ``margin`` is
    how far the last chosen expert lies above the best one not chosen, in
    the router's LOGITS ``x W_r``."""
    mm = functools.partial(_mm, round_to=round_to)
    k = experts_per_token or s["num_experts_per_token"]
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
    "s", "recurrent", "round_to", "experts_per_token", "no_decay",
    "beta_one", "conv_taps", "rotate_mla", "state_dtype"))
def _layer(p, h, *, s, recurrent, round_to, experts_per_token, no_decay,
           beta_one, conv_taps, rotate_mla, state_dtype):
    """One layer; ``p`` holds its parameters without the ``layer_<i>_`` of
    their names, so that layers of one kind share one program."""
    eps = s["rms_norm_eps"]
    x = _rms(h, p["attn_norm"], eps)
    if recurrent:
        h = h + delta_attention(
            p["attn"], x, s, round_to=round_to, no_decay=no_decay,
            beta_one=beta_one, conv_taps=conv_taps, state_dtype=state_dtype)
    else:
        h = h + latent_attention(p["attn"], x, s, round_to=round_to,
                                 rotate_mla=rotate_mla)
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
            no_decay: bool = False, beta_one: bool = False,
            conv_taps: Optional[int] = None, rotate_mla: bool = False,
            state_dtype=None):
    """``tokens [T]`` -> ``(logits [logits_to - logits_from, V], margin
    [T])``: the logits of positions ``logits_from .. logits_to - 1`` and,
    for every position, the smallest router margin over the expert layers
    (``experts``)."""
    s = _Sizes({k: v for k, v in config.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)})
    linear = config["linear_attn_config"]
    s["kda_num_heads"], s["kda_head_dim"] = (linear["num_heads"],
                                             linear["head_dim"])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(f32)
        margin = jnp.full((tokens.shape[0],), jnp.inf)
        for i in range(config["num_hidden_layers"]):
            prefix = f"layer_{i}_"
            layer = {k[len(prefix):]: v for k, v in params.items()
                     if k.startswith(prefix)}
            h, m = _layer(
                layer, h, s=s, recurrent=i + 1 in linear["kda_layers"],
                round_to=round_to, experts_per_token=experts_per_token,
                no_decay=no_decay, beta_one=beta_one, conv_taps=conv_taps,
                rotate_mla=rotate_mla, state_dtype=state_dtype)
            margin = jnp.minimum(margin, m)
        logits = _head(params["norm"], params["head"],
                       h[logits_from:logits_to],
                       eps=config["rms_norm_eps"], round_to=round_to)
    return logits, margin
