"""Plain reference of the ``xing4`` family (``Xing4.0-29B-A4B``): the forward
pass in ``jax.numpy``, float32, ``default_matmul_precision("highest")``. No
cache, no kernel, no absorption, no sorting: attention expands K and V for
every position and every expert is applied to every token under a mask. It
runs a layer at a time (``forward`` is a Python loop over jitted layers, the
queries of attention in blocks and the experts one after another), so that
at the published widths it fits beside the program's bfloat16 weights.

``config`` is the configuration file's dict (the source's keys). Sizes: d
``hidden_size``, H ``num_attention_heads``, d_c ``kv_lora_rank``, d_q
``q_lora_rank``, d_n ``qk_nope_head_dim``, d_r ``qk_rope_head_dim``, d_v
``v_head_dim``, F ``intermediate_size``, F_e ``moe_intermediate_size``, E
``n_routed_experts``, k ``num_experts_per_tok``, n ``hc_mult``, eps
``rms_norm_eps``. ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.

LATENT ATTENTION (DeepSeek-V2 section 2.1, as DeepSeek-V3's released code),
on ``x = RMSNorm(input)``, the sublayer's own norm::

    c_q = RMSNorm(x W_qa)                      [d_q]
    [q_n | q_r] = c_q W_qb         a head      [d_n | d_r]
    [c_kv | k_r] = x W_kva                     [d_c | d_r]
    c_kv = RMSNorm(c_kv)
    q_r, k_r rotated at the token's position (one k_r for all heads)
    [k_n | v] = c_kv W_kvb         a head      [d_n | d_v]
    scores = (q_n . k_n + q_r . k_r) s,  s = (d_n + d_r)^-1/2 m^2,
             m = 0.1 mscale_all_dim ln(factor) + 1
    P = causal softmax in float32;  y = concat_h(P v) W_o

YARN: pair i of the d_r rotary columns turns by ``position * inv_freq[i]``,
``inv_freq = f / factor * ramp + f * (1 - ramp)``, ``f = theta^(-2i/d_r)``,
``ramp = clip((i - low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``,
``high = ceil(c(beta_slow))``, ``c(t) = d_r ln(original / (2 pi t)) / (2 ln
theta)``; cos and sin unscaled (``mscale == mscale_all_dim``).
ASSUMED: pair i is columns ``(i, i + d_r / 2)``.

EXPERTS (DeepSeek-V3 section 2.1.2; ``noaux_tc``, ``n_group`` 1), on ``x =
RMSNorm(input)``::

    s = sigmoid(x W_g)  in float32             [E]
    chosen = the k largest of s + b            (b: ASSUMED zero at random weights)
    g_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
    y = sum_chosen g_i FFN_i(x) + FFN_shared(x)
    FFN(x) = (silu(x W_gate) * x W_up) W_down

No token is dropped. The ``first_k_dense_replace`` leading layers have one
FFN of width F in place of the experts.

HYPER-CONNECTIONS (mHC, arXiv:2512.24880) around each of a layer's two
sublayers F (F holds its own input norm), ``X`` in ``R^(n x d)``::

    x^ = RMSNorm(vec X)                        [n d], gain ASSUMED
    [h_pre | h_post | h_res] = x^ Phi          Phi in R^(n d x (n^2 + 2 n))
    H_pre  = sigmoid(a_pre h_pre + b_pre)      [n]
    H_post = 2 sigmoid(a_post h_post + b_post) [n]
    M_0 = exp(clip(a_res mat(h_res) + B_res, clamp_min, clamp_max))  [n, n]
    hc_sinkhorn_iters times: rows over (their sums + hc_eps), then columns
    H_res = M_iters
    X' = H_res X + H_post^T F(H_pre X)

ASSUMED: the embedding is copied into the n streams; the streams are summed
before the last norm; ``mat`` is row-major; rows before columns; ``a`` 1,
``b_pre`` and ``b_post`` 0, ``B_res`` 2 I at initialisation. The
multi-token-prediction module is not part of the main model's logits and is
left out (``reduced``).

Two knobs exist for the readings a cell's limits are set from, and for
nothing else: ``experts_per_token`` (route to fewer experts than the
configuration states) and ``round_to`` (round both operands of every
matrix product to a narrower dtype, e.g. ``float8_e4m3fn``).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
f32 = jnp.float32


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.matmul(a.astype(f32), b.astype(f32))


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(f32)


def inv_freq(config: Dict[str, Any]) -> np.ndarray:
    rope = config["rope_scaling"]
    d_r, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    original = rope["original_max_position_embeddings"]
    i = np.arange(d_r // 2, dtype=np.float64)
    f = theta ** (-2 * i / d_r)

    def c(turns):
        return d_r * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d_r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / rope["factor"] * ramp + f * (1 - ramp)


def softmax_scale(config: Dict[str, Any]) -> float:
    rope = config["rope_scaling"]
    m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rotate(x, positions, config):
    """x [T, ..., d_r] at positions [T]: pair i = columns (i, i + d_r/2)."""
    angle = positions.astype(f32)[:, None] * jnp.asarray(inv_freq(config), f32)
    angle = angle.reshape((angle.shape[0],) + (1,) * (x.ndim - 2)
                          + (angle.shape[1],))
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def attention(p, x, config, round_to=None):
    """x [T, d] -> [T, d]; p: the ``layer_i_attn`` parameters."""
    T = x.shape[0]
    H, d_n, d_r = (config["num_attention_heads"], config["qk_nope_head_dim"],
                   config["qk_rope_head_dim"])
    d_c, d_v, eps = (config["kv_lora_rank"], config["v_head_dim"],
                     config["rms_norm_eps"])
    mm = functools.partial(_mm, round_to=round_to)
    x = _rms(x, p["norm"], eps)
    positions = jnp.arange(T)
    c_q = _rms(mm(x, p["q_a"]), p["q_norm"], eps)
    q = mm(c_q, p["q_b"]).reshape(T, H, d_n + d_r)
    kv_a = mm(x, p["kv_a"])
    c_kv = _rms(kv_a[:, :d_c], p["kv_norm"], eps)
    k_r = _rotate(kv_a[:, d_c:], positions, config)              # [T, d_r]
    q_r = _rotate(q[..., d_n:], positions, config)               # [T, H, d_r]
    kv = mm(c_kv, p["kv_b"]).reshape(T, H, d_n + d_v)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r[:, None], (T, H, d_r))], -1)
    q = jnp.concatenate([q[..., :d_n], q_r], -1)
    v = kv[..., d_n:]
    if round_to is not None:
        q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
    out = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("thd,shd->hts", q[start:stop], k[:stop]) \
            * softmax_scale(config)
        seen = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", probs, v[:stop]))
    return mm(jnp.concatenate(out, 0).reshape(T, H * d_v), p["o"])


def ffn(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def dense_mlp(p, x, config, round_to=None):
    mm = functools.partial(_mm, round_to=round_to)
    x = _rms(x, p["norm"], config["rms_norm_eps"])
    return ffn(x, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], mm)


def experts(p, x, config, round_to=None, experts_per_token=None):
    """x [T, d] -> (y [T, d], margin [T]): every expert applied to every
    token, weighted by its gate or by zero. ``margin`` is how far the last
    chosen expert's score lies above the best one not chosen."""
    mm = functools.partial(_mm, round_to=round_to)
    k = experts_per_token or config["num_experts_per_tok"]
    x = _rms(x, p["norm"], config["rms_norm_eps"])
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(f32)))
    ranked = jnp.sort(s + p["router_bias"].astype(f32), axis=-1)[:, ::-1]
    chosen = (s + p["router_bias"].astype(f32)) >= ranked[:, k - 1:k]
    gates = jnp.where(chosen, s, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) \
        * config["routed_scaling_factor"]

    def one(acc, expert):
        gate, up, down, g = expert
        return acc + g[:, None] * ffn(x, gate, up, down, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"], gates.T))
    shared = p["shared"]
    y = y + ffn(x, shared["gate"], shared["up"], shared["down"], mm)
    return y, ranked[:, k - 1] - ranked[:, k]


def hyper_connected(p, X, sublayer, config):
    """X [T, n, d] -> X' (and whatever else ``sublayer`` returns)."""
    T, n, d = X.shape
    flat = _rms(X.reshape(T, n * d), p["norm"], config["rms_norm_eps"])
    h = jnp.matmul(flat, p["phi"].astype(f32))
    a = p["a"].astype(f32)
    h_pre = jax.nn.sigmoid(a[0] * h[:, :n] + p["b_pre"])
    h_post = 2 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + p["b_post"])
    m = jnp.exp(jnp.clip(a[2] * h[:, 2 * n:].reshape(T, n, n) + p["b_res"],
                         config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (m.sum(-1, keepdims=True) + config["hc_eps"])
        m = m / (m.sum(-2, keepdims=True) + config["hc_eps"])
    y, *extra = sublayer(jnp.einsum("tn,tnd->td", h_pre, X))
    X = jnp.einsum("tmn,tnd->tmd", m, X) + h_post[:, :, None] * y[:, None, :]
    return (X, *extra)


class _Sizes(dict):
    """The configuration's numbers as a static argument of ``jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnames=("config", "round_to",
                                             "experts_per_token"))
def _layer(p, X, *, config, round_to, experts_per_token):
    """One layer; ``p`` holds its parameters without the ``layer_<i>_``
    of their names, so that layers of one kind share one program."""
    X, = hyper_connected(p["attn_hc"], X, lambda x: (
        attention(p["attn"], x, config, round_to),), config)
    if "mlp" in p:
        X, = hyper_connected(p["mlp_hc"], X, lambda x: (
            dense_mlp(p["mlp"], x, config, round_to),), config)
        return X, jnp.full((X.shape[0],), jnp.inf)
    return hyper_connected(p["mlp_hc"], X, lambda x: experts(
        p["moe"], x, config, round_to, experts_per_token), config)


@functools.partial(jax.jit, static_argnames=("eps", "round_to"))
def _head(norm, head, X, *, eps, round_to):
    return _mm(_rms(X.sum(axis=1), norm, eps), head, round_to)


def forward(params, tokens, config: Dict[str, Any], *, logits_from: int = 0,
            experts_per_token: Optional[int] = None, round_to=None):
    """``tokens [T]`` -> ``(logits [T - logits_from, V], margin [T])``: the
    logits of positions ``logits_from ..`` and, for every position, the
    smallest router margin over the expert layers (``experts``)."""
    sizes = _Sizes({k: v for k, v in config.items() if k != "assumed"
                    and isinstance(v, (int, float, dict))})
    with jax.default_matmul_precision("highest"):
        X = jnp.repeat(params["embed"][tokens].astype(f32)[:, None],
                       config["hc_mult"], axis=1)
        margin = jnp.full((tokens.shape[0],), jnp.inf)
        for i in range(config["num_hidden_layers"]):
            prefix = f"layer_{i}_"
            layer = {k[len(prefix):]: v for k, v in params.items()
                     if k.startswith(prefix)}
            X, m = _layer(layer, X, config=sizes, round_to=round_to,
                          experts_per_token=experts_per_token)
            margin = jnp.minimum(margin, m)
        logits = _head(params["norm"], params["head"], X[logits_from:],
                       eps=config["rms_norm_eps"], round_to=round_to)
    return logits, margin
