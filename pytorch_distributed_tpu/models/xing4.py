"""Xing4.0 language model in flax.linen — latent attention, dropless
experts, a four-stream residual.

The architecture of ``XingChen-AGI/Xing4.0-29B-A4B`` (``config.json``):
DeepSeek-V3's block (multi-head latent attention with YaRN rotary
positions, ``first_k_dense_replace`` SwiGLU layers and then layers of
routed experts with a shared one, sigmoid scores, ``noaux_tc``) whose
residual is ``hc_mult`` streams mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880). The equations are written out in
``chipbench/references/xing4.py``, the plain float32 reference this forward
is held to; no sublayer is shared with ``models.gpt2``.

The forward contract is ``GPT2``'s: ``model.apply(variables, tokens,
deterministic=True, kv_cache=, position_offset=) -> (logits, cache)``, and
``logits`` alone without a cache; ``model.cfg``; ``model.cache_class`` names
the slotted cache the serving engine builds for it
(``serving.kv_cache.LatentCache``: the model touches it through
``cache.attend`` and ``cache.counted``). One departure, which the engine
knows (``serving.engine._slot_prefill``): a FRESH prefill through a cache
(``position_offset=None``) returns the logits of each sequence's last real
position only, ``[B, 1, V]`` at ``kv_cache.lengths - 1``: the untied
131,072-wide head over an 8,192-token bucket would be 7.7 TFLOP and a
2.1 GB temporary for one row that is read (compile result, PERF.md PR 33).

Not in the served model: the multi-token-prediction module
(``num_nextn_predict_layers``), whose output the main model's logits do not
depend on. Dtypes: weights and compute ``param_dtype`` / ``dtype``
(bfloat16 when served); router, hyper-connection coefficients, norms'
statistics, rotary angles and softmax in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops import latent_attention as mla
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    route_sigmoid_topk,
)

__all__ = ["Xing4Config", "Xing4"]


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """The source's keys under their own names, but for ``n_layer``
    (``num_hidden_layers``) and ``n_positions`` (the positions a cache may
    hold; ``max_position_embeddings``), which the serving engine reads."""

    vocab_size: int = 131072
    n_positions: int = 262144
    n_layer: int = 40
    hidden_size: int = 3584
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def softmax_scale(self) -> float:
        return mla.yarn_softmax_scale(
            self.qk_nope_head_dim + self.qk_rope_head_dim, self.rope_factor,
            self.rope_mscale_all_dim)

    @property
    def inv_freq(self):
        return mla.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position_embeddings, self.rope_beta_fast,
            self.rope_beta_slow)


def _rms(x, gain, eps):
    """RMS norm over the last axis, statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _sum4(m, axis):
    """Sum over a short axis as adds of its slices (keepdims): elementwise,
    so that twenty Sinkhorn rounds fuse into one program and not forty
    reductions."""
    parts = jnp.split(m, m.shape[axis], axis=axis)
    return sum(parts[1:], parts[0])


class _Weights(nn.Module):
    """``self.w(name, shape)``: a normal(initializer_range) matrix kept in
    ``param_dtype`` and used in the compute dtype (or kept and used in
    ``dtype``, where one is given); ``self.gain(name, n)``: ones."""
    cfg: Xing4Config

    def w(self, name, shape, dtype=None):
        init = nn.initializers.normal(self.cfg.initializer_range)
        if dtype is not None:
            return self.param(name, init, shape, dtype)
        return self.param(name, init, shape, self.cfg.param_dtype).astype(
            self.cfg.dtype)

    def gain(self, name, n):
        return self.param(name, nn.initializers.ones, (n,),
                          self.cfg.param_dtype)


class LatentAttention(_Weights):
    """F of the attention sublayer: its own input norm, then MLA."""

    @nn.compact
    def __call__(self, x, positions, cache, layer, position_offset):
        cfg = self.cfg
        B, T, d = x.shape
        H, d_n, d_r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim)
        d_c, d_v = cfg.kv_lora_rank, cfg.v_head_dim
        eps = cfg.rms_norm_eps
        x = _rms(x, self.gain("norm", d), eps)
        c_q = _rms(x @ self.w("q_a", (d, cfg.q_lora_rank)),
                   self.gain("q_norm", cfg.q_lora_rank), eps)
        q = (c_q @ self.w("q_b", (cfg.q_lora_rank, H * (d_n + d_r)))
             ).reshape(B, T, H, d_n + d_r)
        kv = x @ self.w("kv_a", (d, d_c + d_r))
        c_kv = _rms(kv[..., :d_c], self.gain("kv_norm", d_c), eps)
        inv_freq = cfg.inv_freq
        q = jnp.concatenate(
            [q[..., :d_n], mla.rotate(q[..., d_n:], positions, inv_freq)], -1)
        latent = jnp.concatenate(
            [c_kv, mla.rotate(kv[..., d_c:], positions, inv_freq)], -1)
        kv_b = self.w("kv_b", (d_c, H * (d_n + d_v))).reshape(
            d_c, H, d_n + d_v)
        if cache is None:
            y = mla.expanded_attention(q, latent, kv_b, d_c=d_c, d_n=d_n,
                                       scale=cfg.softmax_scale)
        else:
            y, cache = cache.attend(layer, q, latent, kv_b, position_offset,
                                    scale=cfg.softmax_scale)
        return y.reshape(B, T, H * d_v) @ self.w("o", (H * d_v, d)), cache


class GatedMLP(_Weights):
    """``(silu(x W_gate) * x W_up) W_down`` of width ``width``."""
    width: int = 0

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        return (jax.nn.silu(x @ self.w("gate", (d, self.width)))
                * (x @ self.w("up", (d, self.width)))
                ) @ self.w("down", (self.width, d))


class Experts(_Weights):
    """F of an expert layer's second sublayer: its own input norm, then
    ``sum g_i FFN_i(x) + FFN_shared(x)`` with no token dropped. Returns
    ``(y, hit)``: ``hit`` counts the experts that got a token."""

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, d = x.shape
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        x = _rms(x, self.gain("norm", d), cfg.rms_norm_eps)
        flat = x.reshape(B * T, d)
        with jax.named_scope("moe/route"):
            experts, gates = route_sigmoid_topk(
                flat, self.w("router", (d, E), jnp.float32),
                self.param("router_bias", nn.initializers.zeros, (E,),
                           jnp.float32),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor)
        with jax.named_scope("moe/experts"):
            y, hit = dropless_experts(
                flat, experts, gates, self.w("experts_gate", (E, d, F)),
                self.w("experts_up", (E, d, F)),
                self.w("experts_down", (E, F, d)))
        with jax.named_scope("moe/shared"):
            y = y.reshape(B, T, d) + GatedMLP(
                cfg, width=F * cfg.n_shared_experts, name="shared")(x)
        return y, hit


class DenseMLP(_Weights):
    @nn.compact
    def __call__(self, x):
        x = _rms(x, self.gain("norm", x.shape[-1]), self.cfg.rms_norm_eps)
        return GatedMLP(self.cfg, width=self.cfg.intermediate_size,
                        name="mlp")(x)


class HyperConnection(_Weights):
    """The maps of one sublayer's hyper-connection: from the streams ``X
    [B, T, n, d]`` the float32 coefficients ``H_pre [B, T, n]``, ``H_post
    [B, T, n]`` and the doubly stochastic ``H_res [B, T, n, n]``."""

    @nn.compact
    def __call__(self, X):
        cfg = self.cfg
        B, T, n, d = X.shape
        f32 = jnp.float32
        flat = _rms(X.reshape(B, T, n * d).astype(f32),
                    self.param("norm", nn.initializers.ones, (n * d,), f32),
                    cfg.rms_norm_eps)
        h = jnp.dot(flat, self.w("phi", (n * d, n * (n + 2)), f32),
                    precision=jax.lax.Precision.HIGHEST)
        a = self.param("a", nn.initializers.ones, (3,), f32)
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), f32)
        b_post = self.param("b_post", nn.initializers.zeros, (n,), f32)
        b_res = self.param(
            "b_res", lambda *_: 2.0 * jnp.eye(n, dtype=f32))
        h_pre = jax.nn.sigmoid(a[0] * h[..., :n] + b_pre)
        h_post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n:2 * n] + b_post)
        m = jnp.exp(jnp.clip(
            a[2] * h[..., 2 * n:].reshape(B, T, n, n) + b_res,
            cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (_sum4(m, -1) + cfg.hc_eps)      # rows
            m = m / (_sum4(m, -2) + cfg.hc_eps)      # columns
        return h_pre, h_post, m


def _connected(cfg, name, X, sublayer):
    """``X' = H_res X + H_post^T F(H_pre X)`` around ``sublayer`` (which
    holds its own input norm and may return extras beside its output)."""
    with jax.named_scope("hc"):
        h_pre, h_post, h_res = HyperConnection(cfg, name=name)(X)
        x = jnp.einsum("btn,btnd->btd", h_pre, X.astype(jnp.float32)
                       ).astype(X.dtype)
    y, *extra = sublayer(x)
    with jax.named_scope("hc"):
        X = (jnp.einsum("btmn,btnd->btmd", h_res, X.astype(jnp.float32))
             + h_post[..., None] * y.astype(jnp.float32)[:, :, None]
             ).astype(X.dtype)
    return (X, *extra)


class Xing4(nn.Module):
    """Decoder-only Xing4.0. Input ``tokens [B, T]`` int32 -> logits (see
    the module docstring for the cache-aware forward)."""

    cfg: Xing4Config

    @property
    def cache_class(self):
        from pytorch_distributed_tpu.serving.kv_cache import LatentCache

        return LatentCache

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, *, kv_cache=None,
                 position_offset=None):
        cfg = self.cfg
        B, T = tokens.shape
        if kv_cache is not None and kv_cache.n_layers != cfg.n_layer:
            raise ValueError(
                f"kv_cache has {kv_cache.n_layers} layers, model has "
                f"{cfg.n_layer}")
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        if position_offset is not None:
            positions = position_offset[:, None] + positions
        positions = jnp.broadcast_to(positions, (B, T))
        init = nn.initializers.normal(cfg.initializer_range)
        with jax.named_scope("embed"):
            embed = self.param("embed", init,
                               (cfg.vocab_size, cfg.hidden_size),
                               cfg.param_dtype)
            x = embed[tokens].astype(cfg.dtype)
        # the embedding copied into the streams
        X = jnp.broadcast_to(x[:, :, None],
                             (B, T, cfg.hc_mult, cfg.hidden_size))
        hit = jnp.zeros((), jnp.int32)
        for i in range(cfg.n_layer):
            def attention(x, i=i):
                with jax.named_scope("mla"):
                    return LatentAttention(cfg, name=f"layer_{i}_attn")(
                        x, positions, kv_cache, i, position_offset)

            X, kv_cache = _connected(cfg, f"layer_{i}_attn_hc", X, attention)
            if i < cfg.first_k_dense_replace:
                X, = _connected(
                    cfg, f"layer_{i}_mlp_hc", X,
                    lambda x, i=i: (DenseMLP(cfg, name=f"layer_{i}_mlp")(x),))
            else:
                X, layer_hit = _connected(
                    cfg, f"layer_{i}_mlp_hc", X,
                    lambda x, i=i: Experts(cfg, name=f"layer_{i}_moe")(x))
                hit = hit + layer_hit
        with jax.named_scope("head"):
            h = X.sum(axis=2)        # the streams summed before the last norm
            if kv_cache is not None and position_offset is None:
                # fresh prefill: only the last real position is sampled from
                last = (kv_cache.lengths - 1) % T
                h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            h = _rms(h, self.param("norm", nn.initializers.ones,
                                   (cfg.hidden_size,), cfg.param_dtype),
                     cfg.rms_norm_eps)
            logits = h @ self.param(
                "head", init, (cfg.hidden_size, cfg.vocab_size),
                cfg.param_dtype).astype(cfg.dtype)
        if kv_cache is not None:
            return logits, kv_cache.counted(experts_hit=hit)
        return logits
