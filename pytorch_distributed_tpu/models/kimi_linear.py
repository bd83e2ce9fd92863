"""The ``kimi_linear`` decoder in flax.linen: a HYBRID stack. Three layers
in four keep a fixed-size recurrent state (Kimi Delta Attention, KDA), the
fourth attends latent rows (MLA), and all but the first layer's MLP are a
SHARE of the routed experts. ONE BLOCK, CONFIGURED (``ROADMAP.md`` R1): with
no KDA layer and ``rope_theta`` set it is ``sarvam_mla``'s block
(``sarvamai/sarvam-105b``: every layer MLA, rotated under YaRN).

The architecture of ``moonshotai/Kimi-Linear-48B-A3B-Instruct`` (where
its ``config.json`` is silent, the released ``fla`` layer), written out in
``chipbench/references/kimi_linear.py`` (and ``sarvam_mla.py``)::

    x <- x + mixer(RMSNorm(x));   x <- x + mlp(RMSNorm(x))
    KDA mixer (``kda_layers``, numbered from 1; H heads of d):
      q = unit(silu(conv(W_q x))) / sqrt(d),  k = unit(silu(conv(W_k x))),
      v = silu(conv(W_v x))                  conv: causal, depthwise, K taps
      log a = -exp(A_log[h]) softplus(f_b(f_a(x)) + dt_bias)   a channel
      beta = sigmoid(W_b x)                                      a head
      S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T,  o = S_t^T q
      y = W_o (RMSNorm_d(o) * sigmoid(g_b(g_a(x))))
    MLA mixer (``full_attn_layers``): q = W_q x (no query latent),
      [c | k_pe] = W_kva x, c <- RMSNorm(c), [k_nope | v] = W_kvb c a head,
      k = [k_nope | k_pe]; causal softmax at (d_n + d_r)^-1/2. NO rotation
      (``rope_theta`` None); or q's and k's ``d_r`` columns turned by the
      position under YaRN and the scale times ``m^2`` (``_turned``)

``mlp`` is a gated MLP in the first ``first_k_dense_replace`` layers and,
in the others, ``sum_i g_i FFN_i(x) + FFN_shared(x)`` over the experts
``route_sigmoid_topk`` chooses among ALL ``num_experts``; a model holds
``held_experts = (first, count)`` of them (``models.exaone_moe``).

The forward contract is ``models.xing4``'s; ``model.cache_class`` names
``serving.state_cache.HybridStateCache``, or ``LatentCache`` (which has a
paged twin) where no layer keeps a state. A FRESH prefill through a cache,
and a prompt's tail through a paged view (``_last_only``), return the
logits of the last real position only, ``[B, 1, V]``. Dtypes: weights and
compute ``param_dtype`` / ``dtype``; router, norms' statistics, angles,
decay, ``beta``, state, softmax, the sum over experts in float32."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.exaone_moe import (
    _EXPERT_CHUNK,
    _TOKEN_CHUNK,
    GatedMLPWeights,
    _by_chunks,
    _gated_mlp,
    computed_tokens,
    fresh_prompt_len,
)
from pytorch_distributed_tpu.models.xing4 import _rms, _Weights
from pytorch_distributed_tpu.ops import kda
from pytorch_distributed_tpu.ops import latent_attention as mla
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    held_share,
    route_sigmoid_topk,
    share_passes,
    share_rows,
)

__all__ = ["KimiLinearConfig", "KimiLinear"]

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The source's keys under their own names, but for ``n_layer``
    (``num_hidden_layers``), ``n_positions`` (``model_max_length``) and the
    keys of ``linear_attn_config`` (``kda_layers``, ``full_attn_layers``,
    ``kda_num_heads``, ``kda_head_dim``, ``short_conv_kernel_size``), and
    ``held_experts``, which is the deployment's and not the source's."""

    vocab_size: int = 163840
    n_positions: int = 1048576
    n_layer: int = 27
    hidden_size: int = 2304
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    #: layers numbered from 1, as ``linear_attn_config`` numbers them
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    held_experts: Tuple[int, int] = (0, 256)
    #: None: no rotation (``mla_use_nope``); else the rotary base, with
    #: YaRN's numbers (``ops.latent_attention.yarn_inv_freq``)
    rope_theta: Optional[float] = None
    rope_factor: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        layers = sorted(self.kda_layers + self.full_attn_layers)
        if layers != list(range(1, self.n_layer + 1)):
            raise ValueError(
                f"kda_layers and full_attn_layers name {layers}, not each "
                f"of the layers 1..{self.n_layer} once")
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"held_experts {self.held_experts} are not among "
                f"{self.num_experts}")

    @property
    def layer_recurrent(self) -> Tuple[bool, ...]:
        return tuple(i + 1 in self.kda_layers for i in range(self.n_layer))


def _log_uniform(lo: float, hi: float):
    """``log(uniform(lo, hi))``: ``A_log``'s initialiser in the source."""
    def init(key, shape, dtype=f32):
        return jnp.log(jax.random.uniform(key, shape, f32, lo, hi)
                       ).astype(dtype)
    return init


def _dt_bias(lo: float = 1e-3, hi: float = 1e-1, floor: float = 1e-4):
    """The source's ``dt_bias``: ``softplus^-1`` of a step drawn log-uniform
    in ``[lo, hi]`` (Mamba's initialiser, as ``fla`` has it)."""
    def init(key, shape, dtype=f32):
        dt = jnp.exp(jax.random.uniform(key, shape, f32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class DeltaAttention(_Weights):
    """The KDA mixer of one layer over ``x [B, T, d]`` (normed)."""

    @nn.compact
    def __call__(self, x, cache, layer, position_offset):
        cfg = self.cfg
        B, T, d = x.shape
        H, D, K = (cfg.kda_num_heads, cfg.kda_head_dim,
                   cfg.short_conv_kernel_size)
        proj = jnp.concatenate(
            [x @ self.w(name, (d, H * D)) for name in ("q", "k", "v")], -1)
        w_conv = jnp.concatenate(
            [self.w(f"{name}_conv", (K, H * D)) for name in ("q", "k", "v")],
            -1)
        # the decay a channel and the write strength a head, in float32
        a_log = self.param("A_log", _log_uniform(1.0, 16.0), (H,), f32)
        dt_bias = self.param("dt_bias", _dt_bias(), (H * D,), f32)
        f = jnp.dot(x @ self.w("f_a", (d, D)), self.w("f_b", (D, H * D)),
                    preferred_element_type=f32)
        log_a = (-jnp.exp(a_log)[:, None]
                 * jax.nn.softplus(f + dt_bias).reshape(B, T, H, D))
        beta = jax.nn.sigmoid(jnp.dot(x, self.w("b", (d, H)),
                                      preferred_element_type=f32))
        if cache is None:
            with jax.named_scope("pdt.kda.prefill"):
                o, _, _ = kda.kda_mix(
                    proj, w_conv, log_a, beta, jnp.zeros((B, H, D, D), f32),
                    jnp.zeros((B, K - 1, 3 * H * D), x.dtype), n_heads=H)
        else:
            o, cache = cache.attend(layer, proj, w_conv, log_a, beta,
                                    position_offset=position_offset)
        gate = jax.nn.sigmoid(jnp.dot(
            x @ self.w("g_a", (d, D)), self.w("g_b", (D, H * D)),
            preferred_element_type=f32)).reshape(B, T, H, D)
        y = (_rms(o, self.gain("o_norm", D), cfg.rms_norm_eps) * gate
             ).astype(x.dtype)
        return y.reshape(B, T, H * D) @ self.w("o", (H * D, d)), cache


class LatentAttention(_Weights):
    """The MLA mixer of one layer over ``x [B, T, d]`` (normed): no query
    latent; rotation and YaRN's scale where the configuration has them."""

    @nn.compact
    def __call__(self, x, cache, layer, position_offset):
        cfg = self.cfg
        B, T, d = x.shape
        H, d_n, d_r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim)
        d_c, d_v = cfg.kv_lora_rank, cfg.v_head_dim
        scale = _softmax_scale(cfg)
        q = (x @ self.w("q", (d, H * (d_n + d_r)))).reshape(
            B, T, H, d_n + d_r)
        kv = x @ self.w("kv_a", (d, d_c + d_r))
        latent = jnp.concatenate(
            [_rms(kv[..., :d_c], self.gain("kv_norm", d_c),
                  cfg.rms_norm_eps), kv[..., d_c:]], -1)
        kv_b = self.w("kv_b", (d_c, H * (d_n + d_v))).reshape(
            d_c, H, d_n + d_v)
        q, latent = _turned(cfg, q, latent, position_offset)
        if cache is None:
            y = _uncached(q, latent, kv_b, scale)
        else:
            y, cache = cache.attend(layer, q, latent, kv_b,
                                    position_offset=position_offset,
                                    scale=scale)
        return y.reshape(B, T, H * d_v) @ self.w("o", (H * d_v, d)), cache


class ExpertShare(_Weights):
    """``sum g_i FFN_i(x) + FFN_shared(x)`` over the experts this model
    holds, ``x [N, d]`` (normed). Returns ``(y, (hit, fill, spill))`` as
    ``models.exaone_moe.ExpertShare`` counts them, in chunks of tokens
    chosen as there."""

    @nn.compact
    def __call__(self, x, n_real=None):
        cfg = self.cfg
        d = x.shape[-1]
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        first, held = cfg.held_experts
        router = self.w("router", (d, E), f32)
        bias = self.param("router_bias", nn.initializers.zeros, (E,), f32)
        w_gate = self.w("experts_gate", (held, d, F))
        w_up = self.w("experts_up", (held, d, F))
        w_down = self.w("experts_down", (held, F, d))
        shared = GatedMLPWeights(cfg, width=F * cfg.num_shared_experts,
                                 name="shared")(d)
        k = cfg.num_experts_per_token
        chunk = (_EXPERT_CHUNK
                 if share_rows(_EXPERT_CHUNK * k, held, E) <= _TOKEN_CHUNK * k
                 else _TOKEN_CHUNK)

        def tokens(x):
            with jax.named_scope("moe/route"):
                experts, gates = held_share(*route_sigmoid_topk(
                    x, router, bias, k, cfg.routed_scaling_factor),
                    first, held)
                pairs, passes = share_passes(experts, held, E)
            with jax.named_scope("moe/experts"):
                y, hit = dropless_experts(x, experts, gates, w_gate, w_up,
                                          w_down, num_experts=E)
            with jax.named_scope("moe/shared"):
                y = y + _gated_mlp(x, *shared)
            fill = 100 * pairs // share_rows(experts.size, held, E)
            return y, hit, fill, jnp.maximum(passes - 1, 0)

        y, hit, fill, spill = _by_chunks(tokens, x, chunk=chunk,
                                         n_real=n_real)
        return y, (hit.max(), fill.max(), spill.sum())


class KimiLinear(nn.Module):
    """Decoder-only ``kimi_linear``. Input ``tokens [B, T]`` int32 ->
    logits (see the module docstring for the cache-aware forward)."""

    cfg: KimiLinearConfig

    @property
    def cache_class(self):
        from pytorch_distributed_tpu.serving import kv_cache, state_cache

        # no state to keep: the latent rows alone, which may lie in pages
        return (state_cache.HybridStateCache if self.cfg.kda_layers
                else kv_cache.LatentCache)

    #: as ``models.exaone_moe.ExaoneMoE.prefill_computed``
    prefill_computed = staticmethod(computed_tokens)

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, *, kv_cache=None,
                 position_offset=None):
        cfg = self.cfg
        B, T = tokens.shape
        if kv_cache is not None and kv_cache.n_layers != cfg.n_layer:
            raise ValueError(
                f"kv_cache has {kv_cache.n_layers} layers, model has "
                f"{cfg.n_layer}")
        init = nn.initializers.normal(cfg.initializer_range)
        d, eps = cfg.hidden_size, cfg.rms_norm_eps

        def gain(name):
            return self.param(name, nn.initializers.ones, (d,),
                              cfg.param_dtype)

        with jax.named_scope("embed"):
            embed = self.param("embed", init, (cfg.vocab_size, d),
                               cfg.param_dtype)
            h = embed[tokens].astype(cfg.dtype)
        hit = fill = spill = jnp.zeros((), jnp.int32)
        n_real = fresh_prompt_len(kv_cache, position_offset, B)
        for i in range(cfg.n_layer):
            x = _rms(h, gain(f"layer_{i}_attn_norm"), eps)
            if cfg.layer_recurrent[i]:
                with jax.named_scope("kda"):
                    y, kv_cache = DeltaAttention(
                        cfg, name=f"layer_{i}_attn")(
                            x, kv_cache, i, position_offset)
            else:
                with jax.named_scope("mla"):
                    y, kv_cache = LatentAttention(
                        cfg, name=f"layer_{i}_attn")(
                            x, kv_cache, i, position_offset)
            h = h + y
            x = _rms(h, gain(f"layer_{i}_mlp_norm"), eps).reshape(B * T, d)
            if i < cfg.first_k_dense_replace:
                mlp = GatedMLPWeights(cfg, width=cfg.intermediate_size,
                                      name=f"layer_{i}_mlp")(d)
                with jax.named_scope("mlp"):
                    y = _by_chunks(lambda x: _gated_mlp(x, *mlp), x,
                                   n_real=n_real)
            else:
                y, layer = ExpertShare(cfg, name=f"layer_{i}_moe")(
                    x, n_real)
                hit, fill, spill = (hit + layer[0],
                                    jnp.maximum(fill, layer[1]),
                                    spill + layer[2])
            h = h + y.reshape(B, T, d)
        with jax.named_scope("head"):
            if _last_only(kv_cache, position_offset):
                # a prompt: only the last real position is sampled from
                last = (kv_cache.lengths - 1) % T
                h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            h = _rms(h, gain("norm"), eps)
            logits = h @ self.param("head", init, (d, cfg.vocab_size),
                                    cfg.param_dtype).astype(cfg.dtype)
        if kv_cache is not None:
            return logits, kv_cache.counted(
                experts_hit=hit, experts_fill_pct=fill, experts_spill=spill)
        return logits


# -------------------------------------------------------------------------
# What a configuration may add to the MLA mixer (down here: the lines above
# are in the call stacks that the served programs' kernels record)
# -------------------------------------------------------------------------
def _softmax_scale(cfg) -> float:
    """``(d_n + d_r)^-1/2``, times YaRN's ``m^2`` where it rotates."""
    d_qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.rope_theta is None:
        return d_qk ** -0.5
    return mla.yarn_softmax_scale(d_qk, cfg.rope_factor,
                                  cfg.rope_mscale_all_dim)


def _turned(cfg, q, latent, position_offset):
    """``q [B, T, H, d_n + d_r]`` and ``latent [B, T, d_c + d_r]`` with
    their last ``d_r`` columns rotated by the tokens' positions
    (``position_offset [B]`` + 0..T-1; None: from 0), pairs ``(i, i + d_r /
    2)``; as they came where the configuration does not rotate."""
    if cfg.rope_theta is None:
        return q, latent
    B, T = q.shape[:2]
    d_r = cfg.qk_rope_head_dim
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    if position_offset is not None:
        positions = position_offset[:, None] + positions
    positions = jnp.broadcast_to(positions, (B, T))
    inv_freq = mla.yarn_inv_freq(
        d_r, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max_position_embeddings, cfg.rope_beta_fast,
        cfg.rope_beta_slow)

    def turn(x):
        return jnp.concatenate(
            [x[..., :-d_r], mla.rotate(x[..., -d_r:], positions, inv_freq)],
            axis=-1)

    return turn(q), turn(latent)


def _uncached(q, latent, kv_b, scale):
    """The forward without a cache: the tokens attend each other."""
    d_c = kv_b.shape[0]
    return mla.expanded_attention(
        q, latent, kv_b, d_c=d_c, d_n=q.shape[-1] - (latent.shape[-1] - d_c),
        scale=scale)


def _last_only(kv_cache, position_offset) -> bool:
    """Whether this forward is ONE prompt's (or the tail of one, through a
    paged cache's view: ``serving.paging.PagedLatentCache.prompt``), whose
    ``kv_cache.lengths`` counts the real new tokens: the head then runs
    over the last of them alone."""
    return kv_cache is not None and (
        position_offset is None or getattr(kv_cache, "prompt", False))
