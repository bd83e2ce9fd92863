"""The ``exaone_moe`` decoder in flax.linen: window and full attention
layers mixed, fewer K/V heads than query heads, norms on the sublayers'
outputs, and a SHARE of the routed experts.

The architecture of ``LGAI-EXAONE/K-EXAONE-236B-A23B`` (``config.json``,
``model_type`` ``exaone_moe``; where it is silent, the family's released
``exaone4`` code). The equations are written out in
``chipbench/references/exaone_moe.py``, the plain float32 reference this
forward is held to. For layer ``l`` with input ``h``::

    q, k, v = h W_q, h W_k, h W_v      H_q heads, H_kv heads, H_kv heads of D
    q, k <- RMSNorm_D(q), RMSNorm_D(k)                 a learned gain each
    window layers only: q, k rotated (all D columns, pairs (i, i + D/2))
    a = causal softmax(q k^T / sqrt(D)) v              head j on K/V head j // G;
                                                       a window layer sees its
                                                       last ``sliding_window``
    h <- h + RMSNorm(a W_o)
    h <- h + RMSNorm(mlp(h))                           no norm BEFORE a sublayer

``mlp`` is a gated MLP in the ``dense`` layers and, in the others, ``sum_i
g_i FFN_i(x) + FFN_shared(x)`` over the ``num_experts_per_tok`` experts that
``ops.dropless_experts.route_sigmoid_topk`` chooses among ALL
``num_experts``. A model holds ``held_experts = (first, count)`` of them
(all, by default): the pairs whose expert it holds go through
``ops.dropless_experts.dropless_experts`` with no token dropped, the others
add nothing here and are neither gathered nor multiplied (``held_share``):
they are another chip's part of an expert-parallel deployment, whose
exchange is not in this file.

The forward contract is ``models.xing4``'s: ``model.apply(variables, tokens,
deterministic=True, kv_cache=, position_offset=) -> (logits, cache)``, and
``logits`` alone without a cache; ``model.cfg``; ``model.cache_class`` names
``serving.window_cache.WindowedKVCache`` (touched through ``cache.attend``
and ``cache.counted``); a FRESH prefill through a cache returns the logits
of each sequence's last real position only, ``[B, 1, V]``. Whatever of a
layer is tokenwise (projections, norms, rotation, the MLPs; all but the
attention itself) runs ``_TOKEN_CHUNK`` tokens at a time: at 32,768 tokens
the dense layer's 18,432-wide intermediates would be 3.6 GB and a norm's
float32 copy of the queries 1 GB. An expert sublayer's arrays are the rows
of the pairs it HOLDS (``ops.dropless_experts.share_rows``: twice the
expected share, a quarter of the tokens' eight pairs for 16 of 128), so it
takes chunks of its own, up to ``_EXPERT_CHUNK`` tokens (``ExpertShare``).
A fresh prefill of ONE padded prompt (the engine's) runs those loops only
as far as the chunk that holds the prompt's last real token
(``fresh_prompt_len``, ``computed_tokens``: the bound is a value the
one-slot cache carries, so a bucket is still one program): the positions
after it are ZEROS in q, k, v and in every sublayer's output, finite
wherever a dense read of the cache multiplies them by a masked zero, and no
real token's arithmetic changes, since it attends earlier positions only
and its own chunk is computed whole.

ONE BLOCK, CONFIGURED (``ROADMAP.md`` R1). The defaults of
``ExaoneMoEConfig`` are the equations above; its last group of fields
states where another family's block differs, and ``mimo_v2``
(``XiaomiMiMo/MiMo-V2.5``; ``chipbench/references/mimo_v2.py`` writes its
equations out) sets every one of them: ``norm_first`` (``h + f(RMSNorm(h))``
in both sublayers, where the default is ``h + RMSNorm(f(h))``), ``qk_norm``
off, ``v_head_dim`` (V heads narrower than K heads; ``o`` is ``H_q * D_v``
deep), ``window_key_value_heads`` (a window layer's own H_kv),
``rotary_dim`` (the first R columns of a head turn, the rest pass),
``full_rope_theta`` (a full layer turns too, by its own base),
``value_scale`` (on V before it is stored), ``window_sink`` (a learned
scalar a query head in the window layers' softmax: ``ops.gqa_attention``),
``num_shared_experts`` 0. A static branch each: a configuration that sets
none of them traces the program it traced before they were there.

Not in the served model: the multi-token-prediction layer
(``num_nextn_predict_layers``), which the main model's logits do not depend
on. Dtypes: weights and compute ``param_dtype`` / ``dtype`` (bfloat16 when
served); router, norms' statistics, rotary angles, scores and softmax, and
the sum over a token's experts in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.xing4 import _rms, _Weights
from pytorch_distributed_tpu.ops import gqa_attention
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    held_share,
    route_sigmoid_topk,
    share_passes,
    share_rows,
)
from pytorch_distributed_tpu.ops.latent_attention import rotate

__all__ = ["ExaoneMoEConfig", "ExaoneMoE"]

#: tokens of a prompt that go through a tokenwise sublayer at a time
_TOKEN_CHUNK = 2048
#: the most that go through an expert sublayer at a time (``ExpertShare``)
_EXPERT_CHUNK = 4096


@dataclasses.dataclass(frozen=True)
class ExaoneMoEConfig:
    """The source's keys under their own names, but for ``n_layer``
    (``num_hidden_layers``) and ``n_positions`` (``max_position_embeddings``),
    which the serving engine reads, and ``held_experts`` (module docstring),
    which is the deployment's and not the source's."""

    vocab_size: int = 153600
    n_positions: int = 262144
    n_layer: int = 48
    hidden_size: int = 6144
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    sliding_window: int = 128
    #: ``"sliding_attention"`` or ``"full_attention"`` a layer
    layer_types: Tuple[str, ...] = ()
    #: ``"dense"`` or ``"sparse"`` a layer
    mlp_layer_types: Tuple[str, ...] = ()
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    held_experts: Tuple[int, int] = (0, 128)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # -- where another family's block differs (module docstring) ----------
    norm_first: bool = False
    qk_norm: bool = True
    #: None: ``head_dim``
    v_head_dim: Optional[int] = None
    #: None: ``num_key_value_heads``
    window_key_value_heads: Optional[int] = None
    #: None: all ``head_dim`` columns
    rotary_dim: Optional[int] = None
    #: None: a full layer has no positions; ``rope_theta`` is the window's
    full_rope_theta: Optional[float] = None
    value_scale: float = 1.0
    window_sink: bool = False

    def __post_init__(self):
        if not (len(self.layer_types) == len(self.mlp_layer_types)
                == self.n_layer):
            raise ValueError(
                f"layer_types and mlp_layer_types name {self.n_layer} "
                f"layers each")
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"held_experts {self.held_experts} are not among "
                f"{self.num_experts}")

    @property
    def layer_windowed(self) -> Tuple[bool, ...]:
        return tuple(t == "sliding_attention" for t in self.layer_types)


def computed_tokens(n, n_real=None, chunk=None):
    """The positions, of ``n`` in a row of which the first ``n_real`` hold
    real tokens, that a loop over chunks of ``chunk`` (``_TOKEN_CHUNK``)
    runs: the chunks up to the one with the last real token where it loops
    (more than one chunk, whole chunks), all ``n`` where it does not or
    ``n_real`` is None. ``n_real`` a traced scalar or a host's integer: the
    loop's bound (``_by_chunks``) and the count the engine's
    ``engine.prefill`` span carries (``ExaoneMoE.prefill_computed``) are
    this one expression."""
    chunk = chunk or _TOKEN_CHUNK
    if n_real is None or n <= chunk or n % chunk:
        return n
    return -(-n_real // chunk) * chunk


def fresh_prompt_len(kv_cache, position_offset, B):
    """The real length of the ONE prompt a fresh prefill runs (``kv_cache``
    a one-slot cache carrying it, no ``position_offset``: ``serving.engine.
    _slot_prefill``), a traced int32 scalar; None for every other forward
    (no cache, a decode or verify step, several sequences), whose loops
    over chunks run to their static end."""
    if kv_cache is None or position_offset is not None or B != 1:
        return None
    return kv_cache.lengths[0]


def _by_chunks(fn, *xs, chunk=None, n_real=None):
    """``fn(*xs)`` over the tokens (leading axis) of the arrays ``xs``,
    ``chunk`` (``_TOKEN_CHUNK``) at a time where there are more (one
    program, run in a loop): what ``fn`` gives per token comes back whole,
    what it gives per call (a scalar) as a vector over the calls. Of rows
    whose first ``n_real`` alone hold real tokens (``fresh_prompt_len``)
    the loop ends with the last real token's chunk
    (``computed_tokens``): the chunks after it are not run and read zero,
    per token and per call."""
    chunk = chunk or _TOKEN_CHUNK
    n = xs[0].shape[0]
    if n <= chunk or n % chunk:
        return jax.tree_util.tree_map(
            lambda a: a if a.ndim else a[None], fn(*xs))
    out = gqa_attention.map_upto(
        lambda part: fn(*part),
        tuple(x.reshape((n // chunk, chunk) + x.shape[1:]) for x in xs),
        computed_tokens(n, n_real, chunk) // chunk)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n,) + a.shape[2:]) if a.ndim > 1 else a, out)


class GatedMLPWeights(_Weights):
    """The three matrices ``(gate, up, down)`` of a gated MLP ``width``
    wide, for ``_gated_mlp``: weights apart from the arithmetic, which runs
    inside ``_by_chunks``'s loop where no module may be born."""
    width: int = 0

    @nn.compact
    def __call__(self, d):
        return (self.w("gate", (d, self.width)), self.w("up", (d, self.width)),
                self.w("down", (self.width, d)))


def _gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _sink_init(key, shape, dtype):
    """normal(4, 1), not ``initializer_range``: a sink is there to take a
    visible share of a softmax. Among a full window's 128 scores of random
    weights (deviation about 1.6, so their exponentials sum to about 490) a
    sink of 4 holds a tenth and one of 5 a quarter; drawn normal(0, 1) it
    holds 0.2-0.6%, and a forward WITHOUT the sink then lies nearer the
    float32 reference than the bfloat16 forward with it does (PERF.md,
    PR 49)."""
    return 4.0 + jax.random.normal(key, shape, dtype)


class Attention(_Weights):
    """``h + RMSNorm(a W_o)`` of one layer (``norm_first``: ``h + a W_o``
    of ``RMSNorm(h)``; ``gain`` is that one norm's), ``h [N, d]`` the ``B x
    T`` tokens in a row. Everything but the attention itself is tokenwise
    and runs in chunks: at 32,768 tokens the queries' float32 copies under
    the norm and the rotation alone would be 2 GB."""
    windowed: bool = False

    @nn.compact
    def __call__(self, h, positions, cache, layer, position_offset, gain,
                 n_real=None):
        cfg = self.cfg
        B, T = positions.shape
        d = h.shape[-1]
        Hq, D = cfg.num_attention_heads, cfg.head_dim
        Hkv = (self.windowed and cfg.window_key_value_heads
               or cfg.num_key_value_heads)
        Dv, R = cfg.v_head_dim or D, cfg.rotary_dim or D
        eps = cfg.rms_norm_eps
        w_q, w_k, w_v = (self.w("q", (d, Hq * D)), self.w("k", (d, Hkv * D)),
                         self.w("v", (d, Hkv * Dv)))
        q_gain, k_gain = ((self.gain("q_norm", D), self.gain("k_norm", D))
                          if cfg.qk_norm else (None, None))
        w_o = self.w("o", (Hq * Dv, d))
        # a full layer has no positions unless it has a base of its own
        theta = cfg.rope_theta if self.windowed else cfg.full_rope_theta
        inv_freq = theta and gqa_attention.rope_inv_freq(R, theta)
        kwargs = {}
        if self.windowed and cfg.window_sink:
            kwargs["sink"] = self.param("sink", _sink_init, (Hq,),
                                        jnp.float32)

        def heads(x, w, H, g):
            y = (x @ w).reshape(x.shape[0], H, D)
            return y if g is None else _rms(y, g, eps)

        def turned(x, at):
            if R == D:
                return rotate(x[None], at[None], inv_freq)[0]
            return jnp.concatenate(
                [rotate(x[None, ..., :R], at[None], inv_freq)[0], x[..., R:]],
                axis=-1)

        def project(x, at):
            if cfg.norm_first:
                x = _rms(x, gain, eps)
            q, k = heads(x, w_q, Hq, q_gain), heads(x, w_k, Hkv, k_gain)
            if theta:
                q, k = turned(q, at), turned(k, at)
            v = (x @ w_v).reshape(x.shape[0], Hkv, Dv)
            return q, k, v if cfg.value_scale == 1 else v * cfg.value_scale

        def output(y, x):
            y = y @ w_o
            return x + (y if cfg.norm_first else _rms(y, gain, eps))

        with jax.named_scope("attn/proj"):
            q, k, v = (a.reshape((B, T) + a.shape[1:]) for a in _by_chunks(
                project, h, positions.reshape(B * T), n_real=n_real))
        with jax.named_scope("attn/window" if self.windowed else "attn/full"):
            if cache is None:
                y = gqa_attention.blockwise_attention(
                    q, k, v, **kwargs,
                    window=cfg.sliding_window if self.windowed else None)
            else:
                y, cache = cache.attend(layer, q, k, v, position_offset,
                                        **kwargs)
        with jax.named_scope("attn/proj"):
            return _by_chunks(output, y.reshape(B * T, Hq * Dv), h,
                              n_real=n_real), cache


class ExpertShare(_Weights):
    """``h + RMSNorm(sum g_i FFN_i(h) + FFN_shared(h))`` over the experts
    this model holds (``norm_first``: the norm on the sublayer's input; no
    shared expert where ``num_shared_experts`` is 0), ``h [N, d]``. Returns
    ``(h, (hit, fill, spill))``,
    which ``serving.window_cache.WindowedKVCache.STEP_STATS`` names
    ``experts_hit``, ``experts_fill_pct``, ``experts_spill``: the held
    experts that got a token and the pairs routed to them against the rows
    of a pass, in percent (of a prompt in chunks, the most in any chunk),
    and the passes ``dropless_experts`` made beyond a chunk's first (a
    buffer over 100 percent full).

    A chunk is ``_EXPERT_CHUNK`` tokens where the rows of a pass
    (``share_rows``) then stay within what ``_TOKEN_CHUNK`` tokens' pairs
    are, and ``_TOKEN_CHUNK`` otherwise: 4,096 for 16 of 128 (8,192 rows a
    pass), 2,048 for a holder of every expert, so a held expert's three
    matrices are read once for about 256 rows and not twice for 128. (At 8,192 tokens the gather
    of every token's 8 rows is 805 MB: the share is slower a token on the
    v5e and the 32,768 bucket's temporaries are 4.6 GB where they may be
    3.9: PERF.md, PR 41.)"""

    @nn.compact
    def __call__(self, h, gain, n_real=None):
        cfg = self.cfg
        n, d = h.shape
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        first, held = cfg.held_experts
        router = self.w("router", (d, E), jnp.float32)
        bias = self.param("router_bias", nn.initializers.zeros, (E,),
                          jnp.float32)
        w_gate = self.w("experts_gate", (held, d, F))
        w_up = self.w("experts_up", (held, d, F))
        w_down = self.w("experts_down", (held, F, d))
        shared = cfg.num_shared_experts and GatedMLPWeights(
            cfg, width=F * cfg.num_shared_experts, name="shared")(d)

        k = cfg.num_experts_per_tok
        chunk = (_EXPERT_CHUNK
                 if share_rows(_EXPERT_CHUNK * k, held, E) <= _TOKEN_CHUNK * k
                 else _TOKEN_CHUNK)

        def tokens(h, computed=None):
            x = _rms(h, gain, cfg.rms_norm_eps) if cfg.norm_first else h
            with jax.named_scope("moe/route"):
                experts, gates = held_share(*route_sigmoid_topk(
                    x, router, bias, k, cfg.routed_scaling_factor),
                    first, held)
                if computed is not None:
                    # zeros all route alike: they would crowd one expert's
                    # group and fill passes of their own; no one's pairs
                    experts = jnp.where(computed[:, None], experts, held)
                    gates = jnp.where(computed[:, None], gates, 0.0)
                pairs, passes = share_passes(experts, held, E)
            with jax.named_scope("moe/experts"):
                y, hit = dropless_experts(x, experts, gates, w_gate, w_up,
                                          w_down, num_experts=E)
            if shared:
                with jax.named_scope("moe/shared"):
                    y = y + _gated_mlp(x, *shared)
            fill = 100 * pairs // share_rows(experts.size, held, E)
            if not cfg.norm_first:
                y = _rms(y, gain, cfg.rms_norm_eps)
            return h + y, hit, fill, jnp.maximum(passes - 1, 0)

        xs = (h,)
        computed = computed_tokens(n, n_real)
        if chunk > _TOKEN_CHUNK and not isinstance(computed, int):
            # a chunk here may reach past the last chunk the tokenwise
            # loops ran, into rows they left zero
            xs += (jnp.arange(n) < computed,)
        h, hit, fill, spill = _by_chunks(tokens, *xs, chunk=chunk,
                                         n_real=n_real)
        return h, (hit.max(), fill.max(), spill.sum())


class ExaoneMoE(nn.Module):
    """Decoder-only ``exaone_moe``. Input ``tokens [B, T]`` int32 -> logits
    (see the module docstring for the cache-aware forward)."""

    cfg: ExaoneMoEConfig

    @property
    def cache_class(self):
        from pytorch_distributed_tpu.serving.window_cache import (
            WindowedKVCache,
        )

        return WindowedKVCache

    #: ``(bucket, n_real)`` -> the positions a fresh prefill's tokenwise
    #: loops run (``serving.engine``: the ``engine.prefill`` span's
    #: ``n_computed``)
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
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        if position_offset is not None:
            positions = position_offset[:, None] + positions
        positions = jnp.broadcast_to(positions, (B, T))
        init = nn.initializers.normal(cfg.initializer_range)
        d, eps = cfg.hidden_size, cfg.rms_norm_eps

        def gain(name):
            return self.param(name, nn.initializers.ones, (d,),
                              cfg.param_dtype)

        with jax.named_scope("embed"):
            embed = self.param("embed", init, (cfg.vocab_size, d),
                               cfg.param_dtype)
            h = embed[tokens.reshape(B * T)].astype(cfg.dtype)
        hit = fill = spill = jnp.zeros((), jnp.int32)
        n_real = fresh_prompt_len(kv_cache, position_offset, B)
        for i in range(cfg.n_layer):
            h, kv_cache = Attention(
                cfg, windowed=cfg.layer_windowed[i], name=f"layer_{i}_attn")(
                    h, positions, kv_cache, i, position_offset,
                    gain(f"layer_{i}_attn_norm"), n_real)
            mlp_gain = gain(f"layer_{i}_mlp_norm")
            if cfg.mlp_layer_types[i] == "dense":
                mlp = GatedMLPWeights(cfg, width=cfg.intermediate_size,
                                      name=f"layer_{i}_mlp")(d)

                def dense(x):
                    if cfg.norm_first:
                        return x + _gated_mlp(_rms(x, mlp_gain, eps), *mlp)
                    return x + _rms(_gated_mlp(x, *mlp), mlp_gain, eps)

                with jax.named_scope("mlp"):
                    h = _by_chunks(dense, h, n_real=n_real)
            else:
                h, layer = ExpertShare(cfg, name=f"layer_{i}_moe")(
                    h, mlp_gain, n_real)
                hit, fill, spill = (hit + layer[0],
                                    jnp.maximum(fill, layer[1]),
                                    spill + layer[2])
        with jax.named_scope("head"):
            h = h.reshape(B, T, d)
            if kv_cache is not None and position_offset is None:
                # fresh prefill: only the last real position is sampled from
                last = (kv_cache.lengths - 1) % T
                h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            h = _rms(h, gain("norm"), eps)
            logits = h @ self.param("head", init, (d, cfg.vocab_size),
                                    cfg.param_dtype).astype(cfg.dtype)
        if kv_cache is not None:
            return logits, kv_cache.counted(
                experts_hit=hit, experts_fill_pct=fill, experts_spill=spill)
        return logits
