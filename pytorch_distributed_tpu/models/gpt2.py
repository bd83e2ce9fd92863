"""GPT-2 language model in flax.linen — bf16-friendly, shardable.

Capability parity: HF ``transformers`` GPT-2 125M as trained by the
reference's FSDP WikiText-103 config (SURVEY.md §2.7, config #4). Standard
GPT-2 architecture: learned positional embeddings, pre-LN blocks, GELU(tanh),
causal self-attention, weight-tied LM head.

TPU-first choices:
  * compute dtype vs param dtype split (bf16 compute natively on MXU).
  * attention as one batched einsum program with static shapes — no KV cache
    branches in the training graph.
  * ``attn_impl`` hook: the block calls a pluggable attention function so the
    context-parallel ring attention / Pallas flash kernel
    (pytorch_distributed_tpu.parallel.context_parallel, SURVEY.md §5.7) can
    replace the reference softmax without touching the module tree.
  * optional ``remat`` (jax.checkpoint) per block — the HBM/FLOPs trade.
  * parameter paths are stable (``h_<i>/attn/c_attn`` ...) so sharding rules
    in pytorch_distributed_tpu.parallel address them by regex.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.mesh import pin_activation

__all__ = ["GPT2Config", "GPT2", "gpt2_125m"]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    # selective checkpointing: name of a ``jax.checkpoint_policies``
    # policy (e.g. "dots_with_no_batch_dims_saveable" — save projection/
    # MLP matmul outputs, recompute only elementwise/attention work; the
    # Megatron selective-recompute trade). None = full per-block remat.
    # Setting a policy without remat=True is rejected at model build
    # (a silently-inert memory lever would surface as an OOM instead).
    remat_policy: Optional[str] = None
    # Mixture-of-experts (GShard/Switch): every ``moe_every``-th block swaps
    # its dense MLP for a top-k routed MoEMLP (parallel/expert.py); expert
    # params stack [E, ...] on dim 0 — shard over the 'ep' mesh axis
    # (ExpertDataParallel). The router's load-balance aux loss is weighted
    # by ``moe_aux_weight`` and returned beside the logits; lm_loss
    # consumes it.
    moe_experts: int = 0          # 0 = dense model
    moe_top_k: int = 1
    moe_every: int = 2            # every moe_every-th block (1 = all)
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_group_size: Optional[int] = None
    # pluggable attention: f(q, k, v, causal) -> out, shapes [B, T, H, D]
    attn_impl: Optional[Callable] = None
    # inter-block activation hook: f(x [B, T, C]) -> x, applied after the
    # embedding and after every block. The TP/SP layer passes
    # ``TensorParallel.activation_constraint()`` here so sequence-parallel
    # activation sharding is pinned in the executed program (Megatron SP —
    # torch tensor/parallel/style.py:339 SequenceParallel). Left None, the
    # same sites take the layout the trainer's strategy states while it
    # traces (``mesh.pin_activation``: the batch layout under FSDP/HSDP,
    # nothing anywhere else).
    act_constraint: Optional[Callable] = None
    # LM-head contraction inputs: fp32 casts (the conservative default) or
    # the compute dtype with fp32 ACCUMULATION (preferred_element_type) —
    # the MXU-native path; on v5e the fp32-input head matmul runs well
    # below bf16 peak, so bf16 inputs are the measured-perf choice for
    # bf16 models.
    head_in_fp32: bool = True


def default_attention(q, k, v, *, causal: bool = True):
    """Reference softmax attention, [B, T, H, D] layout, fp32 softmax."""
    B, T, H, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    # [B, H, T, T]
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


class SelfAttention(nn.Module):
    # ``cache`` switches on the serving path (pytorch_distributed_tpu.
    # serving): the cache itself writes the T new tokens' K/V into its
    # ``layer`` and attends over each sequence (``cache.attend``, the one
    # method a model knows of a cache: serving.kv_cache states the
    # protocol). It arrives whole and goes back whole; how K and V are
    # stored is the cache's business. With cache=None the training path is
    # untouched.
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True, cache=None,
                 layer=None, position_offset=None):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.n_head, cfg.n_embd // cfg.n_head
        qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        if cache is None:
            attn = cfg.attn_impl or default_attention
            y = attn(q, k, v, causal=True)
        else:
            y, cache = cache.attend(layer, q, k, v, position_offset)
        y = y.reshape(B, T, C)
        y = nn.Dense(cfg.n_embd, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.n_layer)),
                     name="c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y, cache


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.cfg
        y = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_fc")(x)
        y = nn.gelu(y, approximate=True)
        y = nn.Dense(cfg.n_embd, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.n_layer)),
                     name="c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class Block(nn.Module):
    cfg: GPT2Config
    use_moe: bool = False

    # NOTE: ``deterministic`` is positional (not kw-only) so nn.remat can mark
    # it static (static_argnums) — a traced boolean would crash nn.Dropout.
    @nn.compact
    def __call__(self, x, deterministic: bool = True, *, cache=None,
                 layer=None, position_offset=None):
        """``(x, router aux loss, cache)``; ``cache`` as in SelfAttention."""
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        y, cache = SelfAttention(cfg, name="attn")(
            ln("ln_1")(x), deterministic=deterministic,
            cache=cache, layer=layer, position_offset=position_offset)
        x = x + y
        if self.use_moe:
            from pytorch_distributed_tpu.parallel.expert import MoEMLP

            y, aux = MoEMLP(
                n_experts=cfg.moe_experts,
                d_ff=4 * cfg.n_embd,
                k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                group_size=cfg.moe_group_size,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(ln("ln_2")(x))
            return x + y, aux["aux_loss"], cache
        x = x + MLP(cfg, name="mlp")(ln("ln_2")(x), deterministic=deterministic)
        return x, jnp.float32(0.0), cache


class GPT2(nn.Module):
    """GPT-2 LM. ``__call__(tokens [B, T]) -> logits [B, T, V]`` (fp32).

    ``return_hidden=True`` returns the post-``ln_f`` hidden states
    ``[B, T, C]`` instead of logits — the chunked-cross-entropy loss path
    (``trainer.lm_loss_chunked``) consumes these with the tied ``wte`` head
    so the fp32 ``[B, T, V]`` logits tensor never materializes.

    ``kv_cache`` (a ``serving.kv_cache.KVCache`` or a
    ``serving.paging.PagedKVCache``) makes the same forward the serving
    one: positions come from ``position_offset`` (``[B]`` int32, the
    current length of each cache slot), each block attends through
    ``kv_cache.attend`` instead of over the T x T causal window, and the
    call returns ``(logits, new_kv_cache)``. Every param binds to the path
    training creates: a training checkpoint IS the serving checkpoint.
    Prefill is this call at T = padded prompt length with NO offset (every
    sequence fresh, from position 0); decode is T = 1 at offset = slot
    length, and the speculative verify step is T = k+1 at the same offset
    (the cache masks per position, so a multi-token window is causal over
    global positions for free). The training path (``kv_cache=None``) is
    untouched.

    ``n_layers`` (with a cache only) truncates the stack: run the first N
    blocks, then ``ln_f`` + the tied head — the self-drafting draft of
    speculative decoding. Layers ``0..N-1`` compute exactly what the full
    forward computes there, so the draft shares the target's cache (only
    the first N layers' K/V are written; the verify pass rewrites them).
    """

    cfg: GPT2Config

    @nn.compact
    def __call__(
        self, tokens, *, deterministic: bool = True,
        return_hidden: bool = False,
        kv_cache=None, position_offset=None, n_layers=None,
    ):
        cfg = self.cfg
        B, T = tokens.shape
        nl = cfg.n_layer if n_layers is None else int(n_layers)
        if kv_cache is None:
            if n_layers is not None:
                raise ValueError(
                    "n_layers (truncated draft forward) requires kv_cache"
                )
            if T > cfg.n_positions:
                raise ValueError(
                    f"sequence length {T} exceeds n_positions "
                    f"{cfg.n_positions}"
                )
            pin = pin_activation
        else:
            # what the serving forward does not do, stated once: no routed
            # MLP (it has no cache story yet), no dropout, no remat (no
            # gradient flows here), no pin of its own (``act_constraint``
            # or nothing)
            if cfg.moe_experts > 0:
                raise ValueError(
                    "kv_cache forward supports dense GPT-2 only "
                    "(moe_experts must be 0)"
                )
            if kv_cache.n_layers != cfg.n_layer:
                raise ValueError(
                    f"kv_cache has {kv_cache.n_layers} layers, model has "
                    f"{cfg.n_layer}"
                )
            if not (1 <= nl <= cfg.n_layer):
                raise ValueError(
                    f"n_layers {nl} must be in [1, n_layer={cfg.n_layer}]"
                )
            deterministic = True
            pin = lambda a: a
        if cfg.remat_policy is not None and not cfg.remat:
            raise ValueError(
                "remat_policy set but remat=False — the policy only "
                "selects WHAT nn.remat saves; enable remat=True"
            )
        wte = self.param(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.n_embd),
            cfg.param_dtype,
        )
        wpe = self.param(
            "wpe",
            nn.initializers.normal(0.01),
            (cfg.n_positions, cfg.n_embd),
            cfg.param_dtype,
        )
        if kv_cache is None:
            pos = slice(T)
        else:
            # each token's GLOBAL position; the clamp guards the padded
            # tail of an over-long prefill (the engine discards those query
            # rows). No offset = every sequence starts at 0.
            pos = jnp.arange(T, dtype=jnp.int32)[None]
            if position_offset is not None:
                pos = position_offset[:, None] + pos
            pos = jnp.minimum(pos, cfg.n_positions - 1)
        with jax.named_scope("embed"):
            x = wte[tokens].astype(cfg.dtype) + wpe[pos].astype(cfg.dtype)
        if cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        constrain = cfg.act_constraint or pin
        x = constrain(x)
        block = Block
        if cfg.remat and kv_cache is None:
            policy = (
                getattr(jax.checkpoint_policies, cfg.remat_policy)
                if cfg.remat_policy is not None else None
            )
            # arg 0 is the module, 1 is x, 2 is deterministic (static)
            block = nn.remat(Block, static_argnums=(2,), policy=policy)
        aux_total = jnp.float32(0.0)
        for i in range(nl):
            use_moe = (
                cfg.moe_experts > 0
                and (i + 1) % cfg.moe_every == 0
            )
            # the cache threads through the blocks whole: each writes its
            # own layer (a truncated draft leaves the later layers as is)
            x, aux, kv_cache = block(cfg, use_moe, name=f"h_{i}")(
                x, deterministic, cache=kv_cache, layer=i,
                position_offset=position_offset)
            aux_total = aux_total + aux
            x = constrain(x)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        # what the losses consume lies as the batch does too: the hidden
        # state here, the logits below (gathered whole on every chip
        # otherwise, when FSDP shards ``wte``)
        x = pin(x)
        if return_hidden:
            out = x
        else:
            # weight-tied LM head; logits in fp32 for a stable softmax/loss
            # (a param is no submodule, so Flax scopes neither this nor the
            # embedding lookup: "head" names it for the device trace)
            with jax.named_scope("head"):
                if cfg.head_in_fp32:
                    logits = jnp.einsum(
                        "btc,vc->btv", x.astype(jnp.float32),
                        wte.astype(jnp.float32),
                    )
                else:
                    logits = jnp.einsum(
                        "btc,vc->btv", x, wte.astype(cfg.dtype),
                        preferred_element_type=jnp.float32,
                    )
                out = pin(logits)
        if kv_cache is not None:
            return out, kv_cache
        if cfg.moe_experts > 0:
            # weighted router load-balance loss, consumed by lm_loss
            return out, cfg.moe_aux_weight * aux_total
        return out


def gpt2_125m(**overrides) -> GPT2:
    """The reference's FSDP workload model (config #4)."""
    return GPT2(GPT2Config(**overrides))
