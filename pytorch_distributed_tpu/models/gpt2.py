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
    # bf16 models (perf/xent_ab.py).
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
    # ``layer_cache``/``position_offset`` switch on the serving decode path
    # (pytorch_distributed_tpu.serving): K/V for the T new tokens are
    # scattered into the preallocated cache and attention runs densely over
    # each slot (ops.decode_attention — the Pallas flash kernel's T x T
    # blocking doesn't apply at T=1). The slotted cache arrives WHOLE,
    # ``(k, v)`` of ``[L, S, Tmax, H*D]`` with ``cache_layer`` naming this
    # block's layer, and goes back whole: rows are written where they lie.
    # The paged cache arrives as this layer's ``(k_pages, v_pages,
    # block_tables)``. With layer_cache=None the training path is untouched.
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True, layer_cache=None,
                 cache_layer=None, position_offset=None):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.n_head, cfg.n_embd // cfg.n_head
        qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        new_cache = None
        if layer_cache is None:
            attn = cfg.attn_impl or default_attention
            y = attn(q, k, v, causal=True)
        elif len(layer_cache) == 3:
            # paged serving path: (k_pages, v_pages, block_tables) — the
            # new K/V scatter through the block table into the shared page
            # pool (ops.paged_attention)
            from pytorch_distributed_tpu.ops.paged_attention import (
                paged_cached_attention,
            )

            y, ck, cv = paged_cached_attention(
                q, k, v, layer_cache[0], layer_cache[1], layer_cache[2],
                position_offset,
            )
            new_cache = (ck, cv)
        else:
            from pytorch_distributed_tpu.ops.decode_attention import (
                cached_attention,
            )

            y, ck, cv = cached_attention(
                q, k, v, layer_cache[0], layer_cache[1], cache_layer,
                position_offset,
            )
            new_cache = (ck, cv)
        y = y.reshape(B, T, C)
        y = nn.Dense(cfg.n_embd, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=nn.initializers.normal(0.02 / jnp.sqrt(2 * cfg.n_layer)),
                     name="c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        if layer_cache is None:
            return y
        return y, new_cache


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
    def __call__(self, x, deterministic: bool = True, *, layer_cache=None,
                 cache_layer=None, position_offset=None):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        if layer_cache is not None:
            # serving decode path: dense block only (the engine rejects MoE
            # configs), returns the updated cache beside the residual
            y, new_cache = SelfAttention(cfg, name="attn")(
                ln("ln_1")(x), deterministic=deterministic,
                layer_cache=layer_cache, cache_layer=cache_layer,
                position_offset=position_offset)
            x = x + y
            x = x + MLP(cfg, name="mlp")(
                ln("ln_2")(x), deterministic=deterministic)
            return x, new_cache
        x = x + SelfAttention(cfg, name="attn")(
            ln("ln_1")(x), deterministic=deterministic)
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
            return x + y, aux["aux_loss"]
        x = x + MLP(cfg, name="mlp")(ln("ln_2")(x), deterministic=deterministic)
        return x, jnp.float32(0.0)


class GPT2(nn.Module):
    """GPT-2 LM. ``__call__(tokens [B, T]) -> logits [B, T, V]`` (fp32).

    ``return_hidden=True`` returns the post-``ln_f`` hidden states
    ``[B, T, C]`` instead of logits — the chunked-cross-entropy loss path
    (``trainer.lm_loss_chunked``) consumes these with the tied ``wte`` head
    so the fp32 ``[B, T, V]`` logits tensor never materializes.

    ``kv_cache`` (a ``serving.kv_cache.KVCache``) switches on the serving
    forward: positions come from ``position_offset`` (``[B]`` int32, the
    current length of each cache slot), each block attends over its cache
    slot instead of the T x T causal window, and the call returns
    ``(logits, new_kv_cache)``. Prefill is this path at T = padded prompt
    length with NO offset (every sequence fresh, from position 0: the
    slotted attention then never reads the cache); decode is T = 1 at
    offset = slot length, and the speculative verify step is T = k+1 at
    the same offset (the cached attention masks per-position, so a
    multi-token window is causal over global positions for free). The
    training path (``kv_cache=None``) is untouched.

    ``n_layers`` (cached path only) truncates the stack: run the first N
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
        if kv_cache is not None:
            return self._cached_forward(
                tokens, kv_cache, position_offset,
                deterministic=deterministic, n_layers=n_layers,
            )
        if n_layers is not None:
            raise ValueError(
                "n_layers (truncated draft forward) requires kv_cache"
            )
        if T > cfg.n_positions:
            raise ValueError(
                f"sequence length {T} exceeds n_positions {cfg.n_positions}"
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
        with jax.named_scope("embed"):
            x = wte[tokens].astype(cfg.dtype) + wpe[:T].astype(cfg.dtype)
        if cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        constrain = cfg.act_constraint or pin_activation
        x = constrain(x)
        block = Block
        if cfg.remat_policy is not None and not cfg.remat:
            raise ValueError(
                "remat_policy set but remat=False — the policy only "
                "selects WHAT nn.remat saves; enable remat=True"
            )
        if cfg.remat:
            policy = (
                getattr(jax.checkpoint_policies, cfg.remat_policy)
                if cfg.remat_policy is not None else None
            )
            # arg 0 is the module, 1 is x, 2 is deterministic (static)
            block = nn.remat(Block, static_argnums=(2,), policy=policy)
        aux_total = jnp.float32(0.0)
        for i in range(cfg.n_layer):
            use_moe = (
                cfg.moe_experts > 0
                and (i + 1) % cfg.moe_every == 0
            )
            x, aux = block(cfg, use_moe, name=f"h_{i}")(x, deterministic)
            aux_total = aux_total + aux
            x = constrain(x)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        # what the losses consume lies as the batch does too: the hidden
        # state here, the logits below (gathered whole on every chip
        # otherwise, when FSDP shards ``wte``)
        x = pin_activation(x)
        if return_hidden:
            if cfg.moe_experts > 0:
                return x, cfg.moe_aux_weight * aux_total
            return x
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
            logits = pin_activation(logits)
        if cfg.moe_experts > 0:
            # weighted router load-balance loss, consumed by lm_loss
            return logits, cfg.moe_aux_weight * aux_total
        return logits

    def _cached_forward(self, tokens, kv_cache, position_offset,
                        *, deterministic: bool = True, n_layers=None):
        """Serving forward over a KV cache: ``(logits, new_kv_cache)``.

        Called from the compact ``__call__`` so every param binds to the
        same path the training forward creates — a training checkpoint IS
        the serving checkpoint. Remat is ignored (no gradients flow here)
        and MoE blocks are rejected (the routed MLP has no cache story yet).

        ``n_layers`` truncates to the first N blocks (self-drafting); the
        returned cache updates ONLY those layers' K/V, in place.
        """
        cfg = self.cfg
        B, T = tokens.shape
        if cfg.moe_experts > 0:
            raise ValueError(
                "kv_cache forward supports dense GPT-2 only "
                "(moe_experts must be 0)"
            )
        if kv_cache.k.shape[0] != cfg.n_layer:
            raise ValueError(
                f"kv_cache has {kv_cache.k.shape[0]} layers, model has "
                f"{cfg.n_layer}"
            )
        nl = cfg.n_layer if n_layers is None else int(n_layers)
        if not (1 <= nl <= cfg.n_layer):
            raise ValueError(
                f"n_layers {nl} must be in [1, n_layer={cfg.n_layer}]"
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
        # duck-typed cache dispatch: a paged cache carries block tables and
        # each layer's K/V is a page pool the sequences index through them
        paged = hasattr(kv_cache, "block_tables")
        # learned positional embedding at each token's GLOBAL position;
        # clamp guards the padded tail of an over-long prefill (those
        # query rows are discarded by the engine). No offset = every
        # sequence starts at 0, which the slotted attention is told as
        # such (a fresh prefill never reads the cache).
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        if position_offset is not None:
            pos = position_offset[:, None] + pos
        elif paged:
            position_offset = jnp.zeros((B,), jnp.int32)
        pos = jnp.minimum(pos, cfg.n_positions - 1)
        x = wte[tokens].astype(cfg.dtype) + wpe[pos].astype(cfg.dtype)

        constrain = cfg.act_constraint or (lambda a: a)
        x = constrain(x)
        # the slotted cache threads through the blocks whole: each writes
        # its own layer's rows into the one (donated) array
        k, v = kv_cache.k, kv_cache.v
        new_k, new_v = [], []
        for i in range(nl):
            layer_cache = (
                (kv_cache.k[i], kv_cache.v[i], kv_cache.block_tables)
                if paged else (k, v)
            )
            x, (ck, cv) = Block(cfg, False, name=f"h_{i}")(
                x, deterministic,
                layer_cache=layer_cache, cache_layer=i,
                position_offset=position_offset,
            )
            if paged:
                new_k.append(ck)
                new_v.append(cv)
            else:
                k, v = ck, cv
            x = constrain(x)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
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
        if not paged:
            # a truncated draft wrote only the first nl layers' rows
            new_cache = kv_cache.replace(k=k, v=v)
        elif nl == cfg.n_layer:
            new_cache = kv_cache.replace(
                k=jnp.stack(new_k), v=jnp.stack(new_v)
            )
        else:
            # truncated draft: only the first nl layers' K/V move (static
            # slice — in place under jit when the cache is donated)
            new_cache = kv_cache.replace(
                k=kv_cache.k.at[:nl].set(jnp.stack(new_k)),
                v=kv_cache.v.at[:nl].set(jnp.stack(new_v)),
            )
        return logits, new_cache


def gpt2_125m(**overrides) -> GPT2:
    """The reference's FSDP workload model (config #4)."""
    return GPT2(GPT2Config(**overrides))
