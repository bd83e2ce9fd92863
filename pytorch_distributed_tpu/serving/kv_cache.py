"""Preallocated slotted KV cache — the serving engine's resident state.

One cache = ``n_slots`` independent sequence slots, each ``max_len`` tokens
deep, for every layer: ``k``/``v`` are ``[L, S, max_len, H*D]`` arrays that
live in device memory across the whole serving session and thread through
the jitted prefill/decode steps as a donated pytree (no realloc, no shape
churn — the static-shape analogue of vLLM's paged pool with page size =
max_len; per-slot lengths are the page table).

The heads are folded into the minor dimension so that a token's K (or V)
of one layer is ONE contiguous ``H*D``-wide row. The TPU pads the minor
dimension to 128 lanes: ``[..., H, D]`` with D = 64 made the compiler keep
the cache positions-minor and copy every layer's slab to a head-dim-minor
layout and back around each one-row write (37.8 of a 51 ms decode step,
PERF.md PR 25). A 768-wide row needs no padding and serves both the row
write and the attention read (``ops.decode_attention`` contracts against
the rows as stored), so a decode step writes ``2 * L * S`` rows in place
and reads K and V once: ``tests/test_chip_compile.py`` holds the compiled
decode program to that.

Slot lifecycle (driven by serving.scheduler):
  * admit   — prefill writes positions ``0..Tpad-1`` of a free slot and
    sets ``lengths[slot] = prompt_len``.
  * decode  — each step writes one token at position ``lengths[slot]`` and
    advances only the ACTIVE slots' lengths.
  * evict   — ``lengths[slot] = 0``; the K/V bytes are NOT zeroed. Masking
    is the isolation boundary: a query at position p attends cache entries
    ``<= p``, all of which were written by the current occupant
    (ops.decode_attention invariant), so stale bytes from a previous
    request are unreachable.

THE CACHE PROTOCOL. How K and V are stored is this class's business (and
``serving.paging.PagedKVCache``'s), nobody else's. A model touches a cache
through one method, the same on both classes:

    ``cache.attend(layer, q, k_new, v_new, position_offset) -> (y, cache)``

``q / k_new / v_new`` are ``[B, T, H, D]`` (batch row b is slot b); the
cache arrives whole and goes back whole with ``layer``'s new rows (or
pages) written; ``position_offset [B]`` is its sequences' first new
positions, ``None`` if all are fresh. Around it the engine and scheduler
use ``create / placed / evict / advance / rollback``, ``n_layers / n_slots
/ max_len``. The names are the protocol; ``LatentCache`` (below, with its
own ``attend`` operands) is a third class with them. No base, no registry.

TWO READS, ONE RESULT. A sequence's earlier rows are read either by the
lengths-aware kernel of ``ops.decode_attention`` (only the blocks of
positions the slot holds: at a chat's occupancy a thirtieth of the cache)
or by the dense contraction against every position of every slot. Which
one is decided here, in ``attend``, from where the cache lies and from
nothing anybody passes: the kernel where the backend is a TPU and the cache
sits whole on each device; the dense read on any other backend (Mosaic has
no CPU) and for a cache that ``placed`` laid out over a mesh (the
partitioner cannot split a custom call, and would gather the cache to run
it). A separation, not a choice: there is no configuration in which both
could serve, so there is nothing to select.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from pytorch_distributed_tpu.ops import latent_attention
from pytorch_distributed_tpu.ops.decode_attention import (
    cached_attention, kernel_reads)
# (folded so KVCache.attend keeps line 156: GPT-2's decode kernel records it)

__all__ = ["KVCache", "LatentCache"]


class KVCache(struct.PyTreeNode):
    """Per-layer K/V arrays ``[L, S, T, H*D]`` + per-slot ``lengths [S]``.

    A plain pytree: jit-carried, donatable, shardable (the serving TP plan
    puts the folded head dim on the ``tp`` axis — whole heads per device
    when ``tp`` divides ``H`` — matching the colwise-sharded ``c_attn``
    that produces it; see serving.sharding).
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    #: ``placed`` laid K and V out over a mesh (static: part of the tree's
    #: structure, so a program is traced for one kind of cache)
    sharded: bool = struct.field(pytree_node=False, default=False)

    @classmethod
    def create(
        cls,
        cfg: Any,
        *,
        n_slots: int,
        max_len: int,
        dtype: Any = None,
    ) -> "KVCache":
        """Zero-filled cache for a ``GPT2Config``-shaped model.

        ``max_len`` bounds prompt + generated tokens per slot and must fit
        the model's learned positional table.
        """
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}"
            )
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        shape = (cfg.n_layer, n_slots, max_len, cfg.n_embd)
        dtype = dtype or cfg.dtype
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((n_slots,), jnp.int32),
        )

    # -- introspection (host-side; cheap static shape reads) ---------------
    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    def bytes_per_slot(self) -> int:
        """HBM footprint of one slot (both K and V, all layers)."""
        per = self.k.dtype.itemsize
        L, _, T, C = self.k.shape
        return 2 * L * T * C * per

    def placed(self, sharding) -> "KVCache":
        """K and V laid out as ``sharding`` says (the TP plan's
        ``serving.sharding.kv_cache_sharding``); the cache remembers that
        it no longer lies whole on one device."""
        return self.replace(
            k=jax.device_put(self.k, sharding),
            v=jax.device_put(self.v, sharding),
            sharded=True,
        )

    def attend(self, layer: int, q, k_new, v_new, position_offset):
        """Write the T new tokens' K/V rows into ``layer`` and attend over
        each slot (``ops.decode_attention``): ``(y [B, T, H, D], cache)``.
        ``position_offset=None`` is the fresh prefill: the new tokens attend
        each other and the cache is written, never read. Otherwise the
        earlier rows are read by the lengths-aware kernel wherever it can
        run (module docstring), else densely."""
        y, k, v = cached_attention(
            q, k_new, v_new, self.k, self.v, layer, position_offset,
            kernel=not self.sharded and kernel_reads(self.k),
        )
        return y, self.replace(k=k, v=v)

    # -- prefill into one slot ---------------------------------------------
    def one_slot(self, n_positions: int, length=0) -> "KVCache":
        """A fresh one-slot cache of ``n_positions``, otherwise shaped as
        this one: what a prompt is prefilled into before ``write_slot``
        lands it, so that nothing of the resident cache is read.
        ``length`` (host or traced int) says how many of the positions the
        prompt will really fill."""
        n_layers, _, _, width = self.k.shape
        rows = jnp.zeros((n_layers, 1, n_positions, width), self.k.dtype)
        return KVCache(k=rows, v=rows,
                       lengths=jnp.full((1,), length, jnp.int32))

    def write_slot(self, slot, block: "KVCache", length) -> "KVCache":
        """``block`` (a ``one_slot`` cache, filled) written over positions
        ``0..`` of ``slot`` as one in-place block, and ``lengths[slot] =
        length`` (``slot`` and ``length`` may be traced)."""
        at = (0, slot, 0, 0)
        return self.replace(
            k=jax.lax.dynamic_update_slice(self.k, block.k, at),
            v=jax.lax.dynamic_update_slice(self.v, block.v, at),
            lengths=self.lengths.at[slot].set(length),
        )

    def evict(self, slot) -> "KVCache":
        """Free a slot (host or traced int). K/V bytes stay — masked out."""
        return self.replace(lengths=self.lengths.at[slot].set(0))

    # -- speculative decode bookkeeping ------------------------------------
    def advance(self, n_tokens, active=None) -> "KVCache":
        """Multi-token append: ``lengths += n_tokens`` (``[S]`` or scalar),
        masked to ``active`` slots. The K/V bytes were already scattered by
        ``attend`` — this commits how many of them are real.
        """
        n = jnp.asarray(n_tokens, jnp.int32)
        if active is not None:
            n = jnp.where(active, n, 0)
        return self.replace(lengths=self.lengths + n)

    def rollback(self, lengths) -> "KVCache":
        """Reset per-slot lengths (rejection rollback). Positions past the
        new length keep their speculative K/V bytes — the masking invariant
        hides them and the next step's writes overwrite them, so no memset,
        no realloc, no shape churn."""
        return self.replace(lengths=jnp.asarray(lengths, jnp.int32))


class LatentCache(struct.PyTreeNode):
    """The slotted cache of a latent-attention (MLA) model: ONE row a token
    a layer, ``rows [L, S, T, W]`` = ``[c_kv | k_r | 0]`` padded to whole
    lanes (``ops.latent_attention`` says why 576 is stored 640 wide), and
    per-slot ``lengths [S]``. The protocol's names are ``KVCache``'s and so
    is the slot lifecycle; what ``attend`` takes is this format's own: the
    queries, the new latents and the map ``W_kvb`` from a latent to a
    head's keys and values, which a fresh prefill expands with and a decode
    step absorbs into its queries.

    ``step_stats [len(STEP_STATS)]`` is what the model counted while it
    last ran over this cache (``experts_hit``: the distinct experts that
    got a token, summed over layers); the engine sends it to the host in
    the read of the step's tokens."""

    STEP_STATS = ("experts_hit",)

    rows: jax.Array
    lengths: jax.Array
    step_stats: jax.Array

    @classmethod
    def create(cls, cfg: Any, *, n_slots: int, max_len: int,
               dtype: Any = None) -> "LatentCache":
        """Zero-filled cache for a config with ``n_layer``,
        ``kv_lora_rank``, ``qk_rope_head_dim``, ``n_positions``, ``dtype``."""
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}")
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        width = latent_attention.row_width(cfg.kv_lora_rank,
                                           cfg.qk_rope_head_dim)
        return cls(
            rows=jnp.zeros((cfg.n_layer, n_slots, max_len, width),
                           dtype or cfg.dtype),
            lengths=jnp.zeros((n_slots,), jnp.int32),
            step_stats=jnp.zeros((len(cls.STEP_STATS),), jnp.int32),
        )

    @property
    def n_layers(self) -> int:
        return self.rows.shape[0]

    @property
    def n_slots(self) -> int:
        return self.rows.shape[1]

    @property
    def max_len(self) -> int:
        return self.rows.shape[2]

    def placed(self, sharding) -> "LatentCache":
        raise NotImplementedError(
            "a latent cache is one row for all heads: there is no head "
            "axis to lay over a mesh (ROADMAP: a tensor-parallel plan for "
            "latent attention)")

    def attend(self, layer: int, q, latent, kv_b, position_offset, *,
               scale: float):
        """Write the T new tokens' latents into ``layer`` and attend over
        each slot: ``(y [B, T, H, d_v], cache)``. ``q [B, T, H, d_n + d_r]``,
        ``latent [B, T, d_c + d_r]``, ``kv_b [d_c, H, d_n + d_v]``.
        ``position_offset=None`` is the fresh prefill (expanded, nothing
        read); otherwise the absorbed read, by the lengths-aware kernel
        wherever it can run, else densely."""
        d_c = kv_b.shape[0]
        y, rows = latent_attention.latent_attention(
            q, latent, kv_b, self.rows, layer, position_offset,
            d_c=d_c, d_n=q.shape[-1] - (latent.shape[-1] - d_c),
            scale=scale, kernel=kernel_reads(self.rows),
        )
        return y, self.replace(rows=rows)

    def counted(self, **stats) -> "LatentCache":
        """The cache with the step's counts (``STEP_STATS``) set."""
        return self.replace(step_stats=jnp.stack(
            [jnp.asarray(stats[name], jnp.int32)
             for name in self.STEP_STATS]))

    # -- prefill into one slot ---------------------------------------------
    def one_slot(self, n_positions: int, length=0) -> "LatentCache":
        n_layers, _, _, width = self.rows.shape
        return self.replace(
            rows=jnp.zeros((n_layers, 1, n_positions, width),
                           self.rows.dtype),
            lengths=jnp.full((1,), length, jnp.int32))

    def write_slot(self, slot, block: "LatentCache", length) -> "LatentCache":
        return self.replace(
            rows=jax.lax.dynamic_update_slice(self.rows, block.rows,
                                              (0, slot, 0, 0)),
            lengths=self.lengths.at[slot].set(length),
            step_stats=block.step_stats,
        )

    def evict(self, slot) -> "LatentCache":
        return self.replace(lengths=self.lengths.at[slot].set(0))

    def advance(self, n_tokens, active=None) -> "LatentCache":
        n = jnp.asarray(n_tokens, jnp.int32)
        if active is not None:
            n = jnp.where(active, n, 0)
        return self.replace(lengths=self.lengths + n)

    def rollback(self, lengths) -> "LatentCache":
        return self.replace(lengths=jnp.asarray(lengths, jnp.int32))

    @staticmethod
    def paged_class():
        """The class that holds these rows in pages (``InferenceEngine(
        cache_kind="paged")``; down here so that ``attend`` keeps its
        line, which Xing4.0's read kernel records)."""
        from pytorch_distributed_tpu.serving.paging import PagedLatentCache

        return PagedLatentCache
