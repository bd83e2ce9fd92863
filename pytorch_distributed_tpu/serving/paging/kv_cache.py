"""Paged KV cache — a pool of fixed-size pages plus per-sequence block tables.

The device half of the paging subsystem. Where the slotted ``KVCache``
reserves ``max_len`` tokens per slot up front, this pytree holds one
shared pool of ``n_pages`` pages of ``page_size`` tokens per layer:
``k``/``v`` are ``[L, n_pages, page_size, H, D]`` and each slot's chain of
page ids lives in ``block_tables [S, max_pages]`` (table position ``m``
covers global token positions ``m*page_size .. (m+1)*page_size-1``).
Same discipline as the slotted cache: the whole pytree threads through the
jitted serving steps as a donated buffer, and the TP plan shards the head
dim (serving.sharding.paged_kv_cache_sharding).

Page id 0 is the TRASH page: never allocated, never referenced by a live
chain. Evicted slots get an all-zero table row, so the padding-lane writes
every batched step performs for inactive slots land in page 0 (the paged
analogue of inactive slots harmlessly writing their own slotted rows), and
gathers through a zero row read page 0 — masked by the ``position <=
query`` visibility invariant. Eviction therefore never zeroes K/V bytes:
masking plus page ownership (a live sequence's visible positions were all
written by itself — serving.paging.allocator's COW discipline) is the
isolation boundary.

Which pages a slot may write is host-side state (PageAllocator); this
pytree only knows the mapping. ``lengths`` carries the same
advance/rollback semantics as the slotted cache so the speculative-decode
programs work unchanged on either cache kind.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from pytorch_distributed_tpu.ops.paged_attention import paged_cached_attention

__all__ = ["PagedKVCache", "fork_pages"]

TRASH_PAGE = 0


class PagedKVCache(struct.PyTreeNode):
    """Page pools ``[L, P, page, H, D]`` + ``block_tables [S, M]`` +
    per-slot ``lengths [S]``. A plain pytree: jit-carried, donatable,
    shardable."""

    k: jax.Array
    v: jax.Array
    block_tables: jax.Array
    lengths: jax.Array

    @classmethod
    def create(
        cls,
        cfg: Any,
        *,
        n_slots: int,
        max_len: int,
        page_size: int = 16,
        n_pages: int | None = None,
        dtype: Any = None,
    ) -> "PagedKVCache":
        """Zero-filled paged cache for a ``GPT2Config``-shaped model.

        ``max_len`` bounds prompt + generated tokens per sequence (rounded
        up to whole pages for the block table width). ``n_pages`` defaults
        to slotted-equivalent capacity (every slot can hold ``max_len``)
        plus the trash page; pass a smaller pool to run more slots than
        worst-case capacity — admission then backpressures on free pages.
        """
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}"
            )
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        max_pages = -(-max_len // page_size)
        if n_pages is None:
            n_pages = n_slots * max_pages + 1  # + trash page
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is the trash page)")
        H, D = cfg.n_head, cfg.n_embd // cfg.n_head
        shape = (cfg.n_layer, n_pages, page_size, H, D)
        dtype = dtype or cfg.dtype
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            block_tables=jnp.zeros((n_slots, max_pages), jnp.int32),
            lengths=jnp.zeros((n_slots,), jnp.int32),
        )

    # -- introspection (host-side; cheap static shape reads) ---------------
    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def max_pages(self) -> int:
        return self.block_tables.shape[1]

    @property
    def max_len(self) -> int:
        return self.max_pages * self.page_size

    def bytes_per_page(self) -> int:
        """HBM footprint of one page (both K and V, all layers)."""
        per = self.k.dtype.itemsize
        L, _, T, H, D = self.k.shape
        return 2 * L * T * H * D * per

    def placed(self, sharding) -> "PagedKVCache":
        """The pools laid out as ``sharding`` says (the TP plan's
        ``serving.sharding.paged_kv_cache_sharding``)."""
        return self.replace(
            k=jax.device_put(self.k, sharding),
            v=jax.device_put(self.v, sharding),
        )

    def attend(self, layer: int, q, k_new, v_new, position_offset):
        """The cache protocol's one method (``serving.kv_cache``): scatter
        the T new tokens' K/V through the block tables into ``layer``'s
        pools and attend over each sequence's chain
        (``ops.paged_attention``): ``(y [B, T, H, D], cache)`` with that
        layer's pools written back. ``position_offset=None`` means every
        sequence is fresh, from position 0."""
        if position_offset is None:
            position_offset = jnp.zeros((q.shape[0],), jnp.int32)
        y, k, v = paged_cached_attention(
            q, k_new, v_new, self.k[layer], self.v[layer],
            self.block_tables, position_offset,
        )
        return y, self.replace(
            k=self.k.at[layer].set(k), v=self.v.at[layer].set(v)
        )

    # -- a prompt into one chain -------------------------------------------
    def one_chain(self, slot, n_new) -> "PagedKVCache":
        """The view a prompt (or its uncached tail) is prefilled through:
        the pools are sequence-agnostic, so unlike the slotted cache there
        is no per-slot slice; B=1 comes from viewing ``slot``'s table row
        alone. (``n_new``, the tokens that are real, is what a latent
        pool's view carries; this one's model gives every position's
        logits.)"""
        row = jax.lax.dynamic_slice_in_dim(self.block_tables, slot, 1, axis=0)
        return self.replace(block_tables=row,
                            lengths=jnp.zeros((1,), jnp.int32))

    def write_chain(self, slot, view: "PagedKVCache", length
                    ) -> "PagedKVCache":
        """The pools as ``view`` left them, ``lengths[slot] = length``."""
        return self.replace(k=view.k, v=view.v,
                            lengths=self.lengths.at[slot].set(length))

    def fork(self, src, dst) -> "PagedKVCache":
        """Page ``src`` copied into ``dst`` across all layers (K and V)."""
        return self.replace(k=self.k.at[:, dst].set(self.k[:, src]),
                            v=self.v.at[:, dst].set(self.v[:, src]))

    # -- lifecycle (lengths/table bookkeeping; page ownership is host-side) -
    def evict(self, slot) -> "PagedKVCache":
        """Free a slot: zero its length AND its table row, so the slot's
        padding-lane writes land in the trash page. K/V bytes stay —
        masking + the allocator's refcounts keep them unreachable until the
        pages are reused (and rewritten) by a new owner."""
        return self.replace(
            lengths=self.lengths.at[slot].set(0),
            block_tables=self.block_tables.at[slot].set(TRASH_PAGE),
        )

    def set_table_row(self, slot, row) -> "PagedKVCache":
        """Install a slot's page chain (host-computed by the allocator)."""
        return self.replace(
            block_tables=self.block_tables.at[slot].set(
                jnp.asarray(row, jnp.int32)
            )
        )

    # -- speculative decode bookkeeping (identical to the slotted cache) ---
    def advance(self, n_tokens, active=None) -> "PagedKVCache":
        n = jnp.asarray(n_tokens, jnp.int32)
        if active is not None:
            n = jnp.where(active, n, 0)
        return self.replace(lengths=self.lengths + n)

    def rollback(self, lengths) -> "PagedKVCache":
        """Reset per-slot lengths (rejection rollback). Speculative K/V
        bytes past the new length stay in their pages, masked; the
        *page-granular* half of rollback — returning pages acquired for
        the rejected span to the free list — is the allocator's job
        (PageAllocator.release_tail)."""
        return self.replace(lengths=jnp.asarray(lengths, jnp.int32))


def _fork_impl(cache, src, dst):
    return cache.fork(jnp.asarray(src, jnp.int32),
                      jnp.asarray(dst, jnp.int32))


# Module-level jitted entry point, imported by the scheduler: graftlint's
# cross-file jit-binding resolution carries the donation spec to callers.
fork_pages = jax.jit(_fork_impl, donate_argnums=(0,))
fork_pages.__doc__ = """Copy-on-write fork: duplicate page ``src`` into
``dst`` across all layers (``cache.fork``: a ``PagedKVCache``'s K and V, a
``PagedLatentCache``'s rows). Called before a write would land in a
shared (refcount > 1) page — the writer re-points its table entry at
``dst`` and the shared original stays frozen. Donates the cache, so the
copy is an in-place HBM page copy, not a pool realloc."""
