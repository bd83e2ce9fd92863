"""Paged latent (MLA) cache: ``serving.kv_cache.LatentCache``'s row ``[c_kv
| k_r | 0]`` (ONE row a token a layer for all heads, 640 wide for 512 + 64)
in ``PagedKVCache``'s pool: ``rows [L, P, page, W]`` shared by every
sequence, ``block_tables [S, M]`` and per-slot ``lengths [S]``. Page 0 is
the trash page; which pages a slot may write is host-side state
(``PageAllocator``), which prompts' pages may be shared the ``RadixTree``'s:
neither knows a row's width, and this pytree only knows the mapping.

A row of 1,152 stored bytes a token a layer is why a latent model is served
over long shared documents: a document's pages are held ONCE whoever asks,
and a later ask of it computes its own tail only.

The cache protocol's names (``serving.kv_cache``) with ``LatentCache``'s
operands: ``attend(layer, q, latent, kv_b, position_offset, scale=)``,
``counted``, ``STEP_STATS``. Three attentions, all in
``ops.latent_paged_attention``:

  * a decode or verify step (the resident cache, batch row b slot b): the
    absorbed read through the tables, by the paged kernel wherever it can
    run, else densely;
  * a prompt (``one_chain(slot, n_new)``: ONE table row, ``lengths`` = how
    many of the bucket's tokens are real, ``prompt`` set) from position 0
    with nothing cached (``position_offset=None``): whole pages written,
    expanded attention among the new tokens, nothing read;
  * a prompt's tail at ``position_offset [1]``, behind pages that are
    already there: the new rows written, then the chain walked in blocks.

A model tells a prompt from a step by ``prompt`` (static: a program is
traced for one kind of view) and gives a prompt's last real position's
logits alone (``lengths - 1`` of the view's new tokens).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from pytorch_distributed_tpu.ops import latent_attention
from pytorch_distributed_tpu.ops import latent_paged_attention as paged
from pytorch_distributed_tpu.serving.paging.kv_cache import TRASH_PAGE

__all__ = ["PagedLatentCache"]


class PagedLatentCache(struct.PyTreeNode):
    """``rows [L, P, page, W]`` + ``block_tables [S, M]`` + ``lengths [S]``
    and ``step_stats``: what was counted while the model last ran over this
    cache (``STEP_STATS``; the engine sends it to the host in the read of
    the step's tokens)."""

    STEP_STATS = ("experts_hit", "experts_fill_pct", "experts_spill",
                  "latent_rows", "live_slots")

    rows: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    step_stats: jax.Array
    #: one chain's view for a prompt (``one_chain``)
    prompt: bool = struct.field(pytree_node=False, default=False)

    @classmethod
    def create(cls, cfg: Any, *, n_slots: int, max_len: int,
               page_size: int = 128, n_pages: int | None = None,
               dtype: Any = None) -> "PagedLatentCache":
        """Zero-filled pool for a config with ``n_layer``, ``kv_lora_rank``,
        ``qk_rope_head_dim``, ``n_positions``, ``dtype``; the geometry as
        ``PagedKVCache.create`` has it."""
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}")
        if n_slots < 1 or page_size < 1:
            raise ValueError("n_slots and page_size must be >= 1")
        max_pages = -(-max_len // page_size)
        if n_pages is None:
            n_pages = n_slots * max_pages + 1  # + trash page
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is the trash page)")
        width = latent_attention.row_width(cfg.kv_lora_rank,
                                           cfg.qk_rope_head_dim)
        return cls(
            rows=jnp.zeros((cfg.n_layer, n_pages, page_size, width),
                           dtype or cfg.dtype),
            block_tables=jnp.zeros((n_slots, max_pages), jnp.int32),
            lengths=jnp.zeros((n_slots,), jnp.int32),
            step_stats=jnp.zeros((len(cls.STEP_STATS),), jnp.int32),
        )

    @property
    def n_layers(self) -> int:
        return self.rows.shape[0]

    @property
    def n_pages(self) -> int:
        return self.rows.shape[1]

    @property
    def page_size(self) -> int:
        return self.rows.shape[2]

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
        L, _, page, width = self.rows.shape
        return L * page * width * self.rows.dtype.itemsize

    def placed(self, sharding) -> "PagedLatentCache":
        raise NotImplementedError(
            "a latent row is every head's: there is no head axis to lay "
            "over a mesh (ROADMAP: a tensor-parallel plan for latent "
            "attention)")

    def attend(self, layer: int, q, latent, kv_b, position_offset, *,
               scale: float):
        """Write the T new tokens' latents into ``layer``'s pages and attend
        over each chain: ``(y [B, T, H, d_v], cache)``; the operands are
        ``LatentCache.attend``'s, the three cases the module docstring's."""
        d_c = kv_b.shape[0]
        dims = dict(d_c=d_c, d_n=q.shape[-1] - (latent.shape[-1] - d_c),
                    scale=scale)
        if not self.prompt:
            with jax.named_scope("read_paged"):
                y, rows = paged.paged_read(
                    q, latent, kv_b, self.rows, self.block_tables, layer,
                    position_offset, kernel=paged.kernel_reads(self.rows),
                    **dims)
            return y, self.replace(rows=rows)
        if q.shape[0] != 1:
            raise ValueError("a prompt's view holds one chain")
        T, W = q.shape[1], self.rows.shape[3]
        new = jnp.pad(latent, ((0, 0), (0, 0), (0, W - latent.shape[-1]))
                      ).astype(self.rows.dtype)
        table = self.block_tables[0]
        if position_offset is None:
            page = self.page_size
            if T % page or T > self.max_len:
                raise ValueError(
                    f"a prompt from position 0 is written in whole pages of "
                    f"{page}, {self.max_pages} at the most: got a bucket of "
                    f"{T}")
            with jax.named_scope("prefill"):
                # what a later step will read back: the rows as stored
                y = paged.cold_prefill(q, new.astype(q.dtype), kv_b,
                                       n_real=self.lengths[0], **dims)
            rows = self.rows.at[layer, table[:T // page]].set(
                new.reshape(T // page, page, W))
            return y, self.replace(rows=rows)
        with jax.named_scope("tail"):
            pos = position_offset[:, None] + jnp.arange(T, dtype=jnp.int32)
            rows = paged.write_rows(self.rows, new, self.block_tables, pos,
                                    layer)
            y = paged.tail_prefill(q, kv_b, rows, table, layer,
                                   position_offset[0], self.lengths[0],
                                   **dims)
        return y, self.replace(rows=rows)

    def counted(self, **stats) -> "PagedLatentCache":
        """The cache with the step's counts set: the model's own
        (``experts_*``) and what a decode step must read: the rows of the
        live chains, ``lengths + 1`` a live slot a layer, and the live
        slots."""
        live = self.lengths > 0
        stats = dict(
            stats,
            latent_rows=self.n_layers
            * jnp.where(live, self.lengths + 1, 0).sum(),
            live_slots=live.sum())
        # a model that counts fewer (``models.xing4``) leaves zeros
        return self.replace(step_stats=jnp.stack(
            [jnp.asarray(stats.get(name, 0), jnp.int32)
             for name in self.STEP_STATS]))

    # -- a prompt into one chain -------------------------------------------
    def one_chain(self, slot, n_new) -> "PagedLatentCache":
        """The view a prompt (or its uncached tail) is prefilled through:
        the whole pool under ``slot``'s table row alone; ``lengths`` = how
        many of the tokens are real."""
        row = jax.lax.dynamic_slice_in_dim(self.block_tables, slot, 1, axis=0)
        return self.replace(block_tables=row, prompt=True,
                            lengths=jnp.full((1,), n_new, jnp.int32))

    def write_chain(self, slot, view: "PagedLatentCache", length
                    ) -> "PagedLatentCache":
        """The pool as ``view`` left it, ``lengths[slot] = length``."""
        return self.replace(rows=view.rows, step_stats=view.step_stats,
                            lengths=self.lengths.at[slot].set(length))

    def fork(self, src, dst) -> "PagedLatentCache":
        """Page ``src`` copied into ``dst`` in every layer (copy-on-write:
        ``serving.paging.fork_pages``)."""
        return self.replace(rows=self.rows.at[:, dst].set(self.rows[:, src]))

    # -- lifecycle, as ``PagedKVCache`` ------------------------------------
    def evict(self, slot) -> "PagedLatentCache":
        return self.replace(
            lengths=self.lengths.at[slot].set(0),
            block_tables=self.block_tables.at[slot].set(TRASH_PAGE))

    def set_table_row(self, slot, row) -> "PagedLatentCache":
        return self.replace(block_tables=self.block_tables.at[slot].set(
            jnp.asarray(row, jnp.int32)))

    def advance(self, n_tokens, active=None) -> "PagedLatentCache":
        n = jnp.asarray(n_tokens, jnp.int32)
        if active is not None:
            n = jnp.where(active, n, 0)
        return self.replace(lengths=self.lengths + n)

    def rollback(self, lengths) -> "PagedLatentCache":
        return self.replace(lengths=jnp.asarray(lengths, jnp.int32))
