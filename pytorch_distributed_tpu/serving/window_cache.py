"""A slotted K/V cache of two depths: whole rows for the layers that attend
every earlier position, a ring of ``window`` rows for those that attend
their last ``window`` only. The third class under the cache protocol's
names (``serving.kv_cache`` states it): ``create / placed / attend /
counted / one_slot / write_slot / evict / advance / rollback``, ``n_layers /
n_slots / max_len``.

A row is a token's K (or V) of one layer, its ``H_kv`` heads folded into the
minor dimension (``H_kv * D`` wide, not the model's width: 8 K/V heads of
128 under 64 query heads are 1,024 of 6,144). Keys are stored AFTER their
norm and rotation, so a row carries its position in itself and a ring's
order does not matter to the softmax.

    full layers    k  [L_full, S, max_len, H_full * D]    row of p at p
                   v  [L_full, S, max_len, H_full * D_v]
    window layers  k  [L_win,  S, window,  H_win * D]     p at p % window
                   v  [L_win,  S, window,  H_win * D_v]

Four widths: a kind of layer has its own number of K/V heads (``H_full``,
``H_win``: 4 and 8 under 64 query heads) and a V head may be narrower than
a K head (``D`` 192, ``D_v`` 128: rows of 768 / 512 and 1,536 / 1,024
columns). How a K head that is not whole lane tiles lies in its row is
``ops.gqa_attention.pack_keys``'s business; no column is padding.

Held as ``KVCache`` holds rows, five layers x 32 slots x 32,768 rows x 4 KB
would be 21.5 GB; by kind, one full layer is 4.29 GB and four rings 67 MB.

Which rows a query sees is decided from POSITIONS, never from what the
bytes are. A query at position p of a window layer sees the ``min(p + 1,
window)`` newest rows of its ring: rows ``0..p`` while ``p < window`` (the
slot's own prefill and decode steps wrote exactly those), every row after
(the ring has wrapped, each row overwritten by this occupant). So ``evict``
is a length reset, as on the other caches: a new occupant's rule makes a
previous occupant's ring rows unreachable until it has overwritten them.

What a ring cannot do: ``rollback`` to an earlier length cannot bring back
the rows that the rolled-back positions overwrote, so the engine refuses
speculative decoding with this class at construction, and ``attend`` takes
one new token a sequence (``serving.engine._slotted_cache_class``).

A fresh prefill (``position_offset=None``) reads nothing: the T new tokens
attend each other in blocks (``ops.gqa_attention.prefill_attention``: a
window layer only inside its band, a full layer by the Pallas kernel on a
TPU), every row goes into a full layer and
the prompt's last ``window`` rows into a ring. A decode step writes its row
and reads the rows its slot holds (``ops.gqa_attention.cached_read``: the
lengths-aware kernel on a TPU, the dense twin elsewhere; decided here from
the backend, as ``KVCache.attend`` does).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from pytorch_distributed_tpu.ops import gqa_attention

__all__ = ["WindowedKVCache"]


class WindowedKVCache(struct.PyTreeNode):
    """``k_full / v_full``, ``k_ring / v_ring`` (module docstring),
    ``lengths [S]``, and ``step_stats``: what was counted while the model
    last ran over this cache (``STEP_STATS``), which the engine sends to
    the host in the read of the step's tokens. ``windowed[l]`` says which
    kind layer ``l`` is (static: part of the tree's structure)."""

    STEP_STATS = ("experts_hit", "experts_fill_pct", "experts_spill",
                  "kv_full_rows", "kv_ring_rows")
    UNSUPPORTED_BECAUSE = (
        "a ring cannot give back rows a rollback would need, a paged pool "
        "whose window layers free pages behind the window and a "
        "tensor-parallel plan are ROADMAP items")

    k_full: jax.Array
    v_full: jax.Array
    k_ring: jax.Array
    v_ring: jax.Array
    lengths: jax.Array
    step_stats: jax.Array
    windowed: Tuple[bool, ...] = struct.field(pytree_node=False, default=())

    @classmethod
    def create(cls, cfg: Any, *, n_slots: int, max_len: int,
               dtype: Any = None) -> "WindowedKVCache":
        """Zero-filled cache for a config with ``layer_windowed``,
        ``sliding_window``, ``num_key_value_heads``, ``head_dim``,
        ``n_positions``, ``dtype`` and, where they are not those two,
        ``window_key_value_heads`` and ``v_head_dim``."""
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}")
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        windowed = tuple(cfg.layer_windowed)
        h_full = cfg.num_key_value_heads
        h_win = getattr(cfg, "window_key_value_heads", None) or h_full
        d_k = cfg.head_dim
        d_v = getattr(cfg, "v_head_dim", None) or d_k
        dtype = dtype or cfg.dtype
        n_win = sum(windowed)
        full = (len(windowed) - n_win, n_slots, max_len)
        ring = (n_win, n_slots, cfg.sliding_window)
        return cls(
            # an array each: a donated tree may not hold one buffer twice
            k_full=jnp.zeros(full + (h_full * d_k,), dtype),
            v_full=jnp.zeros(full + (h_full * d_v,), dtype),
            k_ring=jnp.zeros(ring + (h_win * d_k,), dtype),
            v_ring=jnp.zeros(ring + (h_win * d_v,), dtype),
            lengths=jnp.zeros((n_slots,), jnp.int32),
            step_stats=jnp.zeros((len(cls.STEP_STATS),), jnp.int32),
            windowed=windowed)

    @property
    def n_layers(self) -> int:
        return len(self.windowed)

    @property
    def n_slots(self) -> int:
        return self.k_full.shape[1]

    @property
    def max_len(self) -> int:
        return self.k_full.shape[2]

    @property
    def window(self) -> int:
        return self.k_ring.shape[2]

    def placed(self, sharding) -> "WindowedKVCache":
        raise NotImplementedError(
            "a cache of two depths lies whole on one device (ROADMAP: a "
            "tensor-parallel plan over the K/V heads)")

    def attend(self, layer: int, q, k_new, v_new, position_offset, *,
               sink=None):
        """Write the new tokens' K/V rows into ``layer`` and attend:
        ``(y [B, T, H_q, D_v], cache)``; ``q [B, T, H_q, D]``, ``k_new [B,
        T, H_kv, D]``, ``v_new [B, T, H_kv, D_v]`` (the layer's kind's
        ``H_kv``), batch row b is slot b. ``position_offset=None`` is the
        fresh prefill of ``lengths[b]`` real tokens (nothing read);
        otherwise one new token a sequence at ``position_offset [B]``.
        ``sink [H_q]`` joins the layer's softmax (``ops.gqa_attention``)."""
        B, T, Hq, D = q.shape
        windowed = self.windowed[layer]
        # this layer's place among the layers of its kind
        at = sum(w == windowed for w in self.windowed[:layer])
        k, v = ((self.k_ring, self.v_ring) if windowed
                else (self.k_full, self.v_full))
        depth = k.shape[2]
        k_rows = gqa_attention.pack_keys(k_new).astype(k.dtype)
        v_rows = v_new.reshape(B, T, -1).astype(v.dtype)
        if position_offset is None:
            y = gqa_attention.prefill_attention(
                q, k_new, v_new, window=depth if windowed else None,
                sink=sink, n_real=self.lengths[0] if B == 1 else None,
                kernel=gqa_attention.kernel_prefills(q, k_new, v_new))
            if windowed:
                # ring row r takes the newest real position p = r mod depth
                r = jnp.arange(depth, dtype=jnp.int32)[None]
                n = self.lengths[:, None]
                p = jnp.clip((n - 1 - r) // depth * depth + r, 0, T - 1)
                k_rows = jnp.take_along_axis(k_rows, p[..., None], axis=1)
                v_rows = jnp.take_along_axis(v_rows, p[..., None], axis=1)
                k, v = k.at[at].set(k_rows), v.at[at].set(v_rows)
            else:
                k = k.at[at, :, :T].set(k_rows)
                v = v.at[at, :, :T].set(v_rows)
        else:
            if T != 1:
                raise ValueError(
                    f"a {type(self).__name__} takes one new token a "
                    f"sequence (got {T}): a ring cannot roll back")
            slots = jnp.arange(B, dtype=jnp.int32)
            k = k.at[at, slots, position_offset % depth].set(k_rows[:, 0])
            v = v.at[at, slots, position_offset % depth].set(v_rows[:, 0])
            y = gqa_attention.cached_read(
                q[:, 0], k, v, at, position_offset + 1, sink=sink,
                kernel=gqa_attention.kernel_reads(k, D, v))[:, None]
        if windowed:
            return y, self.replace(k_ring=k, v_ring=v)
        return y, self.replace(k_full=k, v_full=v)

    def counted(self, **stats) -> "WindowedKVCache":
        """The cache with the step's counts set: the model's own
        (``experts_hit``, ``experts_fill_pct``, ``experts_spill``:
        ``models.exaone_moe.ExpertShare``) and the rows a decode step's
        reads held, summed over the layers of each kind: ``lengths + 1`` a
        live slot a full layer, at most ``window`` of them a ring."""
        live = self.lengths > 0
        rows = jnp.where(live, self.lengths + 1, 0)
        n_win = sum(self.windowed)
        stats = dict(
            stats,
            kv_full_rows=(self.n_layers - n_win) * rows.sum(),
            kv_ring_rows=n_win * jnp.minimum(rows, self.window).sum())
        return self.replace(step_stats=jnp.stack(
            [jnp.asarray(stats[name], jnp.int32)
             for name in self.STEP_STATS]))

    # -- prefill into one slot ---------------------------------------------
    def one_slot(self, n_positions: int, length=0) -> "WindowedKVCache":
        """A fresh one-slot cache whose full layers are ``n_positions``
        deep: what a prompt of ``length`` real tokens is prefilled into
        before ``write_slot`` lands it."""
        def rows(k, v, depth):
            """Zeros for one slot of ``k`` and of ``v``: one array where
            they are of one width (a traced block is donated by no one)."""
            k_rows = jnp.zeros((k.shape[0], 1, depth, k.shape[3]), k.dtype)
            if v.shape[3] == k.shape[3]:
                return k_rows, k_rows
            return k_rows, jnp.zeros(k_rows.shape[:3] + v.shape[3:], v.dtype)

        k_full, v_full = rows(self.k_full, self.v_full, n_positions)
        k_ring, v_ring = rows(self.k_ring, self.v_ring, self.window)
        return self.replace(
            k_full=k_full, v_full=v_full, k_ring=k_ring, v_ring=v_ring,
            lengths=jnp.full((1,), length, jnp.int32))

    def write_slot(self, slot, block: "WindowedKVCache", length
                   ) -> "WindowedKVCache":
        at = (0, slot, 0, 0)
        put = jax.lax.dynamic_update_slice
        return self.replace(
            k_full=put(self.k_full, block.k_full, at),
            v_full=put(self.v_full, block.v_full, at),
            k_ring=put(self.k_ring, block.k_ring, at),
            v_ring=put(self.v_ring, block.v_ring, at),
            lengths=self.lengths.at[slot].set(length),
            step_stats=block.step_stats,
        )

    def evict(self, slot) -> "WindowedKVCache":
        """Free a slot: a length reset (module docstring)."""
        return self.replace(lengths=self.lengths.at[slot].set(0))

    def advance(self, n_tokens, active=None) -> "WindowedKVCache":
        n = jnp.asarray(n_tokens, jnp.int32)
        if active is not None:
            n = jnp.where(active, n, 0)
        return self.replace(lengths=self.lengths + n)

    def rollback(self, lengths) -> "WindowedKVCache":
        """Reset per-slot lengths. Right only for lengths no ring row has
        been overwritten past (the engine never asks otherwise)."""
        return self.replace(lengths=jnp.asarray(lengths, jnp.int32))
