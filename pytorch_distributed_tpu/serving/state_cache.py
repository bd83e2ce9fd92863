"""The slotted cache of a HYBRID stack: a fixed-size recurrent state a slot
for the layers that keep one, rows a position for the layers that attend.
The fourth class under the cache protocol's names (``serving.kv_cache``
states it): ``create / placed / attend / counted / one_slot / write_slot /
evict / advance / rollback``, ``n_layers / n_slots / max_len``.

By layer (``recurrent[l]`` says which kind layer ``l`` is):

    KDA layers   state [S, H, d, d] float32       one matrix a head a slot
                 tail  [S, K - 1, 3 * H * d]      the convolution's last inputs
    MLA layers   a ``LatentCache``'s rows [L_mla, S, max_len, 640], by
                 composition: its ``attend``, its lengths-aware read

A KDA layer's arrays are that layer's OWN buffers (a tuple over the layers,
not one stacked array): a decode step rewrites a layer's whole state, and a
donated buffer of its own is rewritten where it lies. The one ``lengths
[S]`` is the latent cache's.

ROWS AND STATES FAIL DIFFERENTLY. A row behind a slot's length is never
read (``serving.kv_cache``: masking is the isolation boundary), so the rows
of a previous occupant and of a prompt's PAD positions are harmless. A
state is read whole and has no positions to mask, so here:

  * admission OVERWRITES: a prompt is prefilled from a zero state into a
    one-slot block (``one_slot``) and ``write_slot`` lands the block's final
    state and tail over whatever the slot held; ``evict`` is a length reset
    as on the other caches, and what it leaves behind is never read;
  * a prompt's PAD positions do not touch the state (``ops.kda``: ``beta =
    0``, ``a = 1`` there) and the tail is taken at the last REAL position;
  * a decode step computes every slot's update (the batch is the slots) and
    DISCARDS it for the slots that are not live: ``where(live, new, old)``
    on the state and the tail, with ``live = lengths > 0``. Inside the
    update's own pass it costs no pass of its own, but an idle slot's state
    is read and written back like a live one's (3.2 GB a step at 128 slots of
    Kimi-Linear's sizes, whatever the occupancy); an indexed update of the
    live slots alone would need their indices as data and a gather and a
    scatter of 12.6 MB a slot in place of one elementwise pass.

What a state cannot do: go back. ``rollback`` to an earlier length cannot
undo the updates of the positions rolled back, so the engine refuses
speculative decoding with this class at construction, ``attend`` takes one
new token a sequence, and a prefix of a prompt cannot be served from
another request's pages (a state is not a function of a page but of every
token before it: a snapshot a page boundary is ROADMAP's).
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from pytorch_distributed_tpu.ops import kda, latent_attention
from pytorch_distributed_tpu.serving.kv_cache import LatentCache

__all__ = ["HybridStateCache"]


class HybridStateCache(struct.PyTreeNode):
    """``state``, ``tail`` (a tuple over the KDA layers each), ``latent``
    (the MLA layers' rows and the slots' lengths) and ``step_stats``: what
    was counted while the model last ran over this cache (``STEP_STATS``),
    which the engine sends to the host in the read of the step's tokens.
    ``recurrent[l]`` is static: part of the tree's structure."""

    STEP_STATS = ("experts_hit", "experts_fill_pct", "experts_spill",
                  "latent_rows", "live_slots", "state_kib")
    UNSUPPORTED_BECAUSE = (
        "a recurrent state cannot be rolled back, has no pages to share (a "
        "snapshot of it a page boundary is a ROADMAP item) and no "
        "tensor-parallel plan")

    state: Tuple[jax.Array, ...]
    tail: Tuple[jax.Array, ...]
    latent: LatentCache
    step_stats: jax.Array
    recurrent: Tuple[bool, ...] = struct.field(pytree_node=False, default=())

    @classmethod
    def create(cls, cfg: Any, *, n_slots: int, max_len: int,
               dtype: Any = None) -> "HybridStateCache":
        """Zero-filled cache for a config with ``layer_recurrent``,
        ``kda_num_heads``, ``kda_head_dim``, ``short_conv_kernel_size``,
        ``kv_lora_rank``, ``qk_rope_head_dim``, ``n_positions``, ``dtype``."""
        if max_len > cfg.n_positions:
            raise ValueError(
                f"max_len {max_len} exceeds model n_positions "
                f"{cfg.n_positions}")
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        recurrent = tuple(cfg.layer_recurrent)
        n_kda = sum(recurrent)
        dtype = dtype or cfg.dtype
        H, d = cfg.kda_num_heads, cfg.kda_head_dim
        width = latent_attention.row_width(cfg.kv_lora_rank,
                                           cfg.qk_rope_head_dim)
        return cls(
            # an array each: a donated tree may not hold one buffer twice
            state=tuple(jnp.zeros((n_slots, H, d, d), jnp.float32)
                        for _ in range(n_kda)),
            tail=tuple(jnp.zeros(
                (n_slots, cfg.short_conv_kernel_size - 1, 3 * H * d), dtype)
                for _ in range(n_kda)),
            latent=LatentCache(
                rows=jnp.zeros((len(recurrent) - n_kda, n_slots, max_len,
                                width), dtype),
                lengths=jnp.zeros((n_slots,), jnp.int32),
                step_stats=jnp.zeros((len(LatentCache.STEP_STATS),),
                                     jnp.int32)),
            step_stats=jnp.zeros((len(cls.STEP_STATS),), jnp.int32),
            recurrent=recurrent)

    @property
    def lengths(self) -> jax.Array:
        return self.latent.lengths

    @property
    def n_layers(self) -> int:
        return len(self.recurrent)

    @property
    def n_slots(self) -> int:
        return self.latent.n_slots

    @property
    def max_len(self) -> int:
        return self.latent.max_len

    def slot_state_bytes(self) -> int:
        """What a decode step must move for one live slot: every KDA
        layer's state read and written, its tail read."""
        return sum(2 * math.prod(s.shape[1:]) * s.dtype.itemsize
                   + math.prod(t.shape[1:]) * t.dtype.itemsize
                   for s, t in zip(self.state, self.tail))

    def placed(self, sharding) -> "HybridStateCache":
        raise NotImplementedError(
            "a hybrid cache lies whole on one device (ROADMAP: a "
            "tensor-parallel plan over the KDA heads)")

    def _at(self, layer: int) -> int:
        """``layer``'s place among the layers of its kind."""
        kind = self.recurrent[layer]
        return sum(r == kind for r in self.recurrent[:layer])

    def attend(self, layer: int, *operands, position_offset=None, **kw):
        """``layer``'s mixer over this cache: ``(y, cache)``, batch row b
        slot b; ``position_offset=None`` is the fresh prefill of
        ``lengths[b]`` real tokens, otherwise one new token a sequence.

        An MLA layer: ``attend(layer, q, latent, kv_b, position_offset=,
        scale=)``, ``LatentCache.attend``'s operands and result. A KDA
        layer: ``attend(layer, x, w_conv, log_a, beta, position_offset=)``,
        ``ops.kda.kda_mix``'s operands; ``y [B, T, H, d]`` float32. A
        prompt starts from a zero state and leaves its final state and the
        tail of its last real position; a decode step moves the live slots'
        state and tail and no others (module docstring)."""
        at = self._at(layer)
        if not self.recurrent[layer]:
            y, latent = self.latent.attend(
                at, *operands, position_offset, **kw)
            return y, self.replace(latent=latent)
        x, w_conv, log_a, beta = operands
        decode = position_offset is not None
        with jax.named_scope("pdt.kda.decode" if decode
                             else "pdt.kda.prefill"):
            y, state, tail = kda.kda_mix(
                x, w_conv, log_a, beta, self.state[at], self.tail[at],
                n_heads=self.state[at].shape[1], lengths=self.lengths,
                decode=decode)
            if decode:
                live = self.lengths > 0
                state = jnp.where(live[:, None, None, None], state,
                                  self.state[at])
                tail = jnp.where(live[:, None, None], tail, self.tail[at])
        return y, self.replace(
            state=self.state[:at] + (state,) + self.state[at + 1:],
            tail=self.tail[:at] + (tail,) + self.tail[at + 1:])

    def counted(self, **stats) -> "HybridStateCache":
        """The cache with the step's counts set: the model's own
        (``experts_hit``, ``experts_fill_pct``, ``experts_spill``) and what
        a decode step must move: the rows its reads hold, ``lengths + 1`` a
        live slot an MLA layer; the live slots, and their states' and tails'
        bytes (``slot_state_bytes``) in KiB (3.2e9 bytes at 128 live slots
        are past an int32)."""
        live = self.lengths > 0
        n_live = live.sum()
        stats = dict(
            stats,
            latent_rows=self.latent.n_layers
            * jnp.where(live, self.lengths + 1, 0).sum(),
            live_slots=n_live,
            state_kib=n_live * (self.slot_state_bytes() // 1024))
        return self.replace(step_stats=jnp.stack(
            [jnp.asarray(stats[name], jnp.int32)
             for name in self.STEP_STATS]))

    # -- prefill into one slot ---------------------------------------------
    def one_slot(self, n_positions: int, length=0) -> "HybridStateCache":
        """A fresh one-slot cache whose MLA layers are ``n_positions`` deep
        and whose states are zero: what a prompt of ``length`` real tokens
        is prefilled into before ``write_slot`` lands it."""
        return self.replace(
            state=tuple(jnp.zeros((1,) + s.shape[1:], s.dtype)
                        for s in self.state),
            tail=tuple(jnp.zeros((1,) + t.shape[1:], t.dtype)
                       for t in self.tail),
            latent=self.latent.one_slot(n_positions, length))

    def write_slot(self, slot, block: "HybridStateCache", length
                   ) -> "HybridStateCache":
        """``block`` over ``slot``: its rows from position 0, its states
        and tails WHOLE (whatever the slot held is gone)."""
        put = jax.lax.dynamic_update_slice
        return self.replace(
            state=tuple(put(s, b, (slot, 0, 0, 0))
                        for s, b in zip(self.state, block.state)),
            tail=tuple(put(t, b, (slot, 0, 0))
                       for t, b in zip(self.tail, block.tail)),
            latent=self.latent.write_slot(slot, block.latent, length),
            step_stats=block.step_stats)

    def evict(self, slot) -> "HybridStateCache":
        """Free a slot: a length reset (module docstring)."""
        return self.replace(latent=self.latent.evict(slot))

    def advance(self, n_tokens, active=None) -> "HybridStateCache":
        return self.replace(latent=self.latent.advance(n_tokens, active))

    def rollback(self, lengths) -> "HybridStateCache":
        """Reset per-slot lengths. Right only for the lengths the states
        are at (the engine never asks otherwise)."""
        return self.replace(latent=self.latent.rollback(lengths))
