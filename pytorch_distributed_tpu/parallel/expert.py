"""Expert parallelism — MoE layer + EP sharding rules.

Capability parity (SURVEY.md §2.2 "EP"): the reference stack has only the
primitive (``all_to_all_single``); the survey's build note asks for EP as a
first-class mesh axis with all-to-all dispatch, so this module provides:

  * :class:`MoEMLP` — a Switch/GShard-style top-k routed expert MLP (flax)
    with capacity-factor truncation and load-balancing auxiliary loss;
  * :class:`ExpertParallel` style for the TP plan engine — expert-stacked
    params shard their leading [E] dim over the ``ep`` mesh axis.

TPU-first: dispatch/combine are dense einsums with a one-hot dispatch mask
(static shapes, MXU-friendly); when expert params are sharded on ``ep`` and
tokens on the data axes, XLA lowers the dispatch contraction to the
all-to-all over ICI — the same communication the reference's
``all_to_all_single`` performs, but fused and overlapped by the compiler.

Scalability: the dispatch mask is [n, E, capacity] per *group* — tokens are
routed within fixed-size groups (``group_size``), the Switch/GShard TPU
recipe, so mask memory is linear in total tokens instead of quadratic.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from pytorch_distributed_tpu.parallel.strategies import ShardingStrategy
from pytorch_distributed_tpu.parallel.tensor_parallel import ParallelStyle

P = PartitionSpec

__all__ = ["MoEMLP", "ExpertParallel", "ExpertDataParallel", "make_dispatch_masks"]


def make_dispatch_masks(expert_idx, gate_vals, n_experts: int, capacity: int,
                        dtype=jnp.float32):
    """Build dispatch/combine masks from top-k routing decisions.

    Args:
      expert_idx: [G, n, k] int — expert chosen per token per slot.
      gate_vals:  [G, n, k] float — router prob of that expert.
      n_experts, capacity: static sizes.

    Returns:
      dispatch [G, n, E, capacity] (0/1 in ``dtype``) and combine
      [G, n, E, capacity] (gate-weighted, fp32).

    Queue positions are computed JOINTLY over all k slots, slot-major: all
    slot-0 (top-1) assignments claim expert capacity before any slot-1
    assignment, and no two (token, slot) assignments to the same expert
    share an (expert, position) cell. (Round-1 bug: an independent cumsum
    per slot collided slots in the same cell, silently summing two tokens'
    embeddings — ADVICE.md round 1, high severity.)
    """
    G, n, k = expert_idx.shape
    E = n_experts
    e_sm = jnp.swapaxes(expert_idx, 1, 2).reshape(G, k * n)  # slot-major
    onehot = jax.nn.one_hot(e_sm, E)  # [G, k*n, E]
    pos = (jnp.cumsum(onehot, axis=1) - onehot) * onehot
    pos_in_e = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [G, k*n]
    keep = pos_in_e < capacity
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos_in_e, capacity), capacity + 1
    )[..., :capacity]  # overflow slot dropped
    d = onehot[..., None] * pos_oh[..., None, :]  # [G, k*n, E, cap]
    d = d.reshape(G, k, n, E, capacity)
    dispatch = d.sum(axis=1).astype(dtype)  # [G, n, E, cap]
    gates_sm = jnp.swapaxes(gate_vals, 1, 2)  # [G, k, n]
    combine = jnp.einsum("gksec,gks->gsec", d, gates_sm)
    return dispatch, combine


class ExpertParallel(ParallelStyle):
    """Shard the leading expert dim [E, ...] over the ep axis."""

    def param_pspec(self, shape, ep_axis):
        if not shape:
            return P()
        spec = [None] * len(shape)
        spec[0] = ep_axis
        return P(*spec)


class MoEMLP(nn.Module):
    """Top-k routed mixture-of-experts MLP (Switch transformer shape).

    Input [B, T, C] → router picks top-k of E experts per token; tokens are
    dispatched up to a per-expert capacity, processed by the expert MLPs,
    and combined weighted by router probs. Returns (out [B, T, C], aux)
    where aux carries the load-balancing loss (add to the task loss scaled
    by ``aux_weight`` at the call site).
    """

    n_experts: int
    d_ff: int
    k: int = 1
    capacity_factor: float = 1.25
    group_size: Optional[int] = None  # tokens per routing group; None = all
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, dict]:
        B, T, C = x.shape
        E, k = self.n_experts, self.k
        n_tokens = B * T
        gsz = self.group_size or n_tokens
        if n_tokens % gsz:
            raise ValueError(
                f"group_size {gsz} must divide token count {n_tokens}"
            )
        G = n_tokens // gsz
        capacity = max(1, int(self.capacity_factor * gsz * k / E))

        xg = x.reshape(G, gsz, C)
        router = nn.Dense(E, dtype=jnp.float32, param_dtype=self.param_dtype,
                          name="router")
        logits = router(xg.astype(jnp.float32))  # [G, n, E]
        probs = jax.nn.softmax(logits, axis=-1)

        # top-k selection per token
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [G, n, k]

        dispatch, combine = make_dispatch_masks(
            expert_idx, gate_vals, E, capacity, self.dtype
        )

        # dispatch tokens: [G, E, capacity, C] — the EP all-to-all contraction
        expert_in = jnp.einsum(
            "gnec,gnd->gecd", dispatch, xg.astype(self.dtype)
        )

        # expert MLPs: stacked params [E, ...] (shard dim 0 over 'ep')
        w_up = self.param(
            "experts_up", nn.initializers.lecun_normal(),
            (E, C, self.d_ff), self.param_dtype,
        )
        w_dn = self.param(
            "experts_down", nn.initializers.lecun_normal(),
            (E, self.d_ff, C), self.param_dtype,
        )
        h = jnp.einsum("gecd,edf->gecf", expert_in, w_up.astype(self.dtype))
        h = nn.gelu(h, approximate=True)
        expert_out = jnp.einsum("gecf,efd->gecd", h, w_dn.astype(self.dtype))

        # combine back: [G, n, C]
        out = jnp.einsum(
            "gnec,gecd->gnd", combine.astype(self.dtype), expert_out
        )

        # Switch load-balancing aux loss: E * sum_e frac_tokens_e * mean_prob_e
        flat_probs = probs.reshape(n_tokens, E)
        me = jnp.mean(flat_probs, axis=0)  # [E]
        top1 = jax.nn.one_hot(expert_idx[..., 0].reshape(-1), E)
        ce = jnp.mean(top1, axis=0)  # fraction routed (top-1)
        aux_loss = E * jnp.sum(me * ce)

        return out.reshape(B, T, C), {
            "aux_loss": aux_loss,
            "expert_fraction": ce,
        }


class ExpertDataParallel(ShardingStrategy):
    """Trainer strategy: DDP over ``dp`` + expert params sharded over
    ``ep`` (the first-class EP mesh axis of SURVEY §2.2's build note).
    Non-expert params replicate (DDP); any param whose path contains
    ``expert_key`` shards its leading [E] dim on ``ep`` — with tokens on
    the data axes, XLA lowers the dispatch einsum to the all-to-all the
    reference performs with ``all_to_all_single``.
    """

    def __init__(self, mesh, dp_axis: str = "dp", ep_axis: str = "ep",
                 expert_key: str = "experts"):
        super().__init__(mesh)
        if dp_axis not in mesh.axis_names:
            raise ValueError(f"axis {dp_axis!r} not in mesh {mesh.axis_names}")
        self.dp_axis = dp_axis
        self.ep_axis = ep_axis
        self.expert_key = expert_key
        self.batch_axes = dp_axis

    def param_pspec(self, path: str, shape):
        if self.expert_key in path:
            return P(self.ep_axis)
        return P()

    def describe(self) -> str:
        return (f"ExpertDataParallel(dp={self.dp_axis!r}, "
                f"ep={self.ep_axis!r})")
