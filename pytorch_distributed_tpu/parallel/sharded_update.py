"""ZeRO-sharded weight update, scheduled by the compiler.

The engine behind ``strategy.sharded_update`` (``ZeRO1``, FSDP). It is the
cross-replica sharded weight update of "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (arXiv 2004.13336), expressed the
SimpleFSDP way (arXiv 2411.00284): not a wrapper module, not a comm hook,
not an extra dispatch — three sharding annotations inside the step function
the trainer already jits with donation:

    grads      --with_sharding_constraint(update layout)-->   reduce-scatter
    opt step   runs on the 1/axis shard (state pinned sharded by the
               ``out_shardings`` the trainer derives from ``opt_pspec``)
    new params --with_sharding_constraint(param layout)-->    all-gather

XLA's SPMD partitioner lowers the first constraint to a reduce-scatter of
the gradients (subsuming the dp all-reduce), keeps the optimizer math on
1/dp-size operands, and lowers the last constraint to an all-gather of the
updated params; the latency-hiding scheduler overlaps both collectives with
neighboring compute. This recovers — declaratively — what the torch stack
builds by hand: ZeroRedundancyOptimizer's rank partitioning + broadcast,
FSDP's FlatParameter unshard/reshard, and the bucketed reduce-scatter comm
hook, while keeping ``AsyncRunner.programs_per_step`` at 1.

Everything here is pure spec/tracer plumbing: the helpers only read pytree
paths and ``.shape``, so they work identically on concrete arrays, jit
tracers, and ``jax.eval_shape`` outputs.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_tpu.parallel.state import _path_str
from pytorch_distributed_tpu.parallel.strategies import ShardingStrategy

__all__ = [
    "update_pspecs",
    "param_pspecs",
    "constrain",
    "shard_grads",
    "apply_sharded_update",
]


def update_pspecs(strategy: ShardingStrategy, params: Any) -> Any:
    """PartitionSpec tree (matching ``params``) of the weight-update layout.

    ``params`` may hold arrays, tracers, or ShapeDtypeStructs — only pytree
    paths and ``.shape`` are read.
    """
    return jtu.tree_map_with_path(
        lambda path, leaf: strategy.update_pspec(
            _path_str(path), tuple(leaf.shape)
        ),
        params,
    )


def param_pspecs(strategy: ShardingStrategy, params: Any) -> Any:
    """PartitionSpec tree of the resident parameter layout."""
    return jtu.tree_map_with_path(
        lambda path, leaf: strategy.param_pspec(
            _path_str(path), tuple(leaf.shape)
        ),
        params,
    )


def constrain(tree: Any, strategy: ShardingStrategy, pspecs: Any) -> Any:
    """Pin every leaf of ``tree`` to the matching spec on the strategy mesh.

    Inside jit this is ``lax.with_sharding_constraint`` — an annotation the
    partitioner must satisfy at that point of the program, i.e. where the
    reduce-scatter/all-gather lands.
    """
    mesh = strategy.mesh.jax_mesh

    def pin(x, spec):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jtu.tree_map(
        pin, tree, pspecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def shard_grads(strategy: ShardingStrategy, grads: Any) -> Any:
    """Constrain fresh gradients into the update layout.

    Placed immediately after grad computation so everything downstream —
    AMP unscale + finite check, global-norm clipping, the optimizer step —
    runs on the 1/axis shard. For ZeRO1 this is the point where SPMD turns
    the dp gradient all-reduce into a reduce-scatter.
    """
    return constrain(grads, strategy, update_pspecs(strategy, grads))


def apply_sharded_update(optimizer, strategy: ShardingStrategy, grads: Any,
                         opt_state: Any, params: Any):
    """Shard-local optimizer step; returns ``(new_params, new_opt_state)``.

    ``grads`` should already be in the update layout (``shard_grads``).
    The params view fed to the optimizer is constrained to the same layout
    so decoupled weight decay / trust-ratio style transforms read the 1/axis
    slice rather than gathering. The *update* (delta) — not the new params —
    is what gets gathered back to the resident ``param_pspec`` layout, and
    ``apply_updates`` then runs on the resident params: the exact ZeRO-1
    "broadcast the step" structure. Gathering the delta instead of the summed
    params keeps ``p + u`` outside the sharded fusion cluster, which is what
    makes the trace bit-exact against the unsharded update (gathering
    new_params instead leaves a 1-ulp fusion wobble on the CPU backend).
    Wire bytes are identical either way (delta and params are the same size).
    """
    import optax  # local: keep module import light for spec-only users

    upd_specs = update_pspecs(strategy, params)
    params_shard = constrain(params, strategy, upd_specs)
    updates, new_opt_state = optimizer.update(grads, opt_state, params_shard)
    updates = constrain(updates, strategy, param_pspecs(strategy, params))
    new_params = optax.apply_updates(params, updates)
    return new_params, new_opt_state
