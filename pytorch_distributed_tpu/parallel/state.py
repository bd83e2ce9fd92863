"""Train state + sharding-spec derivation.

The GSPMD analog of torch's "wrap the module, the wrapper owns placement":
here placement is a *pytree of PartitionSpecs* computed once from the
strategy's rules and applied to the whole train state (params, optimizer
state, batch stats, scaler state) via ``NamedSharding``; jit keeps state
resident in that layout across steps.

Optimizer-state specs are derived structurally: optax states embed copies of
the param tree (e.g. Adam's ``mu``/``nu``), so each opt-state leaf is matched
to its parameter by path *suffix* and gets ``strategy.opt_pspec``; scalar
leaves (counts, schedules) replicate. This is the generic version of torch
FSDP's optimizer-state (de/re)sharding (``_optim_utils.py`` — SURVEY §2.5).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.tree_util as jtu
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_tpu.parallel.strategies import ShardingStrategy

P = PartitionSpec

__all__ = ["TrainState", "make_state_specs", "make_state_shardings"]


class TrainState(struct.PyTreeNode):
    """Complete training state — one pytree, one sharding assignment.

    Fields:
      step: global step counter (replicated scalar).
      params: model parameters.
      model_state: mutable collections (batch_stats, ...); {} if none.
      opt_state: optax optimizer state.
      scaler: loss-scaler state (amp.GradScalerState) or None.
    """

    step: jax.Array
    params: Any
    model_state: Any
    opt_state: Any
    scaler: Optional[Any] = None


def _path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jtu.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jtu.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jtu.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _params_path_table(params) -> dict:
    """Map full param path -> (path, shape)."""
    table = {}
    for path, leaf in jtu.tree_flatten_with_path(params)[0]:
        table[_path_str(path)] = tuple(leaf.shape)
    return table


def _suffix_match(path: str, table: dict) -> Optional[str]:
    """Longest param path that is a '/'-suffix of ``path``."""
    segs = path.split("/")
    for start in range(len(segs)):
        cand = "/".join(segs[start:])
        if cand in table:
            return cand
    return None


def make_state_specs(
    state_shapes: TrainState, strategy: ShardingStrategy
) -> TrainState:
    """PartitionSpec pytree matching a TrainState's structure.

    ``state_shapes`` is typically ``jax.eval_shape(init_fn, ...)`` output —
    no real arrays needed.
    """
    param_table = _params_path_table(state_shapes.params)

    def param_spec(path, leaf):
        return strategy.param_pspec(_path_str(path), tuple(leaf.shape))

    def model_state_spec(path, leaf):
        return strategy.model_state_pspec(_path_str(path), tuple(leaf.shape))

    def opt_spec(path, leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        match = _suffix_match(_path_str(path), param_table)
        if match is not None and param_table[match] == shape:
            return strategy.opt_pspec(match, shape)
        return P()

    def scalar_spec(path, leaf):
        return P()

    return TrainState(
        step=P(),
        params=jtu.tree_map_with_path(param_spec, state_shapes.params),
        model_state=jtu.tree_map_with_path(
            model_state_spec, state_shapes.model_state
        ),
        opt_state=jtu.tree_map_with_path(opt_spec, state_shapes.opt_state),
        scaler=(
            None
            if state_shapes.scaler is None
            else jtu.tree_map_with_path(scalar_spec, state_shapes.scaler)
        ),
    )


def make_state_shardings(
    state_shapes: TrainState, strategy: ShardingStrategy
) -> TrainState:
    """NamedSharding pytree (specs bound to the strategy's mesh)."""
    specs = make_state_specs(state_shapes, strategy)
    mesh = strategy.mesh.jax_mesh

    def bind(spec):
        return NamedSharding(mesh, spec)

    return jtu.tree_map(
        bind, specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
