"""Train→serve bridge: TP-sharded inference weights from training checkpoints.

Training saves a full TrainState (params + optimizer moments) on whatever
mesh the trainer ran — FSDP over 8 hosts, DP×TP, single host. Serving wants
something else entirely: just the params, laid out Megatron-TP over a
``(dp, tp)`` serving mesh sized for latency, not throughput. This module
glues the two with the checkpoint layer's reshard-on-load:

  1. ``serving_mesh`` builds the inference mesh (tp innermost → ICI).
  2. ``gpt2_param_shardings`` derives per-param NamedShardings from the
     canonical ``gpt2_tp_plan`` (same plan engine the trainer uses, so
     serving layout and training TP layout can never drift apart).
  3. ``load_gpt2_params`` partial-restores ONLY the params subtree from a
     CheckpointManager directory, each leaf landing directly sharded on the
     serving mesh — the optimizer state (2-3x the params bytes) is never
     read off disk, and no host ever materializes a full replica.

The KV cache shards on the HEAD dim (``kv_cache_sharding``): colwise
``c_attn`` emits head-sharded K/V, the slotted cache folds the heads into
its minor ``H*D`` dimension and splits that over tp (whole heads per device
when tp divides H), and rowwise ``c_proj`` closes the block with its
all-reduce. Cached attention contracts against the folded rows
(``ops.decode_attention``), so under tp the scores of the slotted path are
summed across the shards as well (every head is non-zero on one shard).

orbax is imported inside functions only: ``import
pytorch_distributed_tpu.serving`` stays dependency-light.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.mesh import DeviceMesh, init_device_mesh
from pytorch_distributed_tpu.parallel.state import _path_str
from pytorch_distributed_tpu.parallel.tensor_parallel import (
    TensorParallel,
    gpt2_tp_plan,
)

__all__ = [
    "serving_mesh",
    "gpt2_params_template",
    "gpt2_param_shardings",
    "draft_param_shardings",
    "kv_cache_sharding",
    "paged_kv_cache_sharding",
    "load_gpt2_params",
    "reshard_gpt2_params",
]


def serving_mesh(
    *, dp: int = 1, tp: int = -1, devices: Optional[Any] = None
) -> DeviceMesh:
    """``(dp, tp)`` inference mesh; tp innermost (ICI-adjacent), ``-1``
    infers an axis from the device count."""
    return init_device_mesh((dp, tp), ("dp", "tp"), devices=devices)


def gpt2_params_template(model) -> Any:
    """Abstract params pytree (ShapeDtypeStructs) for ``model`` — the
    structure/shape template that reshard-on-load targets. Zero FLOPs."""
    t = min(8, model.cfg.n_positions)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, t), jnp.int32)
        )
    )
    return variables["params"]


def gpt2_param_shardings(
    template,
    mesh: DeviceMesh,
    *,
    tp_axis: str = "tp",
    dp_axis: Optional[str] = "dp",
) -> Any:
    """NamedSharding per param leaf from the canonical Megatron plan.

    ``template`` is a params pytree (arrays or ShapeDtypeStructs, e.g. from
    :func:`gpt2_params_template`). Params are sharded on tp only — the dp
    axis replicates weights (pure inference data parallelism).
    """
    strategy = TensorParallel(
        mesh, gpt2_tp_plan(), tp_axis=tp_axis, dp_axis=dp_axis
    )

    def to_sharding(path, leaf):
        spec = strategy.param_pspec(_path_str(path), tuple(leaf.shape))
        return NamedSharding(mesh.jax_mesh, spec)

    return jax.tree_util.tree_map_with_path(to_sharding, template)


def draft_param_shardings(
    draft_model,
    mesh: DeviceMesh,
    *,
    tp_axis: str = "tp",
    dp_axis: Optional[str] = "dp",
) -> Any:
    """TP placement for a separate speculative-decoding draft model.

    The draft is a plain (smaller) GPT-2, so the SAME Megatron plan
    applies: colwise ``c_attn``/``c_fc``, rowwise ``c_proj``, replicated
    norms — and the draft's head-sharded K/V cache reuses
    :func:`kv_cache_sharding` unchanged. Sharding the draft on the same
    mesh keeps the draft+verify round entirely on-device: no host hop, no
    resharding between the k draft forwards and the verify forward.
    """
    return gpt2_param_shardings(
        gpt2_params_template(draft_model), mesh,
        tp_axis=tp_axis, dp_axis=dp_axis,
    )


def kv_cache_sharding(
    mesh: DeviceMesh, *, tp_axis: str = "tp", dp_axis: Optional[str] = None
) -> NamedSharding:
    """Layout for the ``[L, S, T, H*D]`` K/V arrays: the folded head dim on
    tp (matching the colwise c_attn that writes them; tp must divide H so
    that no head straddles two devices); optionally slots on dp."""
    return NamedSharding(
        mesh.jax_mesh, P(None, dp_axis, None, tp_axis)
    )


def paged_kv_cache_sharding(
    mesh: DeviceMesh, *, tp_axis: str = "tp"
) -> NamedSharding:
    """Layout for the paged ``[L, n_pages, page_size, H, D]`` pools: heads
    on tp, exactly like the slotted cache — the page pool is shared by all
    sequences, so there is no slot dim to put on dp; every device holds its
    head-shard of every page and the block tables replicate (they are tiny
    int32 and the host rewrites them each admission)."""
    return NamedSharding(
        mesh.jax_mesh, P(None, None, None, tp_axis, None)
    )


def load_gpt2_params(
    ckpt_dir: str,
    model,
    mesh: Optional[DeviceMesh] = None,
    *,
    step: Optional[int] = None,
    tp_axis: str = "tp",
    dp_axis: Optional[str] = "dp",
) -> Any:
    """Load serving weights from a training checkpoint directory.

    Returns the full variables dict (``{"params": ...}``) ready for
    ``InferenceEngine``; with a mesh, every leaf arrives TP-sharded on it
    (reshard-on-load — no full-replica staging), else host-local. Leaves
    the checkpoint layer cannot slice-read onto the serving topology are
    moved there by the ``redistribute/`` planner (bounded peak memory).
    """
    from pytorch_distributed_tpu.checkpoint import load_params

    template = gpt2_params_template(model)
    shardings = None
    if mesh is not None:
        shardings = gpt2_param_shardings(
            template, mesh, tp_axis=tp_axis, dp_axis=dp_axis
        )
    params = load_params(ckpt_dir, template, step=step, shardings=shardings)
    return {"params": params}


def reshard_gpt2_params(
    variables: Any,
    mesh: DeviceMesh,
    *,
    tp_axis: str = "tp",
    dp_axis: Optional[str] = "dp",
    max_staging_bytes: Optional[int] = None,
) -> Any:
    """Move LIVE weights (any mesh/layout, or host numpy) onto ``mesh``.

    The in-memory counterpart of :func:`load_gpt2_params`: same canonical
    Megatron placement, but the source is a params pytree already in hand —
    a trainer's FSDP state, another pod's serving layout, a host-loaded
    file. Every leaf goes through one planned transfer from the
    ``redistribute/`` engine (all-gather / all-to-all / dynamic-slice /
    device_put, peak = src shard + dst shard — never gather-then-slice).

    Takes and returns the full variables dict (``{"params": ...}``).
    """
    from pytorch_distributed_tpu.redistribute import redistribute_tree

    params = variables["params"]
    shardings = gpt2_param_shardings(
        params, mesh, tp_axis=tp_axis, dp_axis=dp_axis
    )
    params = redistribute_tree(
        params, shardings, max_staging_bytes=max_staging_bytes
    )
    return dict(variables, params=params)
