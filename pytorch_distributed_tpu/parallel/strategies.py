"""Sharding strategies: param/optimizer/batch placement rules.

Each strategy answers five questions for a given mesh:
  * ``param_pspec(path, shape)``  — how a parameter is laid out
  * ``opt_pspec(path, shape)``    — how its optimizer-state companions are laid out
  * ``update_pspec(path, shape)`` — how the weight *update* is laid out when
    ``sharded_update`` is set (the ZeRO reduce-scatter → shard-local optimizer
    step → all-gather path, arXiv 2004.13336)
  * ``batch_axes``                — which mesh axes shard the batch dim
  * ``activation_pin(specs)``     — the layout activations are held to, where
    parameters and batch share a mesh axis (else None)

The FSDP rule ("shard the largest dim divisible by the axis size") is the
standard JAX/GSPMD fsdp recipe — the semantic twin of torch FlatParameter's
pad-to-divisible 1/world_size shard (``_flat_param.py:945`` per SURVEY §2.2),
expressed per-param so XLA can fuse the all-gather into consumers.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax.tree_util as jtu
from jax.sharding import PartitionSpec

from pytorch_distributed_tpu.mesh import DeviceMesh

P = PartitionSpec

__all__ = [
    "ShardingStrategy",
    "NoShard",
    "DataParallel",
    "FullyShardedDataParallel",
    "HybridShard",
    "ZeRO1",
    "shard_spec_with_reason",
]

#: why ``shard_spec_with_reason`` replicated (or didn't) a given shape
SHARD_REASONS = ("sharded", "scalar", "trivial_axis", "small", "indivisible")


def shard_spec_with_reason(
    shape: Tuple[int, ...], axis_name: str, axis_size: int, min_size: int
) -> Tuple[PartitionSpec, str]:
    """(spec, reason) for the largest-divisible-dim rule.

    The spec shards the largest dim divisible by ``axis_size``; ties break
    toward the *first* such dim so the choice (and therefore the jit cache
    key) is deterministic. Every replication fallback is named so callers —
    the memory probe in particular — can report them instead of silently
    eating the memory win:

      * ``scalar``        rank-0 params have no dim to shard
      * ``trivial_axis``  ``axis_size <= 1``: sharding would be a no-op
        annotation, and GSPMD rejects unknown/degenerate layouts earlier
        than a replicated spec would
      * ``small``         fewer than ``min_size`` elements — the analog of
        DDP's small-first-bucket / FSDP's min wrap size
      * ``indivisible``   no dim is a positive multiple of ``axis_size``
        (covers zero-size dims too: an 8-way shard of 0 rows is legal but
        meaningless, so it stays replicated)
    """
    shape = tuple(shape)
    if not shape:
        return P(), "scalar"
    if axis_size <= 1:
        return P(), "trivial_axis"
    n = 1
    for s in shape:
        n *= s
    if n < min_size:
        return P(), "small"
    best = None
    for i, s in enumerate(shape):
        if s > 0 and s % axis_size == 0:
            if best is None or s > shape[best]:
                best = i
    if best is None:
        return P(), "indivisible"
    spec: list = [None] * len(shape)
    spec[best] = axis_name
    return P(*spec), "sharded"


def _shard_largest_divisible_dim(
    shape: Tuple[int, ...], axis_name: str, axis_size: int, min_size: int
) -> PartitionSpec:
    """Spec sharding the largest dim divisible by ``axis_size`` (else
    replicate); see ``shard_spec_with_reason`` for the named fallbacks."""
    return shard_spec_with_reason(shape, axis_name, axis_size, min_size)[0]


class ShardingStrategy:
    """Base: everything replicated, batch sharded on nothing."""

    #: mesh axes that shard the global batch dim (None → replicated input)
    batch_axes: Union[str, Tuple[str, ...], None] = None

    #: when True the trainer routes the optimizer step through the
    #: sharded-update engine (``parallel.sharded_update``): grads are
    #: constrained into the ``update_pspec`` layout (lowered by SPMD to a
    #: reduce-scatter), the optimizer runs on that 1/axis shard, and the
    #: updated params are constrained back to ``param_pspec`` (the
    #: all-gather) — all inside the ONE fused donated step program.
    sharded_update: bool = False

    def __init__(self, mesh: DeviceMesh):
        self.mesh = mesh

    # -- placement rules --------------------------------------------------
    def param_pspec(self, path: str, shape: Tuple[int, ...]) -> PartitionSpec:
        return P()

    def opt_pspec(self, path: str, shape: Tuple[int, ...]) -> PartitionSpec:
        # by default optimizer state follows its parameter
        return self.param_pspec(path, shape)

    def update_pspec(self, path: str, shape: Tuple[int, ...]) -> PartitionSpec:
        """Layout of a parameter's gradient + weight update inside the
        sharded optimizer step. Defaults to the param layout: for FSDP that
        already IS the 1/fsdp shard; ZeRO1 overrides it to the opt-state
        layout so replicated params still get a 1/dp update."""
        return self.param_pspec(path, shape)

    def model_state_pspec(self, path: str, shape) -> PartitionSpec:
        # batch_stats etc. are small; replicate
        return P()

    def batch_pspec(self) -> PartitionSpec:
        if self.batch_axes is None:
            return P()
        return P(self.batch_axes)

    def _batch_axes(self) -> Tuple[str, ...]:
        if self.batch_axes is None:
            return ()
        if isinstance(self.batch_axes, str):
            return (self.batch_axes,)
        return tuple(self.batch_axes)

    def activation_pin(self, param_pspecs: Any) -> Optional[PartitionSpec]:
        """The layout that goes with ``batch_pspec()`` for an activation
        whose leading dimension is the batch's (that dimension on
        ``batch_axes``, the rest unsharded), or None where there is
        nothing to pin. ``param_pspecs`` is the tree of ``param_pspec``
        results for the model at hand.

        It is a layout exactly when some parameter is sharded over a mesh
        axis (larger than 1) that the batch is sharded over too. Only then
        does the partitioner have a choice to make at each ``x @ W``:
        un-shard the parameter (FSDP) or un-shard the activation and
        compute a shard of the output features, which is tensor
        parallelism nobody asked for. Holding the activations to the batch
        layout leaves it the first. Everywhere else (replicated
        parameters; parameters on an axis of their own, as under TP, PP
        or EP; any axis of size 1) the batch's own constraint decides
        alone, and nothing is emitted."""
        shared = {a for a in self._batch_axes() if self.mesh.size(a) > 1}
        if not shared:
            return None
        specs = jtu.tree_leaves(
            param_pspecs, is_leaf=lambda s: isinstance(s, PartitionSpec)
        )
        used = {
            axis
            for spec in specs for entry in spec if entry is not None
            for axis in ((entry,) if isinstance(entry, str) else entry)
        }
        return self.batch_pspec() if shared & used else None

    @property
    def data_shard_count(self) -> int:
        """Number of data shards (the 'world size' for the sampler)."""
        n = 1
        for a in self._batch_axes():
            n *= self.mesh.size(a)
        return n

    def describe(self) -> str:
        return f"{type(self).__name__}(mesh={self.mesh!r})"

    def collective_signature(self) -> dict:
        """Structural contract on the compiled train step's tensor-grade
        collective set — what graftir (``analysis/ir``) asserts against
        the optimized HLO. Keys:

        * ``grad_reduce`` — a tensor-grade gradient reduction must
          appear. Checked as an op *family* (all-reduce OR
          reduce-scatter): the spelling is the partitioner's choice and
          CPU's HLO pipeline expands reduce-scatter into
          all-reduce(+slice).
        * ``param_gather`` — ``"none"`` (tensor all-gathers are
          forbidden: pure DP keeps params replicated end to end),
          ``"delta"`` (ZeRO1 sharded update: gathers total exactly the
          sharded-update leaves' bytes, each gather at most one leaf —
          never a monolithic full-param gather), or ``"per_param"``
          (FSDP: gathers present, none approaching the monolithic
          whole-model gather a FlatParameter design would emit).
        * ``forbid`` — families that have no business in a data-parallel
          train step at all.
        * ``activations`` — ``"local"`` where the strategy pins the
          activations to the batch layout (``activation_pin``): every
          tensor-grade collective then moves a parameter, a gradient or
          a shard of one, and NONE an activation. On the TPU this is
          held for every family, the ring steps (collective-permute) of
          a reduce-scatter included, bar the row exchange of a sharded
          embedding table (one all-to-all of the looked-up rows each
          way and the gather of the token ids: 10 MB where the table's
          gather is 129, ``tests/test_chip_compile.py``).
        """
        return {
            "grad_reduce": False,
            "param_gather": "none",
            "forbid": ("all-to-all", "collective-permute"),
        }


class NoShard(ShardingStrategy):
    """Single-device / fully replicated debug strategy (torch
    ``ShardingStrategy.NO_SHARD`` — SURVEY §2.2 FSDP api.py:32-68)."""


class DataParallel(ShardingStrategy):
    """DDP semantics: replicated params, dp-sharded batch (SURVEY §3.3).

    XLA's gradient all-reduce is emitted where torch's bucketed Reducer ran;
    overlap with backward is the latency-hiding scheduler's job.
    """

    def __init__(self, mesh: DeviceMesh, dp_axis: str = "dp"):
        super().__init__(mesh)
        if dp_axis not in mesh.axis_names:
            raise ValueError(f"axis {dp_axis!r} not in mesh {mesh.axis_names}")
        self.dp_axis = dp_axis
        self.batch_axes = dp_axis

    def collective_signature(self) -> dict:
        sig = super().collective_signature()
        sig["grad_reduce"] = True
        return sig


class FullyShardedDataParallel(ShardingStrategy):
    """FSDP FULL_SHARD semantics: params + grads + opt state sharded over
    ``fsdp``; batch also sharded over ``fsdp`` (each shard-rank sees its own
    data, as in torch FSDP where FSDP ranks are also DP ranks).

    SimpleFSDP-style (arXiv 2411.00284) parameter-as-sharded-computation:
    there is no FlatParameter, no unshard/reshard bookkeeping, no bucketed
    comm hook — the sharded ``param_pspec`` annotations are the whole
    mechanism. XLA's SPMD partitioner inserts the forward/backward
    all-gathers and the gradient reduce-scatter, and the latency-hiding
    scheduler overlaps them with compute. ``sharded_update`` pins the
    optimizer step to the same 1/fsdp layout (``update_pspec`` defaults to
    ``param_pspec``), so grads/opt-state/update all stay sharded.

    What makes the partitioner gather the PARAMETER at each use is
    ``activation_pin``: batch and parameters share the ``fsdp`` axis, so
    at every ``x[B/n, T, C] @ W[C, N/n]`` one operand must be un-sharded,
    and the largest-divisible-dim rule lays ``W`` out exactly as
    Megatron's column- and row-parallel layers would. Left to its cost
    model the partitioner gathers the activation and runs tensor
    parallelism (PERF.md, PR 29: 139 ms of exposed collectives in a 470 ms
    step on four v5e chips). The trainer therefore holds the model's
    activations to the batch layout while it traces (``mesh.pin_activation``
    at the model's hook sites); where the compiler then places each
    gather, and how far ahead, stays its own decision.

    ``min_shard_size`` keeps tiny params replicated (wrap-policy analog).
    Optionally composes an extra pure-DP axis: ``batch_axes=('dp','fsdp')``
    when the mesh has both.
    """

    sharded_update = True

    def __init__(
        self,
        mesh: DeviceMesh,
        fsdp_axis: str = "fsdp",
        *,
        dp_axis: Optional[str] = None,
        min_shard_size: int = 1024,
    ):
        super().__init__(mesh)
        if fsdp_axis not in mesh.axis_names:
            raise ValueError(f"axis {fsdp_axis!r} not in mesh {mesh.axis_names}")
        if dp_axis is not None and dp_axis not in mesh.axis_names:
            raise ValueError(f"axis {dp_axis!r} not in mesh {mesh.axis_names}")
        self.fsdp_axis = fsdp_axis
        self.dp_axis = dp_axis
        self.min_shard_size = min_shard_size
        self.batch_axes = (
            (dp_axis, fsdp_axis) if dp_axis is not None else fsdp_axis
        )

    def param_pspec(self, path: str, shape) -> PartitionSpec:
        return _shard_largest_divisible_dim(
            tuple(shape),
            self.fsdp_axis,
            self.mesh.size(self.fsdp_axis),
            self.min_shard_size,
        )

    def collective_signature(self) -> dict:
        sig = super().collective_signature()
        sig["grad_reduce"] = True
        sig["param_gather"] = "per_param"
        sig["activations"] = "local"
        return sig


class HybridShard(FullyShardedDataParallel):
    """HSDP (torch FSDP ``HYBRID_SHARD`` — SURVEY §2.2): shard params over the
    inner ICI axis, replicate over the outer DCN axis; the batch is sharded
    over both (every device sees distinct data). Use with a mesh from
    ``init_hybrid_mesh((per_slice,), (n_slices,), ('dcn', 'fsdp'))``.
    """

    def __init__(
        self,
        mesh: DeviceMesh,
        fsdp_axis: str = "fsdp",
        dcn_axis: str = "dcn",
        *,
        min_shard_size: int = 1024,
    ):
        if dcn_axis not in mesh.axis_names:
            raise ValueError(f"axis {dcn_axis!r} not in mesh {mesh.axis_names}")
        super().__init__(
            mesh, fsdp_axis, dp_axis=dcn_axis, min_shard_size=min_shard_size
        )
        self.dcn_axis = dcn_axis


class ZeRO1(DataParallel):
    """ZeRO stage 1 (torch ``ZeroRedundancyOptimizer`` — SURVEY §2.2):
    replicated params/grads in the forward/backward, optimizer state AND
    the weight update sharded over the dp axis.

    This is the full cross-replica sharded weight update of arXiv
    2004.13336: the trainer constrains grads into the 1/dp
    ``update_pspec`` layout (SPMD lowers the dp all-reduce into a
    reduce-scatter), the optimizer step runs on the shard next to its
    sharded state, and the updated params are constrained back to
    replicated (the all-gather) — the torch rank-partitioned step +
    broadcast, without the hand-written partitioning cache, and without
    leaving the one fused step program.
    """

    sharded_update = True

    def __init__(
        self,
        mesh: DeviceMesh,
        dp_axis: str = "dp",
        *,
        min_shard_size: int = 1024,
    ):
        super().__init__(mesh, dp_axis)
        self.min_shard_size = min_shard_size

    def opt_pspec(self, path: str, shape) -> PartitionSpec:
        return _shard_largest_divisible_dim(
            tuple(shape), self.dp_axis, self.mesh.size(self.dp_axis),
            self.min_shard_size,
        )

    def update_pspec(self, path: str, shape) -> PartitionSpec:
        # grads + update live where the optimizer state lives
        return self.opt_pspec(path, shape)

    def collective_signature(self) -> dict:
        sig = super().collective_signature()
        # the delta all-gather of arXiv 2004.13336: per sharded-update
        # leaf, full-param bytes — never one monolithic gather
        sig["param_gather"] = "delta"
        return sig
