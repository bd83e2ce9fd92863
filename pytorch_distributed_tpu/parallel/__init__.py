"""Parallelism strategies — the TPU-native DDP/FSDP/ZeRO/HSDP layer.

Capability parity: torch ``nn/parallel/distributed.py`` (DDP),
``distributed/fsdp/`` (FSDP1/2), ``distributed/optim/zero_redundancy_optimizer``
(ZeRO-1) and FSDP HYBRID_SHARD (SURVEY.md §2.2).

TPU-first design (SURVEY.md §7 "Design stance"): a strategy is not a module
wrapper — it is a *sharding assignment*. Under ``jit`` with
``NamedSharding``-annotated state, XLA inserts and overlaps the collectives:

  * DataParallel   — params replicated, batch sharded on ``dp``; XLA emits the
    gradient all-reduce (the DDP Reducer's job, SURVEY §3.3) during backward.
  * FullyShardedDataParallel — every param sharded on its largest divisible
    dim over ``fsdp``; XLA emits all-gather before use and reduce-scatter of
    grads (the FlatParameter unshard/reshard story, SURVEY §3.4), overlapped
    by the latency-hiding scheduler.
  * HybridShard    — shard over the inner (ICI) axis, replicate over the outer
    (DCN) axis: reduce-scatter rides ICI, residual all-reduce rides DCN.
  * ZeRO1          — params replicated, *optimizer state + weight update*
    sharded: grads are reduce-scattered, the optimizer steps on the 1/dp
    shard, updated params are all-gathered (``sharded_update.py``,
    arXiv 2004.13336) — all annotations inside the one fused step program.

Composition with TP/SP/CP/PP lives in the sibling modules (tensor_parallel,
context_parallel, pipeline).
"""

from pytorch_distributed_tpu.parallel.strategies import (
    DataParallel,
    FullyShardedDataParallel,
    HybridShard,
    NoShard,
    ShardingStrategy,
    ZeRO1,
    shard_spec_with_reason,
)
from pytorch_distributed_tpu.parallel.sharded_update import (
    apply_sharded_update,
    shard_grads,
    update_pspecs,
)
from pytorch_distributed_tpu.parallel.state import (
    TrainState,
    make_state_specs,
    make_state_shardings,
)
from pytorch_distributed_tpu.parallel.pipeline import (
    EagerPipelineExecutor,
    GPT2Pipe,
    PipelineParallel,
    Schedule1F1B,
    ScheduleDualPipeV,
    ScheduleGPipe,
    ScheduleInterleaved1F1B,
    ScheduleInterleavedZeroBubble,
    ScheduleLoopedBFS,
    ScheduleZBVZeroBubble,
    ScheduleZeroBubble,
    gpipe_spmd,
)

__all__ = [
    "ShardingStrategy",
    "NoShard",
    "DataParallel",
    "FullyShardedDataParallel",
    "HybridShard",
    "ZeRO1",
    "shard_spec_with_reason",
    "apply_sharded_update",
    "shard_grads",
    "update_pspecs",
    "TrainState",
    "make_state_specs",
    "make_state_shardings",
    "EagerPipelineExecutor",
    "GPT2Pipe",
    "PipelineParallel",
    "Schedule1F1B",
    "ScheduleDualPipeV",
    "ScheduleGPipe",
    "ScheduleInterleaved1F1B",
    "ScheduleInterleavedZeroBubble",
    "ScheduleLoopedBFS",
    "ScheduleZBVZeroBubble",
    "ScheduleZeroBubble",
    "gpipe_spmd",
]

from pytorch_distributed_tpu.parallel.expert import (  # noqa: F401,E402
    ExpertDataParallel,
    ExpertParallel,
    MoEMLP,
)

__all__ += ["ExpertDataParallel", "ExpertParallel", "MoEMLP"]
