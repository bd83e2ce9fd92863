"""graftir program registry: the repo's own step programs as auditable
closures.

Each :class:`StepProgram` is one (strategy × AMP policy) train step over
the probe MLP, built on a real mesh over however many devices the
platform exposes — on CPU
the CLI provisions virtual host devices, so the whole grid compiles
device-free on a laptop exactly like the dryrun gate. The registry is
the seam between the auditor and the trainer stack: checks consume the
program's lowered/compiled artifacts and declared specs, never jit
internals.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Tuple

__all__ = [
    "StepProgram",
    "FAST_GRID",
    "FULL_GRID",
    "provision_virtual_devices",
    "build_program",
    "build_grid",
]

#: tier-1 subset: the two strategies whose comm budgets bracket the
#: pure-DP path (replicated update vs ZeRO1 sharded update)
FAST_GRID: Tuple[Tuple[str, str], ...] = (
    ("dp", "fp32"),
    ("dp", "fp16"),
    ("zero1", "fp32"),
    ("zero1", "fp16"),
)

#: full strategy × AMP grid (behind the ``slow`` marker in tests)
FULL_GRID: Tuple[Tuple[str, str], ...] = FAST_GRID + (
    ("fsdp", "fp32"),
    ("fsdp", "fp16"),
    ("hybrid", "fp32"),
    ("hybrid", "fp16"),
)

#: params below this element count replicate (keeps the probe MLP's
#: Dense kernels sharded while the 10-wide head bias falls back —
#: exercising the `indivisible` branch the sharding audit surfaces)
MIN_SHARD_SIZE = 8


def provision_virtual_devices(n: int = 8) -> bool:
    """Ensure ``n`` host devices for CPU-only runs by setting
    ``xla_force_host_platform_device_count``. jax reads XLA_FLAGS at
    backend initialization, not at import, so this works any time before
    the first device touch — which is why the CLI calls it first thing.
    No-op (returns False) when the flag is already present (the test
    conftest provisions its own)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return False
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    return True


def _mlp():
    import flax.linen as nn

    from pytorch_distributed_tpu.mesh import pin_activation

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(256)(x)
            # a model's hook site: under FSDP/HSDP the hidden activation
            # stays batch-sharded and the kernels are gathered
            x = pin_activation(nn.relu(x))
            return pin_activation(nn.Dense(10)(x))

    return MLP()


def _host_batch(batch_size: int):
    import numpy as np

    rng = np.random.default_rng(0)
    return (
        rng.normal(size=(batch_size, 8, 8, 1)).astype(np.float32),
        rng.integers(0, 10, (batch_size,)).astype(np.int32),
    )


@dataclasses.dataclass
class StepProgram:
    """One auditable (strategy × AMP) train step.

    ``state`` is never executed against by the lowering-side checks —
    only traced — so it stays valid for repeated audits; executing
    checks (the runner path) take a fresh state via :meth:`fresh_state`
    because the fused step donates its input."""

    name: str
    strategy_name: str
    amp: str
    trainer: object
    state: object
    batch: tuple
    rng: object

    _lowered: object = None
    _compiled: object = None

    def lowered(self):
        if self._lowered is None:
            self._lowered, self._compiled = self.trainer.step_artifacts(
                self.state, self.batch, self.rng
            )
        return self._lowered

    def compiled(self):
        self.lowered()
        return self._compiled

    def fresh_state(self):
        import jax

        return self.trainer.init(jax.random.key(0), self.batch)

    @property
    def strategy(self):
        return self.trainer.strategy

    def donated_leaf_count(self) -> int:
        import jax.tree_util as jtu

        return len(jtu.tree_leaves(self.state))

    def donated_leaf_paths(self) -> List[str]:
        import jax.tree_util as jtu

        return [
            jtu.keystr(path)
            for path, _ in jtu.tree_leaves_with_path(self.state)
        ]

    def declared_state_specs(self):
        """The strategy's declared PartitionSpec layout for the state —
        what the sharding-propagation audit compares compiled output
        shardings against."""
        import jax

        from pytorch_distributed_tpu.parallel import make_state_specs

        shapes = jax.eval_shape(lambda s: s, self.state)
        return make_state_specs(shapes, self.trainer.strategy)


def _build_mesh(strategy_name: str):
    import jax

    from pytorch_distributed_tpu.mesh import init_device_mesh, init_hybrid_mesh

    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            f"graftir needs >=2 devices to audit sharded programs "
            f"(have {n}); on CPU run the CLI, which provisions virtual "
            f"host devices, or set xla_force_host_platform_device_count"
        )
    if strategy_name in ("dp", "zero1"):
        return init_device_mesh((n,), ("dp",))
    if strategy_name == "fsdp":
        return init_device_mesh((n,), ("fsdp",))
    if strategy_name == "hybrid":
        if n % 2:
            raise RuntimeError(
                f"hybrid audit mesh needs an even device count, have {n}"
            )
        return init_hybrid_mesh(
            (n // 2,), (2,), ("dcn", "fsdp"), stub_slices=True
        )
    raise ValueError(f"unknown strategy {strategy_name!r}")


def _build_strategy(strategy_name: str, mesh):
    from pytorch_distributed_tpu.parallel import (
        DataParallel,
        FullyShardedDataParallel,
        HybridShard,
        ZeRO1,
    )

    if strategy_name == "dp":
        return DataParallel(mesh)
    if strategy_name == "zero1":
        return ZeRO1(mesh, min_shard_size=MIN_SHARD_SIZE)
    if strategy_name == "fsdp":
        return FullyShardedDataParallel(mesh, min_shard_size=MIN_SHARD_SIZE)
    if strategy_name == "hybrid":
        return HybridShard(mesh, min_shard_size=MIN_SHARD_SIZE)
    raise ValueError(f"unknown strategy {strategy_name!r}")


def build_program(
    strategy_name: str, amp: str = "fp32", *, batch_size: Optional[int] = None
) -> StepProgram:
    import jax
    import optax

    from pytorch_distributed_tpu.trainer import Trainer

    mesh = _build_mesh(strategy_name)
    strategy = _build_strategy(strategy_name, mesh)
    if batch_size is None:
        batch_size = 2 * mesh.size()
    trainer = Trainer(
        _mlp(), optax.sgd(0.1, momentum=0.9), strategy, policy=amp
    )
    batch = _host_batch(batch_size)
    state = trainer.init(jax.random.key(0), batch)
    return StepProgram(
        name=f"{strategy_name}:{amp}",
        strategy_name=strategy_name,
        amp=amp,
        trainer=trainer,
        state=state,
        batch=batch,
        rng=jax.random.key(0),
    )


def build_grid(grid: str = "fast") -> List[StepProgram]:
    entries = {"fast": FAST_GRID, "full": FULL_GRID}.get(grid)
    if entries is None:
        raise ValueError(f"unknown grid {grid!r} (expected fast|full)")
    return [build_program(s, amp) for s, amp in entries]
