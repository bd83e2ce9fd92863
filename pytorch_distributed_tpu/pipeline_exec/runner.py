"""AsyncRunner: the K-deep pipelined train-step driver.

One jitted program per step — the trainer's raw ``step_fn`` composed with
:meth:`MetricRing.push` and a stacked snapshot output::

    pstep(state, ring, batch, rng) -> (new_state, new_ring, snapshot)

``state`` and ``ring`` are donated (the in-place update path); the
``[n_metrics, size]`` snapshot is the only fresh output and serves two
jobs at once:

  * **fence** — the host keeps the last ``depth`` snapshots and blocks on
    the one ``depth`` steps behind before dispatching further, so at most
    ``depth`` steps are ever in flight (bounded queue growth, no
    unbounded host run-ahead) while the current step is never waited on;
  * **drain** — every ``drain_every`` steps the host starts
    ``copy_to_host_async`` on it and stashes the handle. The transfer
    overlaps subsequent steps; the values are only *read* (and therefore
    the host only blocks) at :meth:`AsyncRunner.finish`.

Bit-exactness: the runner runs the SAME ``Trainer._make_step_fn``
program logic as ``Trainer.step`` — the ring write is appended after the
state update, so per-step losses and the final state are identical to
sequential stepping (pinned by tests/test_pipeline_exec.py).
"""

from __future__ import annotations

import collections
import time
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_tpu.observability import (
    record_event,
    register_program,
    shapes_of,
    span,
)
from pytorch_distributed_tpu.pipeline_exec.metric_ring import MetricRing

__all__ = ["AsyncRunner", "MetricHistory"]


class MetricHistory:
    """Per-step metric series drained from the device ring: step ``i`` of
    ``history[name]`` is exactly the scalar ``Trainer.step`` would have
    returned for that step."""

    def __init__(self, series: Dict[str, np.ndarray]):
        self.series = series

    def __getitem__(self, name: str) -> np.ndarray:
        return self.series[name]

    def __contains__(self, name: str) -> bool:
        return name in self.series

    def keys(self):
        return self.series.keys()

    @property
    def n_steps(self) -> int:
        if not self.series:
            return 0
        return len(next(iter(self.series.values())))

    def first(self, name: str = "loss") -> float:
        return float(self.series[name][0])

    def last(self, name: str = "loss") -> float:
        return float(self.series[name][-1])


class AsyncRunner:
    """Pipelined executor over a :class:`..trainer.Trainer`.

    Args:
      trainer: the Trainer whose step to drive.
      depth: max steps in flight (K >= 1). 2 is enough to hide dispatch:
        while step i runs, step i+1 is already enqueued.
      drain_every: ring size N; metric readback is issued (async) once
        per N steps. The host never blocks on it until ``finish()``.
    """

    def __init__(self, trainer, *, depth: int = 2, drain_every: int = 32):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if drain_every < 1:
            raise ValueError(
                f"drain_every must be >= 1, got {drain_every}"
            )
        self.trainer = trainer
        self.depth = int(depth)
        self.drain_every = int(drain_every)
        self._pstep = None
        self._names: Tuple[str, ...] = ()
        self._reset()

    #: the whole step — forward, backward, optimizer, metric-ring write,
    #: snapshot — is ONE fused XLA program; nothing else is dispatched
    #: per step (drain readbacks are transfers, not programs)
    programs_per_step: float = 1.0

    @property
    def dispatch_count(self) -> int:
        """Programs dispatched since :meth:`start` — with
        :meth:`executable_count`, the structural evidence behind the
        ``programs_per_step == 1`` claim (graftir's program-count audit
        asserts ``dispatch_count == submits`` and one executable)."""
        return self._dispatches

    @property
    def executable_count(self) -> int:
        """Distinct compiled executables behind the pipelined step (the
        jit cache size). 1 after any number of same-shape submits; a
        second entry is a recompile hazard the structural audit flags.
        0 before the first :meth:`start`."""
        if self._pstep is None:
            return 0
        return int(self._pstep._cache_size())

    @property
    def sharded_update(self) -> bool:
        """True when the trainer's strategy routes the optimizer step
        through the ZeRO sharded-update engine. Provenance for bench
        stamps: the engine is sharding annotations *inside* the one fused
        step program, so enabling it must not move ``programs_per_step``
        off 1 — benchmarks assert on the pair."""
        return bool(getattr(self.trainer.strategy, "sharded_update", False))

    def _reset(self) -> None:
        self._state = None
        self._ring = None
        self._rng = None
        self._n = 0
        self._dispatches = 0
        self._fences: collections.deque = collections.deque()
        self._drains: list = []
        self._last_snap = None
        self._started = False

    # -- setup -------------------------------------------------------------
    def _build(self, state, placed_batch, rng):
        trainer = self.trainer
        raw = trainer._make_step_fn()
        _, m_shapes = jax.eval_shape(raw, state, placed_batch, rng)
        bad = {k: v.shape for k, v in m_shapes.items() if v.shape != ()}
        if bad:
            raise ValueError(
                f"pipelined metric ring holds scalars only; non-scalar "
                f"metrics: {bad}"
            )
        self._names = tuple(sorted(m_shapes))
        mesh = trainer.strategy.mesh.jax_mesh
        replicated = NamedSharding(mesh, PartitionSpec())

        def pstep(state, ring, batch, rng):
            new_state, metrics = raw(state, batch, rng)
            with jax.named_scope("metric_ring"):
                new_ring = ring.push(metrics)
                snapshot = new_ring.stacked()
            return new_state, new_ring, snapshot

        # sharding prefixes: the ring and its snapshot are replicated
        # scalars; the state keeps the strategy's pinned layout exactly
        # like Trainer._build_step
        return jax.jit(
            pstep,
            donate_argnums=(0, 1),
            out_shardings=(
                trainer.state_shardings, replicated, replicated,
            ),
        )

    def start(self, state, sample_batch, rng=None) -> "AsyncRunner":
        """Bind the runner to a state and build the pipelined step (the
        ``sample_batch`` defines the trace shapes; it is NOT consumed —
        pass it to :meth:`submit` as well). ``state`` is owned by the
        runner from here on: the first ``submit`` donates it."""
        self._reset()
        trainer = self.trainer
        trainer._ensure_shardings(state)
        if rng is None:
            rng = jax.random.key(0)
        placed = trainer._place_batch(sample_batch)
        if self._pstep is None:
            # kept across start() calls: re-running the same runner on
            # a new stream (e.g. a benchmark's synthetic then from-disk
            # loop) reuses the compiled executable instead of re-jitting
            self._pstep = self._build(state, placed, rng)
        mesh = trainer.strategy.mesh.jax_mesh
        # commit the fresh ring to the SAME replicated sharding pstep
        # outputs: an uncommitted zeros-ring is a different jit cache key
        # than the ring fed back from pstep, so leaving it uncommitted
        # recompiles on the second submit — after the warmup barrier,
        # inside the caller's timed region
        self._ring = jax.device_put(  # graftlint: disable=hand-rolled-reshard -- first placement of a fresh host-built metric ring, not a layout change of sharded data; no planner cost to bound
            MetricRing.create(self._names, self.drain_every),
            NamedSharding(mesh, PartitionSpec()),
        )
        self._state = state
        self._rng = rng
        self._started = True
        # the lazy way to the compiled step: shapes only, so the thunk holds
        # no array and compiles nothing until somebody calls it
        pstep = self._pstep
        shapes = shapes_of((state, self._ring, placed, rng))
        register_program("step", lambda: pstep.lower(*shapes).compile())
        return self

    # -- the hot path ------------------------------------------------------
    def submit(self, batch) -> None:
        """Dispatch one step. Never blocks on the step just submitted;
        blocks only on the step ``depth`` behind (the bounded in-flight
        window) once the pipeline is full."""
        if not self._started:
            raise RuntimeError("AsyncRunner.start(state, batch) first")
        step = self._n
        with span("runner.submit", step=step):
            with span("runner.place_batch"):
                batch = self.trainer._place_batch(batch)
            with span("runner.dispatch", step=step) as dispatch:
                self._state, self._ring, snap = self._pstep(
                    self._state, self._ring, batch, self._rng
                )
                # after the call, so the step that recompiled shows it
                dispatch.set_metadata(executables=self.executable_count)
            self._n += 1
            self._dispatches += 1
            self._last_snap = snap
            self._fences.append(snap)
            if len(self._fences) > self.depth:
                old = self._fences.popleft()
                # backpressure fence, not a step sync: this blocks on the
                # snapshot of step i-depth (long since dispatched) so the
                # host stays exactly `depth` steps ahead; the current step
                # is never waited on.
                with span("runner.fence", step=step - self.depth):
                    old.block_until_ready()  # graftlint: disable=host-sync-in-hot-loop -- bounded K-deep in-flight window: waits on the step `depth` behind, keeping dispatch ahead of compute; removing it lets the host run unboundedly ahead
            if self._n % self.drain_every == 0:
                # non-blocking drain: start the D2H transfer of the full
                # window and keep the handle; values are read at finish()
                snap.copy_to_host_async()
                self._drains.append(snap)

    def step_artifacts(self, batch):
        """``(lowered, compiled)`` IR artifacts of the pipelined step —
        the graftir audit surface for the runner path (donation of the
        state AND the metric ring, collective set). Trace-only: nothing
        executes, the bound state/ring are not consumed."""
        if not self._started:
            raise RuntimeError("AsyncRunner.start(state, batch) first")
        placed = self.trainer._place_batch(batch)
        lowered = self._pstep.lower(
            self._state, self._ring, placed, self._rng
        )
        return lowered, lowered.compile()

    def sync(self) -> None:
        """Block until every dispatched step has executed. NOT a hot-path
        call — use it as the compile/warmup barrier before a timed
        region (the warm submit's compile must not leak into the clock);
        the pipeline keeps running afterwards."""
        with span("runner.sync", steps=self._n):
            if self._last_snap is not None:
                self._last_snap.block_until_ready()

    # -- the one sync ------------------------------------------------------
    def finish(self):
        """Block until the whole chain executed, assemble the per-step
        metric history, and return ``(final_state, MetricHistory)``. This
        is the ONLY full host sync the runner performs (epoch end)."""
        if not self._started:
            raise RuntimeError("AsyncRunner.start(state, batch) first")
        t0 = time.perf_counter()
        with span("runner.finish", steps=self._n):
            series = {k: np.zeros(self._n, np.float32) for k in self._names}
            tail = None
            if self._n:
                # the final snapshot depends (through the donated state
                # chain) on every prior step: reading it IS the honest
                # end-of-chain barrier
                tail = np.asarray(self._last_snap)
            for w, snap in enumerate(self._drains):
                arr = np.asarray(snap)  # transfer already started async
                lo = w * self.drain_every
                for i, k in enumerate(self._names):
                    series[k][lo:lo + self.drain_every] = arr[i]
            rem = self._n % self.drain_every
            if rem and tail is not None:
                lo = self._n - rem
                for i, k in enumerate(self._names):
                    series[k][lo:lo + rem] = tail[i, :rem]
        record_event(
            "pipeline_exec.step_budget",
            steps=self._n,
            depth=self.depth,
            drain_every=self.drain_every,
            programs_per_step=self.programs_per_step,
            sharded_update=self.sharded_update,
            drains_issued=len(self._drains),
            finish_block_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )
        state = self._state
        self._reset()
        return state, MetricHistory(series)

    # -- convenience -------------------------------------------------------
    def run(self, state, batches: Iterable, rng=None):
        """Drive a whole batch stream: ``start`` on the first batch,
        ``submit`` everything, ``finish``. Composes with
        ``data.loader.prefetch_to_mesh`` so placement, dispatch, and
        compute all overlap."""
        it = iter(batches)
        try:
            first = next(it)
        except StopIteration:
            return state, MetricHistory({})
        self.start(state, first, rng=rng)
        self.submit(first)
        for batch in it:
            self.submit(batch)
        return self.finish()
