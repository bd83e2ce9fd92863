"""Driver of ``kind: train`` traffic: the program's ``Trainer`` behind its
``AsyncRunner`` on a mesh, fed from a pool of seeded batches that lives on
the device.

A window is a sequence of chunks of ``chunk_steps`` whole steps, each chunk
closed by ``runner.sync()`` (a wait on the last step's device result). The
rate is all the window's work over all its time: units per step x steps,
over the time from the window's start to its last ``sync``. The chunk times,
their median and extremes go on an earlier line: they show whether a slow
run was slow throughout or stalled once. A ``sync`` opens one dispatch-long
bubble a chunk, some tenths of a millisecond against two seconds.

Strategy and optimizer are named by the data: the traffic file gives the
strategy's class in ``pytorch_distributed_tpu.parallel`` and its arguments,
the configuration gives the optimizer's name in ``optax`` and its.
"""

from __future__ import annotations

import functools
import importlib
import time
from typing import Any, Dict, List

from chipbench import measure, trace_reduce
from chipbench.measure import Result, Spans, emit


#: The reference is trained this many updates beside the program, both on
#: the run's first batch: on one batch the loss falls by some percent an
#: update (on fresh random batches by a hundredth of that), so an update
#: that is dropped, mis-sized or missing from one shard shows in the loss.
REFERENCE_UPDATES = 3
#: Before any update the two sides differ by rounding in the forward alone:
#: at most 2.1e-5 of the loss in every recorded run (records/, PR 23).
FIRST_LOSS_TOLERANCE = 1e-4


def _optimizer(spec: Dict[str, Any]):
    """``{"optax": "adamw", "kwargs": {...}}`` -> ``optax.adamw(**kwargs)``."""
    import optax

    return getattr(optax, spec["optax"])(**spec["kwargs"])


def _strategy(spec: Dict[str, Any], mesh):
    """``{"class": "FullyShardedDataParallel", "kwargs": {...}}`` -> that
    class of the program's ``parallel`` package on ``mesh``."""
    from pytorch_distributed_tpu import parallel

    return getattr(parallel, spec["class"])(mesh, **spec.get("kwargs", {}))


def seed_key(seed: int):
    """A key from any whole number: ``jax.random.key`` takes 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.key(seed % 2 ** 31), seed // 2 ** 31)


def sharded_layout_fault(params, n_devices: int):
    """None if every sharded parameter has one shard of 1/n of its bytes on
    each of the n devices and those leaves hold nearly all parameter bytes;
    else what is wrong."""
    import jax.tree_util as jtu

    total = sharded = 0
    for path, leaf in jtu.tree_leaves_with_path(params):
        total += leaf.nbytes
        if leaf.sharding.is_fully_replicated:
            continue
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n_devices:
            return f"{jtu.keystr(path)} is on {len(shards)} devices"
        if any(s.data.nbytes * n_devices != leaf.nbytes for s in shards):
            return f"{jtu.keystr(path)} is not in {n_devices} equal shards"
        sharded += leaf.nbytes
    if sharded < 0.99 * total:
        return f"only {sharded} of {total} parameter bytes are sharded"
    return None


def reference_step(task, optimizer, param_layout, opt_layout):
    """One update of the plain float32 reference, jitted: ``(params,
    opt_state, batch) -> (loss on batch, params, opt_state)`` with the
    loss and gradient from ``references/`` and ``optimizer`` from optax."""
    import jax
    import optax

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       out_shardings=(None, param_layout, opt_layout))
    def step(params, opt_state, batch):
        loss, grads = task.reference_loss_and_grad(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    return step


def reference_losses(task, optimizer, state, batch) -> List[float]:
    """The reference trained on ``batch`` from the program's initial
    weights: its loss before and after each of ``REFERENCE_UPDATES``
    updates. Where the program shards its parameters and optimizer state,
    the reference's copies are laid out the same way (a placement, not a
    computation)."""
    import jax
    import jax.numpy as jnp

    param_layout = jax.tree.map(lambda a: a.sharding, state.params)
    opt_layout = jax.tree.map(lambda a: a.sharding, state.opt_state)
    step = reference_step(task, optimizer, param_layout, opt_layout)
    params = jax.tree.map(jnp.copy, state.params)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_layout)(params)
    losses = []
    for _ in range(REFERENCE_UPDATES + 1):
        loss, params, opt_state = step(params, opt_state, batch)
        losses.append(float(loss))
    return losses


def run(cell, seed: int, seconds: float, trace: bool, devices,
        trace_dir: str) -> Result:
    import jax
    import numpy as np

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.trainer import Trainer

    config, traffic = cell.config, cell.traffic
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    task = family.train_task(config, traffic)
    compiles = measure.CompileCounter()
    spans = Spans()

    mesh = ptd.init_device_mesh(tuple(traffic["mesh"]["shape"]),
                                tuple(traffic["mesh"]["axes"]),
                                devices=devices)
    strategy = _strategy(traffic["strategy"], mesh)
    optimizer = _optimizer(config["assumed"]["optimizer"])
    trainer = Trainer(family.build_model(config), optimizer, strategy,
                      loss_fn=task.loss_fn,
                      policy=config["assumed"]["policy"])
    k_weights, k_data = jax.random.split(seed_key(seed))
    state = trainer.init(k_weights, task.sample_batch)
    n_pool = traffic["pool_batches"]
    batch_sharding = jax.sharding.NamedSharding(
        mesh.jax_mesh, strategy.batch_pspec())
    pool = jax.jit(
        lambda k: tuple(task.make_batch(kk)
                        for kk in jax.random.split(k, n_pool)),
        out_shardings=batch_sharding,
    )(k_data)

    faults: List[str] = []
    if "params_sharded_over" in traffic:
        fault = sharded_layout_fault(state.params,
                                     traffic["params_sharded_over"])
        if fault:
            faults.append(fault)

    def batch_of(step: int):
        """The first batch until the checked updates are made, then the
        pool in turn."""
        return pool[0 if step <= REFERENCE_UPDATES else step % n_pool]

    reference = reference_losses(task, optimizer, state, pool[0])

    runner = AsyncRunner(trainer)
    runner.start(state, pool[0])
    _, compiled = runner.step_artifacts(pool[0])
    programs = {"step": trace_reduce.memory_of(compiled)}
    del state
    with spans.span("warmup"):
        runner.submit(pool[0])
        runner.sync()
    emit({"event": "setup", **compiles.snapshot(),
          "memory_stats": devices[0].memory_stats(),
          "step_program_bytes": programs["step"]})

    k = traffic["chunk_steps"]
    submitted = [1]

    def run_chunk() -> float:
        """``k`` steps and a wait for the last: when that wait ended."""
        for _ in range(k):
            with spans.span("submit"):
                runner.submit(batch_of(submitted[0]))
            submitted[0] += 1
        with spans.span("sync"):
            runner.sync()
        return time.perf_counter()

    def run_chunks(start: float, go_on) -> List[float]:
        """Chunks while ``go_on(n_done)``: each one's seconds, the first
        counted from ``start`` and every other from the end of the one
        before it, so that they add up to all the time that passed."""
        ends = [start]
        while go_on(len(ends) - 1):
            ends.append(run_chunk())
        return [b - a for a, b in zip(ends, ends[1:])]

    run_chunk()          # one unmeasured chunk: the window opens on a full pipe
    resident = measure.resident_bytes(devices)
    compiled_before = compiles.programs
    before_window = submitted[0]

    window_t0 = time.perf_counter()
    reduced = None
    if trace:
        # the rate first, with the profiler off; then a short traced window
        times = run_chunks(window_t0,
                           lambda n: n < traffic["trace_rate_chunks"])
        with trace_reduce.tracing(trace_dir):
            with spans.span("window"):
                for _ in range(traffic["trace_chunks"]):
                    run_chunk()
        reduced = trace_reduce.reduce(trace_dir)
    else:
        times = run_chunks(
            window_t0, lambda n: time.perf_counter() - window_t0 < seconds)
    dispatches, executables = runner.dispatch_count, runner.executable_count
    with spans.span("finish"):
        _, history = runner.finish()
    compiled_in_window = compiles.programs - compiled_before

    loss = np.asarray(history["loss"], np.float64)
    window_steps = submitted[0] - before_window
    summary = measure.chunk_summary(times)
    per_chip = task.units_per_step * k / len(devices)
    rate = measure.window_rate(per_chip, times)
    emit({"event": "chunks", "chunk_steps": k, "times_s": times, **summary,
          "rate": rate, "rate_by_median": per_chip / summary["median_s"]})

    rel = [abs(ours - ref) / ref for ours, ref in zip(loss, reference)]
    emit({"event": "check", "loss": list(loss[:len(reference)]),
          "reference_loss": reference, "reference_rel_diff": rel,
          "loss_last": loss[-1], "untrained_loss": task.untrained_loss,
          "dispatches": dispatches, "steps": submitted[0],
          "executables": executables,
          "compiled_in_window": compiled_in_window})
    if not np.isfinite(loss).all():
        faults.append("a loss is not finite")
    if abs(loss[0] - task.untrained_loss) > 0.05 * task.untrained_loss:
        faults.append(f"first loss {loss[0]} is not within 5% of "
                      f"ln(classes) {task.untrained_loss}")
    tolerances = ([FIRST_LOSS_TOLERANCE]
                  + [config["assumed"]["trained_loss_tolerance"]]
                  * REFERENCE_UPDATES)
    for i, (r, tolerance) in enumerate(zip(rel, tolerances)):
        if not r <= tolerance:
            faults.append(f"loss {loss[i]} after {i} updates leaves the "
                          f"reference's {reference[i]} by {r:.2e}")
    if dispatches != submitted[0] or executables != 1:
        faults.append(f"{dispatches} dispatches for {submitted[0]} steps "
                      f"from {executables} executables")
    if compiled_in_window:
        faults.append(f"{compiled_in_window} programs compiled in the window")
    if len(loss) != submitted[0]:
        faults.append(f"{len(loss)} losses for {submitted[0]} steps")

    return Result(
        correct=not faults, attempted=window_steps,
        failed=int((~np.isfinite(loss[-window_steps:])).sum()),
        setup_end=window_t0, resident_bytes=resident,
        end_to_end={traffic["rate_metric"]: rate},
        context={
            "spans": spans, "window_t0": window_t0, "trace": reduced,
            "programs": programs, "steps_in_trace":
                k * traffic["trace_chunks"] if trace else 0,
            "counters": {"rate_per_chip": rate,
                         "flops_per_unit": task.flops_per_unit,
                         "device_kind": devices[0].device_kind},
        },
        why_incorrect="; ".join(faults) or None,
    )
