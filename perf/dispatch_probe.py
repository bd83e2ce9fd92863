"""Dispatch probe: where does a train step's wall time go — host dispatch
or device compute?

Prints ONE JSON line answering three questions about the step executor:

  1. **dispatch_ms_per_program** — the fixed host cost of launching any
     XLA program, measured on a tiny dependent chain (``v = tiny(v)``)
     whose compute is ~zero: the enqueue loop's wall time is pure
     dispatch. Not measured on the current machine.

  2. **step budget** — from :meth:`Trainer.compile_step`'s executable:
     enqueue N chained steps without reading anything (loop time = host
     dispatch per step), then fetch the final loss (chain-dependent, so
     the elapsed total = device compute per step). The gap between a
     per-step-synced loop and the async chain is the dispatch + fetch
     round-trip the pipeline is hiding.

  3. **programs_per_step** — the runner dispatches ONE fused program per
     step (forward+backward+update+metric-ring write) and zero host
     fetches until the epoch ends; the legacy loop dispatches the same
     program but adds a blocking D2H fetch every step.

Standalone (any platform; shapes shrink off-TPU so it always prints)::

    JAX_PLATFORMS=cpu python perf/dispatch_probe.py
    python perf/dispatch_probe.py --steps 50 --batch 64 --hw 128

``probe()`` is importable for the tier-1 smoke test.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def probe(steps: int = 20, batch: int = 8, hw: int = 32,
          classes: int = 100, depth: int = 2) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_tpu.mesh import DeviceMesh
    from pytorch_distributed_tpu.models import resnet18
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    dev = jax.devices()[0]
    mesh = DeviceMesh(("dp",), np.array([dev]))
    trainer = Trainer(
        resnet18(num_classes=classes),
        optax.sgd(0.1, momentum=0.9),
        DataParallel(mesh),
        loss_fn=classification_loss,
    )
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, classes, batch).astype(np.int32)
    state = trainer.init(jax.random.key(0), (x, y))

    # -- 1. per-program dispatch floor (tiny dependent chain) -------------
    tiny = jax.jit(lambda v: v + 1.0)
    v = tiny(jnp.zeros((8,), jnp.float32))
    v.block_until_ready()
    n_tiny = 200
    t0 = time.perf_counter()
    for _ in range(n_tiny):
        v = tiny(v)
    enqueue_s = time.perf_counter() - t0
    np.asarray(v)  # drain the chain before reusing the device below
    dispatch_ms_per_program = enqueue_s / n_tiny * 1e3

    # -- 2. dispatch vs compute on the REAL compiled step -----------------
    # compile_step is the supported surface for the executable: the same
    # program serves the enqueue-only chain, the blocking loop, and (via
    # as_text/cost_analysis) any HLO inspection a caller wants next.
    compiled, placed, key = trainer.compile_step(state, (x, y))
    for _ in range(2):
        state, m = compiled(state, placed, key)
    float(m["loss"])  # warm barrier: compile + first steps off the clock

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, placed, key)
    t_enqueue = time.perf_counter() - t0
    final = float(m["loss"])  # chain-dependent: closes the whole region
    t_total = time.perf_counter() - t0

    enqueue_ms = t_enqueue / steps * 1e3
    chained_ms = t_total / steps * 1e3

    # legacy executor: same program, plus one blocking fetch per step
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, placed, key)
        float(m["loss"])
    blocking_ms = (time.perf_counter() - t0) / steps * 1e3

    # -- 3. the pipelined runner over the same trainer --------------------
    runner = AsyncRunner(trainer, depth=depth, drain_every=steps + 1)
    runner.start(state, (x, y))
    runner.submit((x, y))
    runner.sync()  # runner's own compile + warm step off the clock
    t0 = time.perf_counter()
    for _ in range(steps):
        runner.submit((x, y))
    state, hist = runner.finish()
    runner_ms = (time.perf_counter() - t0) / steps * 1e3

    return {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "steps": steps,
        "batch": batch,
        "hw": hw,
        "dispatch_ms_per_program": round(dispatch_ms_per_program, 3),
        "programs_per_step": {
            # one fused program (fwd+bwd+update+ring write); metric
            # readback is an async transfer every drain_every steps,
            # not a program and not a sync
            "runner": runner.programs_per_step,
            "legacy_blocking": 1.0,
        },
        "host_fetches_per_step": {
            "runner": round(1.0 / max(steps, 1), 4),  # one, at finish()
            "legacy_blocking": 1.0,
        },
        "step_budget": {
            "enqueue_ms_per_step": round(enqueue_ms, 3),
            "chained_ms_per_step": round(chained_ms, 3),
            "blocking_ms_per_step": round(blocking_ms, 3),
            "runner_ms_per_step": round(runner_ms, 3),
            "blocking_extra_ms": round(blocking_ms - chained_ms, 3),
            "dispatch_fraction": round(
                min(enqueue_ms / chained_ms, 1.0), 4
            ) if chained_ms > 0 else None,
        },
        "runner_depth": runner.depth,
        "loss_final": round(final, 4),
        "loss_runner_last": round(hist.last(), 4),
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--hw", type=int, default=32)
    p.add_argument("--depth", type=int, default=2)
    args = p.parse_args()
    print(json.dumps(probe(
        steps=args.steps, batch=args.batch, hw=args.hw, depth=args.depth,
    )))


if __name__ == "__main__":
    main()
