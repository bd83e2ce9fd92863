"""Pipelined step execution — keep the device queue full.

A loop that reads ``float(metrics["loss"])`` every step makes the host
wait for the device and the device wait for the host: one dispatch +
fetch round trip per step (its size is not measured on the current
machine; ROADMAP item 1). The fix
is structural, not a kernel: never put a device→host read on the hot
path. :class:`AsyncRunner` composes the trainer's raw step with an
on-device :class:`MetricRing` so the jitted program itself accumulates
per-step scalars; the host just dispatches (a bounded ``depth`` steps
ahead), starts a non-blocking readback every ``drain_every`` steps, and
blocks exactly once — at :meth:`AsyncRunner.finish`.

The eager-SPMD overlap model (veScale, arXiv 2509.07003) is the
exemplar: dispatch and metric readback live entirely off the critical
path, and the DDP/FSDP characterization study (arXiv 2505.12832) is the
evidence that input feed + host sync, not collectives, is what separates
measured MFU from the hardware roofline.

Typical use (or the :meth:`..trainer.Trainer.run` facade)::

    runner = AsyncRunner(trainer, depth=2, drain_every=32)
    runner.start(state, first_batch)
    for batch in batches:
        runner.submit(batch)
    state, history = runner.finish()   # the ONE host sync
    history["loss"]                     # per-step series, bit-exact
"""

from pytorch_distributed_tpu.pipeline_exec.metric_ring import MetricRing
from pytorch_distributed_tpu.pipeline_exec.runner import (
    AsyncRunner,
    MetricHistory,
)

__all__ = ["AsyncRunner", "MetricHistory", "MetricRing"]
