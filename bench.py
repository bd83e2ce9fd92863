"""Headline benchmark — prints ONE JSON line.

Metric (BASELINE.json): ResNet-50 ImageNet images/sec/chip — full training
step (forward+backward+SGD update, bf16 compute, SyncBN-semantics global-view
jit) on one chip. It needs a TPU: with no chip it fails (the ``bench_error``
line, exit 1) — it never shrinks to a CPU shape and never prints a number
that this run did not measure.

Honesty rules:
  * The timed region ends with a host fetch of chain-dependent data (the
    runner's last metric snapshot). Each step's loss depends on the params
    produced by every prior step, so that device-to-host fetch cannot
    complete until the whole chain executed.
  * A second, per-step-synced loop measures the step-time distribution.
  * Achieved TFLOP/s and MFU are computed against the chip's bf16 peak
    (``benchmarks/peaks.py``; an unknown ``device_kind`` is an error); if
    the pipelined number implies MFU > 100% (physically impossible) the
    blocking per-step median is reported instead and the anomaly is flagged.
  * Loss must end below where it started (or below random-chance loss for
    1000 classes — the fixed batch gets memorized); otherwise the bench
    reports an error rather than a throughput.
"""

from __future__ import annotations

import json
import statistics
import sys
import time

# ResNet-50 @224x224: ~4.09 GFLOP forward per image (standard count, conv+fc
# MACs x2); training fwd+bwd ~= 3x forward.
RESNET50_TRAIN_GFLOP_PER_IMG_224 = 4.09 * 3

# Round-1 measured single-chip number (commit 25be340: 2183 img/s on one
# v5e chip) — the anchor for vs_baseline until the reference publishes one
# (BASELINE.json "published" is {}). Only comparable on the same chip
# generation: a v4/v5p run must not report a cross-chip ratio.
ROUND1_BASELINE_IMG_PER_SEC = 2183.0
ROUND1_BASELINE_DEVICE_KINDS = ("v5 lite", "v5e")


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.peaks import peak_bf16_flops
    from pytorch_distributed_tpu.compile_cache import enable_compile_cache
    from pytorch_distributed_tpu.mesh import DeviceMesh
    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; found {dev.platform} "
            f"({dev.device_kind}) — refusing to report a CPU number"
        )
    peak = peak_bf16_flops(dev.device_kind) / 1e12  # TFLOP/s; unknown kind raises
    enable_compile_cache()
    batch, hw, steps, sync_steps, warmup = 128, 224, 50, 15, 3

    mesh = DeviceMesh(("dp",), np.array([dev]))
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    trainer = Trainer(
        model,
        optax.sgd(0.1, momentum=0.9),
        DataParallel(mesh),
        loss_fn=classification_loss,
        policy="bf16",
    )

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 1000, batch).astype(np.int32)

    # -- dispatch overhead: tiny dependent-chain program ------------------
    # The per-program host dispatch cost: a blocking step pays dispatch +
    # fetch round-trip latency per step, a pipelined chain amortizes it.
    tiny = jax.jit(lambda v: v + 1.0)
    v = tiny(jnp.zeros((8,), jnp.float32))
    float(v[0])
    t0 = time.perf_counter()
    for _ in range(50):
        v = tiny(v)
    float(v[0])
    dispatch_ms = (time.perf_counter() - t0) / 50 * 1e3

    state = trainer.init(jax.random.key(0), (x, y))
    batch_dev = trainer._place_batch((x, y))  # device-resident once; the
    # timed loop must measure the step, not host->device copies

    # ONE compile, AOT: the same executable serves cost_analysis and the
    # blocking comparison loop below.
    rng_key = jax.random.key(0)
    if trainer._step_fn is None:
        trainer._step_fn = trainer._build_step()
    compiled_step = trainer._step_fn.lower(state, batch_dev, rng_key).compile()
    xla_flops = float(compiled_step.cost_analysis()["flops"])

    def step(s):
        return compiled_step(s, batch_dev, rng_key)

    # -- pipelined throughput: the AsyncRunner is the product path ---------
    # One fused program per step (fwd+bwd+update+metric-ring write), at
    # most `depth` steps in flight, NO host read until finish(). The
    # runner compiles its own program (a second compile on top of the AOT
    # one above — the AOT executable is still needed for cost_analysis
    # and the blocking comparison loop); submit+sync below keeps that
    # compile and the warmup chain off the clock. finish() assembles the
    # per-step loss series by reading the last snapshot, which depends on
    # every prior step through the donated state chain — the same
    # cannot-lie barrier as the old float(m["loss"]) fetch.
    runner = AsyncRunner(trainer, depth=2, drain_every=warmup + steps)
    runner.start(state, batch_dev)
    for _ in range(warmup):  # stabilize + compile, excluded from the clock
        runner.submit(batch_dev)
    runner.sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        runner.submit(batch_dev)
    state, hist = runner.finish()  # the one chain-closing host fetch
    dt_pipelined = time.perf_counter() - t0
    first_loss = hist.first("loss") if warmup == 0 else float(
        hist["loss"][warmup - 1]
    )  # loss of the LAST warmup step — same anchor the old loop used

    # -- per-step blocking distribution ------------------------------------
    # deliberately synced every step: this loop MEASURES the stall the
    # runner removes (blocking_extra_ms below), it is not the product path
    step_times = []
    for _ in range(sync_steps):
        t1 = time.perf_counter()
        state, m = step(state)
        float(m["loss"])  # per-step host sync
        step_times.append(time.perf_counter() - t1)
    final_loss = float(m["loss"])
    p50 = statistics.median(step_times)
    n = len(step_times)
    p90 = sorted(step_times)[max(0, -(-9 * n // 10) - 1)]  # nearest-rank ceil

    # SGD(0.1, momentum) on random labels can transiently overshoot the
    # post-warmup loss, so also accept anything below random-chance loss.
    random_chance_loss = float(np.log(1000.0))
    trained = final_loss < first_loss or final_loss < 0.9 * random_chance_loss
    if not trained or not np.isfinite(final_loss):
        raise RuntimeError(
            f"loss did not decrease ({first_loss:.4f} -> {final_loss:.4f}) — "
            f"the step is not training; refusing to report throughput"
        )

    images_per_sec = batch * steps / dt_pipelined
    images_per_sec_sync = batch / p50

    gflop_per_img = RESNET50_TRAIN_GFLOP_PER_IMG_224
    achieved_tflops = images_per_sec * gflop_per_img / 1000.0
    mfu = achieved_tflops / peak
    anomaly = None
    if mfu > 1.0:
        # physically impossible — async dispatch escaped the fetch barrier
        # somehow; fall back to the per-step blocking measurement
        anomaly = (
            f"pipelined number implied MFU {mfu:.2f} > 1.0; "
            f"reported blocking per-step median instead"
        )
        images_per_sec = images_per_sec_sync
        achieved_tflops = images_per_sec * gflop_per_img / 1000.0
        mfu = achieved_tflops / peak
        if mfu > 1.0:
            # still impossible — the peak-FLOPs table is wrong for this
            # chip, not async escape; refuse to report a fabricated number
            raise RuntimeError(
                f"blocking measurement still implies MFU {mfu:.2f} > 1.0 "
                f"against peak {peak} TFLOP/s for "
                f"{dev.device_kind} — peak table is wrong"
            )

    device_kind = dev.device_kind
    # vs_baseline only meaningful on the same chip generation the round-1
    # anchor was measured on
    comparable = any(
        k in device_kind.lower() for k in ROUND1_BASELINE_DEVICE_KINDS
    )
    step_ms_pipelined = dt_pipelined / steps * 1e3
    # if the anomaly guard discredited the pipelined timing, every derived
    # number must switch to the blocking measurement too
    dt_step_trusted = p50 if anomaly else dt_pipelined / steps
    out = {
        "metric": "resnet50_imagenet_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / ROUND1_BASELINE_IMG_PER_SEC, 4)
        if comparable
        else 0.0,
        "platform": dev.platform,
        "device_kind": device_kind,
        "timed_steps": steps,
        "step_ms_p50": round(p50 * 1e3, 2),
        "step_ms_p90": round(p90 * 1e3, 2),
        "images_per_sec_blocking": round(images_per_sec_sync, 2),
        "achieved_tflops": round(achieved_tflops, 1),
        "mfu": round(mfu, 4),
        "mfu_xla": round(xla_flops / dt_step_trusted / (peak * 1e12), 4),
        "dispatch_ms_per_program": round(dispatch_ms, 2),
        # the blocking-vs-pipelined gap is the dispatch + fetch round trip a
        # per-step host read pays (see dispatch_ms_per_program)
        "step_budget": {
            "blocking_ms_p50": round(p50 * 1e3, 2),
            "dispatch_ms_per_program": round(dispatch_ms, 3),
        } if anomaly else {
            "pipelined_ms": round(step_ms_pipelined, 2),
            "blocking_extra_ms": round(p50 * 1e3 - step_ms_pipelined, 2),
            "dispatch_ms_per_program": round(dispatch_ms, 3),
            "programs_per_step": runner.programs_per_step,
            "sharded_update": runner.sharded_update,
            "runner_depth": runner.depth,
            "metric_drain_every": runner.drain_every,
        },
        "loss_first": round(first_loss, 4),
        "loss_last": round(final_loss, 4),
    }
    if anomaly:
        out["anomaly"] = anomaly
    print(json.dumps(out))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # always emit the one line
        print(json.dumps({
            "metric": "bench_error",
            "value": 0,
            "unit": "error",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }))
        sys.exit(1)
