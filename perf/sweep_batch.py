"""Batch-size sweep for the ResNet-50 train step on the real chip.

Measures pipelined throughput (chain N steps, fetch final loss) per batch
size, plus XLA's own cost analysis of the compiled step, so MFU is computed
against XLA-counted FLOPs rather than the paper estimate.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pytorch_distributed_tpu.mesh import DeviceMesh
from pytorch_distributed_tpu.models import resnet50
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.trainer import Trainer, classification_loss

PEAK = 197e12  # v5e bf16


def run_one(batch: int, hw: int = 224, steps: int = 30, copts: dict | None = None) -> dict:
    dev = jax.devices()[0]
    mesh = DeviceMesh(("dp",), np.array([dev]))
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    trainer = Trainer(
        model,
        optax.sgd(0.1, momentum=0.9),
        DataParallel(mesh),
        loss_fn=classification_loss,
        policy="bf16",
        compiler_options=copts,
    )
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 1000, batch).astype(np.int32)
    state = trainer.init(jax.random.key(0), (x, y))
    bd = trainer._place_batch((x, y))
    rng_key = jax.random.key(0)

    # ONE compile (AOT), reused for cost_analysis AND the timed loops —
    # same structure as bench.py
    t_c0 = time.perf_counter()
    if trainer._step_fn is None:
        trainer._step_fn = trainer._build_step()
    compiled = trainer._step_fn.lower(state, bd, rng_key).compile()
    compile_s = time.perf_counter() - t_c0
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops = ca.get("flops")
    except Exception as e:
        flops = f"err: {e}"

    for _ in range(3):
        state, m = compiled(state, bd, rng_key)
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, bd, rng_key)
    last = float(m["loss"])
    dt = time.perf_counter() - t0
    step_ms = dt / steps * 1e3
    img_s = batch * steps / dt
    mfu_paper = img_s * 12.27e9 / PEAK
    mfu_xla = (flops / (dt / steps)) / PEAK if isinstance(flops, (int, float)) else None
    return {
        "batch": batch,
        "step_ms": round(step_ms, 2),
        "img_per_sec": round(img_s, 1),
        "mfu_paper": round(mfu_paper, 4),
        "mfu_xla": round(mfu_xla, 4) if mfu_xla else flops,
        "xla_flops_per_step_G": round(flops / 1e9, 1) if isinstance(flops, (int, float)) else None,
        "compile_s": round(compile_s, 1),
        "loss_last": round(last, 3),
    }


if __name__ == "__main__":
    import os
    copts = json.loads(os.environ.get("SWEEP_COPTS", "null"))
    batches = [int(a) for a in sys.argv[1:]] or [128, 256, 512]
    for b in batches:
        try:
            r = run_one(b, copts=copts)
            r["copts"] = copts
            print(json.dumps(r), flush=True)
        except Exception as e:
            print(json.dumps({"batch": b, "copts": copts, "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)
