"""Chip calibration: peak-achievable matmul FLOP/s + step decomposition.

1. Big bf16 matmul chain — establishes what fraction of the 197 TFLOP/s
   spec this chip/platform can actually deliver (MXU ceiling).
2. ResNet-50 step decomposition: fwd-only vs fwd+bwd vs full step.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax


def _fetch(out):
    """Force completion: host-fetch a scalar that depends on the whole
    computation."""
    leaf = jtu.tree_leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def timeit(fn, *args, steps=20, warmup=3):
    """fn(*args) -> out. Iterations are independent (throughput-style,
    pipelined dispatch) but completion is forced by a host fetch of the
    LAST call's output, which depends on every dispatched program having
    executed on device (programs on one device execute in order)."""
    for _ in range(warmup):
        out = fn(*args)
    _fetch(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _fetch(out)
    return (time.perf_counter() - t0) / steps


def matmul_bench():
    n = 8192
    k = jax.random.key(0)
    a = jax.random.normal(k, (n, n), jnp.bfloat16)
    b = jax.random.normal(k, (n, n), jnp.bfloat16)

    @jax.jit
    def chain(a, b):
        x = a
        for _ in range(8):
            x = jnp.dot(x, b)
        return x

    dt = timeit(chain, a, b)
    flops = 8 * 2 * n**3
    print(json.dumps({
        "bench": "matmul8192_bf16_chain8",
        "ms": round(dt * 1e3, 2),
        "tflops": round(flops / dt / 1e12, 1),
        "pct_of_197": round(flops / dt / 197e12 * 100, 1),
    }), flush=True)


def conv_bench():
    # the dominant ResNet-50 conv: 3x3 256ch stride1 at 14x14, and stage-1 56x56
    import flax.linen as nn
    for (hw, cin, cout, bs) in [(56, 64, 64, 128), (28, 128, 128, 128), (14, 256, 256, 128)]:
        conv = nn.Conv(cout, (3, 3), use_bias=False, dtype=jnp.bfloat16)
        x = jnp.ones((bs, hw, hw, cin), jnp.bfloat16)
        v = conv.init(jax.random.key(0), x)
        f = jax.jit(lambda v, x: conv.apply(v, x))
        dt = timeit(f, v, x)
        flops = 2 * bs * hw * hw * 9 * cin * cout
        print(json.dumps({
            "bench": f"conv3x3_{hw}px_{cin}->{cout}_bs{bs}",
            "ms": round(dt * 1e3, 3),
            "tflops": round(flops / dt / 1e12, 1),
            "pct_of_197": round(flops / dt / 197e12 * 100, 1),
        }), flush=True)


def step_decomposition(batch=128, hw=224):
    from pytorch_distributed_tpu.mesh import DeviceMesh
    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    dev = jax.devices()[0]
    mesh = DeviceMesh(("dp",), np.array([dev]))
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    trainer = Trainer(model, optax.sgd(0.1, momentum=0.9), DataParallel(mesh),
                      loss_fn=classification_loss, policy="bf16")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 1000, batch).astype(np.int32)
    state = trainer.init(jax.random.key(0), (x, y))
    xd, yd = trainer._place_batch((x, y))
    xb = xd.astype(jnp.bfloat16)

    variables = {"params": state.params, **state.model_state}

    fwd_train = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))
    dt_f = timeit(fwd_train, variables, xb)
    print(json.dumps({"bench": f"fwd_train_bs{batch}", "ms": round(dt_f * 1e3, 2)}), flush=True)

    fwd_eval = jax.jit(lambda v, x: model.apply(v, x, train=False))
    dt_fe = timeit(fwd_eval, variables, xb)
    print(json.dumps({"bench": f"fwd_eval_bs{batch}", "ms": round(dt_fe * 1e3, 2)}), flush=True)

    def loss_only(params, ms, x, y):
        loss, _ = classification_loss(
            model, {"params": params, **ms}, (x, y), True, None)
        return loss

    gradfn = jax.jit(jax.grad(loss_only))
    dt_g = timeit(gradfn, state.params, state.model_state, xb, yd)
    print(json.dumps({"bench": f"fwd_bwd_bs{batch}", "ms": round(dt_g * 1e3, 2)}), flush=True)

    s = state
    def full(s):
        s2, m = trainer.step(s, (xd, yd))
        return s2, m
    for _ in range(3):
        s, m = full(s)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(20):
        s, m = full(s)
    float(m["loss"])  # chain-dependent: each step consumes the prior state
    dt_s = (time.perf_counter() - t0) / 20
    print(json.dumps({"bench": f"full_step_bs{batch}", "ms": round(dt_s * 1e3, 2)}), flush=True)


def conv_chain_bench():
    """Conv throughput with dispatch amortized: N convs chained in ONE jit."""
    import flax.linen as nn
    N = 40
    for (hw, cin, cout, bs) in [
        (56, 64, 64, 128), (28, 128, 128, 128),
        (14, 256, 256, 128), (7, 512, 512, 128),
    ]:
        conv = nn.Conv(cout, (3, 3), use_bias=False, dtype=jnp.bfloat16)
        x = jax.random.normal(jax.random.key(1), (bs, hw, hw, cin), jnp.bfloat16)
        v = conv.init(jax.random.key(0), x)

        @jax.jit
        def chain(v, x):
            for _ in range(N):
                x = conv.apply(v, x) * 0.1  # keep values bounded
            return x

        dt = timeit(chain, v, x, steps=10)
        flops = N * 2 * bs * hw * hw * 9 * cin * cout
        print(json.dumps({
            "bench": f"convchain{N}_{hw}px_{cin}ch_bs{bs}",
            "ms": round(dt * 1e3, 2),
            "tflops": round(flops / dt / 1e12, 1),
            "pct_of_197": round(flops / dt / 197e12 * 100, 1),
        }), flush=True)


def dispatch_bench():
    """Per-program dispatch overhead: trivial jit in a dependent chain."""
    @jax.jit
    def tiny(x):
        return x + 1.0
    x = jnp.zeros((8,), jnp.float32)
    x = tiny(x)
    float(x[0])
    t0 = time.perf_counter()
    for _ in range(100):
        x = tiny(x)
    float(x[0])
    dt = (time.perf_counter() - t0) / 100
    print(json.dumps({"bench": "dispatch_tiny_chain", "us_per_call": round(dt * 1e6, 1)}), flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "matmul"):
        matmul_bench()
    if which in ("all", "conv"):
        conv_bench()
    if which in ("all", "convchain"):
        conv_chain_bench()
    if which in ("all", "dispatch"):
        dispatch_bench()
    if which in ("all", "step"):
        step_decomposition()
