"""The benchmark matrix: the reference's five configs (BASELINE.json
"configs"; SURVEY §6) plus two from-disk variants (#6/#7) that put the
real input pipeline — JPEG ImageFolder / memmapped token-bin through the
worker DataLoader — in the timed loop next to the synthetic number
(VERDICT r4 #2).

Each config function returns a JSON-able result dict; ``python -m
benchmarks.matrix`` runs the whole matrix for the current platform and
writes ``benchmarks/results_<platform>.json``. BASELINE.md's measured
table is generated from those files by ``python -m benchmarks.report``.

Honesty rules (same as bench.py): timed loops are dependent chains closed
by a host fetch of chain-dependent data; compile time excluded; losses
must decrease or the config reports an error instead of a throughput.
Timed loops run on the pipelined executor (``pipeline_exec.AsyncRunner``):
no per-step device->host sync ever sits inside the clock — per-step
losses come from the on-device metric ring drained once at the end
(which is also the chain-closing fetch).

Platform handling: the matrix runs ImageNet-class shapes and reports
absolute images-or-tokens/sec/chip, and it needs a TPU to do so — a run
that finds no chip fails, it does not shrink. The smoke shapes (tiny
models, the CPU virtual mesh) run only when asked for: ``smoke=True`` on a
config function, as the tests do, or ``python -m benchmarks.matrix
--smoke``. Smoke numbers validate the harness and measure SCALING SHAPE
(DP-vs-FSDP ratio, ws-1-vs-8 behavior on the virtual mesh), not absolute
throughput; results are tagged with the platform so the report never
mixes them. A config that raises is recorded in the JSON, the other
configs still run, and the process exits non-zero.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Optional

__all__ = ["run_matrix", "CONFIGS"]


def _timed_steps(trainer, state, batch, steps: int, *, runner=None,
                 batches=None, depth: int = 2):
    """Dependent-chain timing on the pipelined executor
    (``pipeline_exec.AsyncRunner``): ``depth`` steps stay in flight, the
    per-step metrics accumulate in the on-device ring, and the timed
    region is closed by ``finish()``'s host fetch of the last metric
    snapshot — chain-dependent through the donated state, so it cannot
    complete until every timed step executed. No per-step host sync ever
    happens inside the clock (the old ``float(m["loss"])``-per-step bug
    class, now lint-enforced). The warm submit (compile) runs before the
    clock behind a ``sync()`` barrier; its loss is ``history[0]`` — the
    loss guard's ``first``, same semantics as the old warmup step.

    ``batches`` (iterable of ``steps`` host batches) feeds fresh data per
    step (the from-disk configs); default re-submits ``batch``. Pass the
    returned ``runner`` back in to reuse the compiled pipelined program
    across loops (one compile serves synthetic AND from-disk timing).
    Returns ``(dt, state, history, runner)``."""
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner

    if runner is None:
        runner = AsyncRunner(trainer, depth=depth, drain_every=steps + 1)
    runner.start(state, batch)
    runner.submit(batch)   # compile + warm — excluded from the clock
    runner.sync()
    stream = batches if batches is not None \
        else (batch for _ in range(steps))
    t0 = time.perf_counter()
    for b in stream:
        runner.submit(b)
    state, hist = runner.finish()
    return time.perf_counter() - t0, state, hist, runner


def _runner_stamp(runner) -> dict:
    """Executor provenance for the config-row JSON (report.py renders
    these alongside the throughput)."""
    return {
        "runner_depth": runner.depth,
        "metric_drain_every": runner.drain_every,
        "programs_per_step": runner.programs_per_step,
        # ZeRO sharded update: must read True with programs_per_step
        # still 1 — the engine is annotations inside the fused step
        "sharded_update": runner.sharded_update,
    }


def _loss_guard(first: float, last: float, n_classes: Optional[int] = None):
    import numpy as np

    ok = last < first
    if n_classes:
        ok = ok or last < 0.9 * float(np.log(n_classes))
    if not ok or not np.isfinite(last):
        raise RuntimeError(
            f"loss did not decrease ({first:.4f} -> {last:.4f})"
        )


def _no_divergence_guard(first: float, last: float):
    """From-disk configs time the INPUT PIPELINE on fresh random-noise
    batches each step — a handful of steps on noise can legitimately move
    the loss either way (configs 1-4 own the convergence checks, on fixed
    batches); the guard here is that real steps executed and produced a
    finite loss (catches NaN/inf and fake loops)."""
    import numpy as np

    if not (np.isfinite(first) and np.isfinite(last)):
        raise RuntimeError(
            f"non-finite loss ({first:.4f} -> {last:.4f})"
        )


def _full_size(smoke: bool) -> bool:
    """True for the real shapes, False for the smoke shapes. Smoke is the
    caller's request only (the tests, ``--smoke``); a full-size run that
    finds no TPU raises instead of quietly shrinking to a CPU number."""
    if smoke:
        return False
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"the benchmark matrix needs a TPU, found {dev.platform} "
            f"({dev.device_kind}); pass smoke=True / --smoke for the "
            f"harness-only smoke shapes"
        )
    return True


def _peak_bf16_flops() -> float:
    import jax

    from benchmarks.peaks import peak_bf16_flops

    return peak_bf16_flops(jax.devices()[0].device_kind)


# -- config #1: single-process DP, ResNet-18 / CIFAR-10 --------------------
def config1_resnet18_cifar(smoke: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models import resnet18
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    tpu = _full_size(smoke)
    batch, steps = (256, 30) if tpu else (32, 5)
    mesh = ptd.init_device_mesh((1,), ("dp",), devices=jax.devices()[:1])
    model = resnet18(num_classes=10, cifar_stem=True,
                     dtype=jnp.bfloat16 if tpu else jnp.float32)
    trainer = Trainer(model, optax.sgd(0.1, momentum=0.9),
                      DataParallel(mesh), loss_fn=classification_loss,
                      policy="bf16" if tpu else "fp32")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, batch).astype(np.int32)
    state = trainer.init(jax.random.key(0), (x, y))
    bd = trainer._place_batch((x, y))
    dt, state, hist, runner = _timed_steps(trainer, state, bd, steps)
    _loss_guard(hist.first(), hist.last(), 10)
    return {
        "config": 1, "name": "resnet18_cifar10_1dev",
        "images_per_sec": round(batch * steps / dt, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "batch": batch,
        **_runner_stamp(runner),
    }


# -- config #2: DP ResNet-50 / ImageNet shapes -----------------------------
def _resnet50_dp(n_dev: int, batch_per_dev: int, hw: int, steps: int,
                 policy: str, accum: int = 1,
                 strategy: str = "dp") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.parallel import DataParallel, ZeRO1
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    batch = batch_per_dev * n_dev
    mesh = ptd.init_device_mesh(
        (n_dev,), ("dp",), devices=jax.devices()[:n_dev]
    )
    model = resnet50(
        num_classes=1000,
        dtype=jnp.bfloat16 if policy != "fp32" else jnp.float32,
        bn_axis_name=None,
    )
    strat = (
        ZeRO1(mesh) if strategy == "zero1" else DataParallel(mesh)
    )
    trainer = Trainer(model, optax.sgd(0.1, momentum=0.9),
                      strat, loss_fn=classification_loss,
                      policy=policy, grad_accum_steps=accum)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 1000, batch).astype(np.int32)
    state = trainer.init(jax.random.key(0), (x, y))
    bd = trainer._place_batch((x, y))
    dt, state, hist, runner = _timed_steps(trainer, state, bd, steps)
    _loss_guard(hist.first(), hist.last(), 1000)
    return {
        "world_size": n_dev,
        "images_per_sec": round(batch * steps / dt, 1),
        "images_per_sec_per_dev": round(batch * steps / dt / n_dev, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "global_batch": batch,
        **_runner_stamp(runner),
    }


def config2_resnet50_dp_scaling(smoke: bool = False) -> dict:
    tpu = _full_size(smoke)
    if tpu:
        # one real chip: absolute per-chip throughput (the headline number)
        r1 = _resnet50_dp(1, 128, 224, 30, "bf16")
        return {
            "config": 2, "name": "resnet50_imagenet_dp",
            "ws1": r1,
            "note": "one chip; the ws8 scaling shape is a CPU "
                    "virtual-mesh smoke row (results_cpu.json), not a "
                    "device measurement",
        }
    r1 = _resnet50_dp(1, 8, 64, 4, "fp32")
    r8 = _resnet50_dp(8, 8, 64, 4, "fp32")
    # ZeRO sharded weight update on the same 8-way mesh: same model, same
    # data, optimizer state + update sharded 1/8 (memory numbers in the
    # top-level memory_per_chip stamp); the row's runner stamp is the
    # programs_per_step==1 proof for the sharded path
    r8z = _resnet50_dp(8, 8, 64, 4, "fp32", strategy="zero1")
    # weak scaling on a shared-host virtual mesh: per-device work constant,
    # ideal = step time unchanged; on CPU all 8 "devices" share the host's
    # cores so this measures SPMD program overhead shape, not hardware
    return {
        "config": 2, "name": "resnet50_dp_scaling_smoke",
        "ws1": r1, "ws8": r8, "ws8_zero1": r8z,
        "weak_scaling_step_ratio": round(r8["step_ms"] / r1["step_ms"], 3),
        "zero1_over_dp_step_ratio": round(
            r8z["step_ms"] / r8["step_ms"], 3
        ),
    }


# -- config #3: DP + mixed precision + gradient accumulation ---------------
def config3_amp_accum(smoke: bool = False) -> dict:
    tpu = _full_size(smoke)
    if tpu:
        base = _resnet50_dp(1, 128, 224, 30, "bf16", accum=1)
        amp = _resnet50_dp(1, 128, 224, 30, "bf16", accum=2)
    else:
        base = _resnet50_dp(1, 8, 64, 4, "fp32", accum=1)
        amp = _resnet50_dp(1, 8, 64, 4, "fp32", accum=2)
    return {
        "config": 3, "name": "resnet50_amp_grad_accum",
        "baseline": base, "accum2": amp,
        "accum_overhead_pct": round(
            (amp["step_ms"] / base["step_ms"] - 1) * 100, 1
        ),
    }


# -- config #4: FSDP GPT-2 125M web text ----------------------------------
def config4_gpt2_fsdp(smoke: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.trainer import Trainer, lm_loss

    tpu = _full_size(smoke)
    if tpu:
        cfg = GPT2Config(dtype=jnp.bfloat16, remat=False)  # full 125M
        # B=16 is the largest batch whose dense-loss step fits one v5e:
        # compiled for a described v5e the step needs 7.4 GB at B=8 and
        # 13.0 GB at B=16, and at B=32 the compiler refuses it (17.5 GB
        # of 15.75 GB HBM; the fp32 logits are the largest allocation).
        # lm_loss_chunked is the memory path for B=32 / long-T / big-V.
        # (Earlier rounds measured B=16 ahead of B=8 and dense ahead of
        # chunked CE at this shape; not measured on the current machine.)
        B, T, steps, n_dev = 16, 1024, 20, 1
    else:
        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4)
        B, T, steps, n_dev = 8, 32, 4, 8

    if n_dev == 1:
        mesh = ptd.init_device_mesh(
            (1,), ("fsdp",), devices=jax.devices()[:1]
        )
    else:
        mesh = ptd.init_device_mesh((n_dev,), ("fsdp",))
    model = GPT2(cfg)
    trainer = Trainer(
        model,
        optax.adamw(3e-4, weight_decay=0.01),
        FullyShardedDataParallel(mesh, min_shard_size=8),
        loss_fn=lm_loss,
        policy="bf16" if tpu else "fp32",
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    state = trainer.init(jax.random.key(0), (tokens, targets))
    bd = trainer._place_batch((tokens, targets))
    dt, state, hist, runner = _timed_steps(trainer, state, bd, steps)
    _loss_guard(hist.first(), hist.last(), cfg.vocab_size)
    toks = B * T * steps / dt
    out = {
        "config": 4, "name": "gpt2_fsdp",
        "tokens_per_sec": round(toks, 1),
        "tokens_per_sec_per_dev": round(toks / n_dev, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "batch": B, "seq_len": T, "world_size": n_dev,
        **_runner_stamp(runner),
    }
    if tpu:
        # transformer MFU: 6 * params * tokens/sec over bf16 peak
        n_params = sum(
            x.size for x in jax.tree_util.tree_leaves(state.params)
        )
        flops_per_tok = 6 * n_params
        out["n_params"] = int(n_params)
        out["mfu"] = round(toks * flops_per_tok / _peak_bf16_flops(), 4)
    else:
        # DP-vs-FSDP comparison (the BASELINE.json scaling-efficiency
        # metric, shape-level on the virtual mesh): same model/batch under
        # pure DP (replicated params, grad all-reduce) vs FSDP (sharded
        # params, all-gather + reduce-scatter)
        from pytorch_distributed_tpu.parallel import DataParallel

        mesh_dp = ptd.init_device_mesh((n_dev,), ("dp",))
        trainer_dp = Trainer(
            GPT2(cfg), optax.adamw(3e-4, weight_decay=0.01),
            DataParallel(mesh_dp), loss_fn=lm_loss, policy="fp32",
        )
        sdp = trainer_dp.init(jax.random.key(0), (tokens, targets))
        bdp = trainer_dp._place_batch((tokens, targets))
        dt_dp, sdp, _, _ = _timed_steps(trainer_dp, sdp, bdp, steps)
        out["dp_step_ms"] = round(dt_dp / steps * 1e3, 2)
        out["fsdp_over_dp_step_ratio"] = round(
            (dt / steps) / (dt_dp / steps), 3
        )
    return out


# -- config #5: multi-node elastic launch ----------------------------------
def config5_elastic_restart(smoke: bool = False) -> dict:
    """2 agents (nodes) x 1 worker, worker killed once; measures rendezvous
    + restart recovery latency. CPU-only control-plane (no jit), so the
    same measurement is valid on any platform and ``smoke`` changes
    nothing. The agents are threads of the process that holds the chip and
    their workers are its children: the worker script must stay JAX-free,
    because a child that reaches for the chip its parent holds hangs."""
    import os
    import sys
    import tempfile
    import textwrap
    import time as _t

    from pytorch_distributed_tpu.distributed.store import TCPStore
    from pytorch_distributed_tpu.elastic.agent import (
        LocalElasticAgent as ElasticAgent,
        WorkerSpec,
    )

    script = textwrap.dedent("""
        import json, os, sys, time
        marker = sys.argv[1]
        restart = int(os.environ.get("TPURUN_RESTART_COUNT", "0"))
        if restart == 0 and os.environ["RANK"] == "0":
            sys.exit(3)  # first incarnation of rank 0 dies immediately
        # surviving workers "train" long enough for their agent to notice
        # the peer's round advance (a real job would block on a collective)
        time.sleep(3)
        with open(marker + os.environ["RANK"], "w") as f:
            f.write(json.dumps({"restart": restart,
                                "t": time.time()}))
    """)
    with tempfile.TemporaryDirectory() as td:
        script_path = os.path.join(td, "worker.py")
        with open(script_path, "w") as f:
            f.write(script)
        marker = os.path.join(td, "done")

        import threading

        from datetime import timedelta

        from pytorch_distributed_tpu.elastic.rendezvous import (
            DynamicRendezvous,
        )

        master = TCPStore("127.0.0.1", 0, 2, is_master=True,
                          timeout=timedelta(seconds=60))
        t0 = _t.time()
        errors = []

        def run_agent(node):
            try:
                store = master if node == 0 else TCPStore(
                    "127.0.0.1", master.port, 2,
                    timeout=timedelta(seconds=60),
                )
                rdzv = DynamicRendezvous(store, "bench5", 2, 2)
                spec = WorkerSpec(
                    cmd=[sys.executable, script_path, marker],
                    nproc_per_node=1,
                    max_restarts=2,
                    run_id="bench5",
                    log_dir=os.path.join(td, f"logs{node}"),
                )
                ElasticAgent(spec, rdzv).run()
            except Exception as e:  # surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=run_agent, args=(n,)) for n in range(2)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        elapsed = _t.time() - t0
        restarts = None
        try:
            with open(marker + "0") as f:
                restarts = json.load(f)["restart"]
        except OSError:
            pass
        master.close()
    if errors:
        raise RuntimeError(f"elastic run failed: {errors}")
    return {
        "config": 5, "name": "elastic_2node_restart",
        "recovered_after_worker_death": restarts == 1,
        "total_wall_s_incl_restart": round(elapsed, 2),
    }


# -- configs #6/#7: the input pipeline in the loop (from-disk variants) ----
def _cycling_batches(loader):
    """Endless batch stream cycling epochs (fresh shuffles/augments per
    epoch via set_epoch)."""
    epoch = 0
    while True:
        loader.set_epoch(epoch)
        yield from loader
        epoch += 1


def config6_resnet50_from_disk(smoke: bool = False) -> dict:
    """Config-2's model/step fed from a JPEG ImageFolder tree through the
    worker DataLoader (VERDICT r4 #2: every committed TPU number ran
    synthetic input; this measures the same compiled step with the input
    pipeline in the loop). ONE compile serves both timed loops — the
    synthetic-vs-disk gap is decode+transfer cost, nothing else. The
    loader-only rate (no training step) bounds what the host can decode;
    on a single-core host the JPEG path is expected host-bound and the
    measured bound is the honest result (the worker model's scaling with
    real cores is pinned by tests/test_disk_data.py)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.data import DataLoader
    from pytorch_distributed_tpu.data.disk import (
        ImageFolderDataset,
        make_image_transform,
        write_image_folder,
    )
    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    tpu = _full_size(smoke)
    if tpu:
        batch, hw, steps = 128, 224, 10
        n_classes, per_class, img_size = 10, 40, (256, 232)
        workers = 2
    else:
        batch, hw, steps = 8, 64, 3
        n_classes, per_class, img_size = 2, 16, (72, 64)
        workers = 0

    mesh = ptd.init_device_mesh((1,), ("dp",), devices=jax.devices()[:1])
    model = resnet50(
        num_classes=n_classes,
        dtype=jnp.bfloat16 if tpu else jnp.float32, bn_axis_name=None,
    )
    # low lr: this config measures pipeline throughput on noise images;
    # config 2 owns the convergence claim at the training lr
    trainer = Trainer(model, optax.sgd(0.01),
                      DataParallel(mesh), loss_fn=classification_loss,
                      policy="bf16" if tpu else "fp32")
    with tempfile.TemporaryDirectory() as root:
        write_image_folder(
            root, n_classes=n_classes, per_class=per_class, size=img_size,
        )
        ds = ImageFolderDataset(
            root, transform=make_image_transform(hw, train=True)
        )
        loader = DataLoader(
            ds, batch_size=batch, shuffle=True, drop_last=True,
            num_workers=workers, prefetch_factor=2,
            mp_context="spawn",  # jax is live in this process
        )

        # loader-only: the host decode bound, nothing else in the loop
        gen = _cycling_batches(loader)
        next(gen)  # warm the worker pool
        t0 = time.perf_counter()
        seen = 0
        while seen < batch * max(2, steps // 2):
            bx, by = next(gen)
            seen += bx.shape[0]
        loader_rate = seen / (time.perf_counter() - t0)

        # one compiled pipelined program serves both timed loops (the
        # runner is passed back in for the from-disk loop)
        bx, by = next(gen)
        state = trainer.init(jax.random.key(0), (bx, by))
        bd = trainer._place_batch((bx, by))
        dt_syn, state, hist, runner = _timed_steps(
            trainer, state, bd, steps
        )
        first = hist.first()

        # the workers kept prefetching while the synthetic loop ran;
        # drain the queue so the timed loop sees the SUSTAINED decode
        # rate, not up to prefetch*workers pre-decoded free batches
        for _ in range(2 * max(1, workers)):
            next(gen)
        dt_disk, state, hist, _ = _timed_steps(
            trainer, state, next(gen), steps, runner=runner,
            batches=(next(gen) for _ in range(steps)),
        )
        last = hist.last()
    _no_divergence_guard(first, last)
    syn_rate = batch * steps / dt_syn
    disk_rate = batch * steps / dt_disk
    return {
        "config": 6, "name": "resnet50_from_disk",
        "synthetic_images_per_sec": round(syn_rate, 1),
        "from_disk_images_per_sec": round(disk_rate, 1),
        "loader_only_images_per_sec": round(loader_rate, 1),
        "gap_pct": round((1 - disk_rate / syn_rate) * 100, 1),
        "num_workers": workers, "batch": batch, "image_px": hw,
        "host_cores": __import__("os").cpu_count(),
        **_runner_stamp(runner),
    }


def config7_gpt2_from_disk(smoke: bool = False) -> dict:
    """Config-4's GPT-2 step fed from a memmapped token-bin corpus
    (nanoGPT/Megatron format) through the DataLoader. Token windows are
    memmap slices — no decode — so this is the config whose from-disk
    rate should sit within a few percent of synthetic even on a one-core
    host; ``num_workers=0`` is deliberate (a memcpy-bound dataset only
    pays IPC with workers; the worker path is config 6's job)."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.data import DataLoader
    from pytorch_distributed_tpu.data.disk import (
        TokenBinDataset,
        write_token_bin,
    )
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.trainer import Trainer, lm_loss

    tpu = _full_size(smoke)
    if tpu:
        cfg = GPT2Config(dtype=jnp.bfloat16, remat=False)
        B, T, steps = 16, 1024, 20
    else:
        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4)
        B, T, steps = 4, 32, 3

    mesh = ptd.init_device_mesh((1,), ("fsdp",), devices=jax.devices()[:1])
    trainer = Trainer(
        GPT2(cfg), optax.adamw(3e-4, weight_decay=0.01),
        FullyShardedDataParallel(mesh, min_shard_size=8),
        loss_fn=lm_loss, policy="bf16" if tpu else "fp32",
    )
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "corpus.bin")
        n_tok = (B * (steps + 4) + 2) * (T + 1)
        write_token_bin(
            path, rng.integers(0, cfg.vocab_size, n_tok).astype(np.uint16)
        )
        ds = TokenBinDataset(path, seq_len=T)
        loader = DataLoader(ds, batch_size=B, shuffle=True, drop_last=True)
        gen = _cycling_batches(loader)

        t0 = time.perf_counter()
        seen = 0
        while seen < B * steps:
            tok, _ = next(gen)
            seen += tok.shape[0]
        loader_rate = seen * T / (time.perf_counter() - t0)

        tok, tgt = next(gen)
        state = trainer.init(jax.random.key(0), (tok, tgt))
        bd = trainer._place_batch((tok, tgt))
        dt_syn, state, hist, runner = _timed_steps(
            trainer, state, bd, steps
        )
        first = hist.first()

        dt_disk, state, hist, _ = _timed_steps(
            trainer, state, next(gen), steps, runner=runner,
            batches=(next(gen) for _ in range(steps)),
        )
        last = hist.last()
    _no_divergence_guard(first, last)
    syn = B * T * steps / dt_syn
    disk = B * T * steps / dt_disk
    return {
        "config": 7, "name": "gpt2_from_disk",
        "synthetic_tokens_per_sec": round(syn, 1),
        "from_disk_tokens_per_sec": round(disk, 1),
        "loader_only_tokens_per_sec": round(loader_rate, 1),
        "gap_pct": round((1 - disk / syn) * 100, 1),
        "batch": B, "seq_len": T,
        **_runner_stamp(runner),
    }


# -- config #8: GPT-2 350M single-chip headline ----------------------------
def config8_gpt2_350m(smoke: bool = False) -> dict:
    """GPT-2 350M (medium: 24L/1024d/16h) on one chip — transformer MFU
    rises with model size, so this is the stronger matching-or-beating
    headline beyond the 125M shape's measured 0.383 paper-MFU ceiling
    (BASELINE.md r4 decomposition). Vocab-chunked CE is the memory lever
    that fits 350M + AdamW + full activations on one v5e at B=8
    (VERDICT r4 #9); the measured remat ladder (BASELINE.md 350M note):
    full remat 0.309 MFU -> dots_with_no_batch_dims 0.323 ->
    dots_saveable 0.333 -> NO remat 0.364, so this config keeps
    remat=False and ``GPT2Config.remat_policy`` is the documented lever
    for shapes that don't fit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.trainer import (
        Trainer,
        lm_loss,
        lm_loss_chunked,
    )

    tpu = _full_size(smoke)
    if tpu:
        cfg = GPT2Config(
            n_embd=1024, n_layer=24, n_head=16,
            dtype=jnp.bfloat16, remat=False,
        )
        B, T, steps = 8, 1024, 10
        loss_fn = lm_loss_chunked
    else:
        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4, remat=True,
                         remat_policy="dots_saveable")
        B, T, steps = 2, 32, 2
        loss_fn = lm_loss

    mesh = ptd.init_device_mesh((1,), ("fsdp",), devices=jax.devices()[:1])
    trainer = Trainer(
        GPT2(cfg), optax.adamw(3e-4, weight_decay=0.01),
        FullyShardedDataParallel(mesh, min_shard_size=8),
        loss_fn=loss_fn, policy="bf16" if tpu else "fp32",
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    state = trainer.init(jax.random.key(0), (tokens, targets))
    bd = trainer._place_batch((tokens, targets))
    dt, state, hist, runner = _timed_steps(trainer, state, bd, steps)
    _loss_guard(hist.first(), hist.last(), cfg.vocab_size)
    toks = B * T * steps / dt
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(state.params)
    )
    out = {
        "config": 8, "name": "gpt2_350m_single_chip",
        "tokens_per_sec": round(toks, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "batch": B, "seq_len": T, "n_params": int(n_params),
        "remat": bool(cfg.remat), "remat_policy": cfg.remat_policy,
        "loss": "chunked_ce" if tpu else "dense",
        **_runner_stamp(runner),
    }
    if tpu:
        out["mfu"] = round(toks * 6 * n_params / _peak_bf16_flops(), 4)
    return out


# -- config #9: KV-cached decode (serving) ---------------------------------
def _decode_bench(model, variables, vocab: int, n_slots: int, max_len: int,
                  prefill_len: int, prompt_len: int, steps: int) -> dict:
    """Steady-state decode at a fixed slot count: prefill every slot, one
    warm step (compile excluded), then a timed chain of full-batch decode
    steps. Every step is closed by the host fetch of the sampled tokens —
    that sync IS the serving pattern (the scheduler needs the ids for
    EOS/join-evict), so the per-step latency here is the honest per-token
    (inter-token) latency a request experiences."""
    import numpy as np

    from pytorch_distributed_tpu.observability import LatencyTracker
    from pytorch_distributed_tpu.serving import InferenceEngine

    eng = InferenceEngine(model, variables, n_slots=n_slots,
                          max_len=max_len, prefill_len=prefill_len)
    cache = eng.init_cache()
    rng = np.random.default_rng(0)
    last = np.zeros(n_slots, np.int32)
    active = np.ones(n_slots, bool)
    for s in range(n_slots):
        cache, tok = eng.prefill(
            cache, s, rng.integers(0, vocab, prompt_len)
        )
        last[s] = tok
    cache, last = eng.decode(cache, last, active)  # compile + warm
    lat = LatencyTracker()
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        cache, last = eng.decode(cache, last, active)
        lat.add(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    return {
        "n_slots": n_slots,
        "cache_kind": eng.cache_kind,
        "tokens_per_sec": round(n_slots * steps / dt, 1),
        "per_token_p50_ms": round(lat.percentile(50) * 1e3, 3),
        "per_token_p99_ms": round(lat.percentile(99) * 1e3, 3),
        "steps": steps,
    }


def _spec_decode_bench(model, variables, vocab: int, n_slots: int,
                       max_len: int, prefill_len: int, prompt_len: int,
                       steps: int, spec_k: int, draft_layers: int) -> dict:
    """Steady-state SPECULATIVE decode: same harness shape as
    ``_decode_bench`` but each timed step is one draft(k)+verify round, so
    the step emits 1..k+1 tokens per slot. The host fetch of the emitted
    tokens + accept counts closes the chain (the scheduler needs both).
    Reports the two efficiency numbers that define speculative decoding:
    accept-rate (accepted drafts / proposed drafts) and target forwards
    per generated token (1 / mean span — the <1.0 figure is the win)."""
    import numpy as np

    from pytorch_distributed_tpu.serving import InferenceEngine

    eng = InferenceEngine(model, variables, n_slots=n_slots,
                          max_len=max_len, prefill_len=prefill_len,
                          spec_k=spec_k, draft_layers=draft_layers)
    cache = eng.init_cache()
    dcache = eng.init_draft_cache()
    rng = np.random.default_rng(0)
    last = np.zeros(n_slots, np.int32)
    prev = np.zeros(n_slots, np.int32)
    active = np.ones(n_slots, bool)
    for s in range(n_slots):
        prompt = rng.integers(0, vocab, prompt_len)
        cache, tok = eng.prefill(cache, s, prompt)
        last[s] = tok
        prev[s] = int(prompt[-1])

    def advance(last, prev, emitted, counts, prev_next):
        for s in range(n_slots):
            last[s] = emitted[s, int(counts[s]) - 1]
        return last, np.asarray(prev_next, np.int32).copy()

    # compile + warm (excluded from timing)
    cache, dcache, emitted, counts, prev_next = eng.spec_decode(
        cache, dcache, last, prev, active
    )
    last, prev = advance(last, prev, emitted, counts, prev_next)
    from pytorch_distributed_tpu.observability import LatencyTracker

    tokens = 0
    accepted = 0
    lat = LatencyTracker()
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        cache, dcache, emitted, counts, prev_next = eng.spec_decode(
            cache, dcache, last, prev, active
        )
        lat.add(time.perf_counter() - t1)
        last, prev = advance(last, prev, emitted, counts, prev_next)
        tokens += int(np.asarray(counts).sum())
        accepted += int(np.asarray(counts).sum()) - n_slots
    dt = time.perf_counter() - t0
    # one verify program per step advances every slot: slot-forwards =
    # steps * n_slots; spec efficiency is forwards/token < 1
    fwd_per_tok = steps * n_slots / tokens if tokens else float("inf")
    return {
        "n_slots": n_slots, "spec_k": spec_k,
        "cache_kind": eng.cache_kind,
        "draft_layers": draft_layers,
        "tokens_per_sec": round(tokens / dt, 1),
        "accept_rate": round(accepted / (steps * n_slots * spec_k), 4),
        "target_forwards_per_token": round(fwd_per_tok, 4),
        "mean_tokens_per_step": round(tokens / (steps * n_slots), 3),
        "per_step_p50_ms": round(lat.percentile(50) * 1e3, 3),
        "steps": steps,
    }


def _multihost_bench(model, variables, vocab: int, n_hosts: int,
                     n_slots: int, max_len: int, prefill_len: int,
                     prompt_len: int, n_requests: int,
                     max_new: int) -> dict:
    """Router + N in-process host workers over a HashStore: end-to-end
    request throughput THROUGH the control plane (admission, routing,
    chunked reassembly), not raw decode — compare against the same-shape
    ``_decode_bench`` row to read the control-plane overhead. Stamped
    with ``platform`` like every config-9 row: a CPU harness number can
    never be quoted as multi-host TPU serving throughput."""
    import threading

    import jax
    import numpy as np

    from pytorch_distributed_tpu.distributed.store import HashStore
    from pytorch_distributed_tpu.serving import (
        InferenceEngine, Request, Scheduler,
    )
    from pytorch_distributed_tpu.serving.multihost import HostWorker, Router

    store = HashStore()
    workers = []
    for i in range(n_hosts):
        eng = InferenceEngine(model, variables, n_slots=n_slots,
                              max_len=max_len, prefill_len=prefill_len)
        workers.append(HostWorker(
            store, Scheduler(eng, emit_events=False), host_id=f"host{i}",
            emit_events=False,
        ))
    threads = [
        threading.Thread(target=w.serve_forever, daemon=True)
        for w in workers
    ]
    for t in threads:
        t.start()
    router = Router(store, emit_events=False)
    rng = np.random.default_rng(0)
    # warmup: one tiny request per host so jit compile (prefill + decode
    # programs on every worker) lands outside the timed window — the row
    # is meant to be comparable against the same-slots _decode_bench row
    from pytorch_distributed_tpu.observability import LatencyTracker
    for _ in range(n_hosts):
        router.submit(Request(
            prompt=rng.integers(0, vocab, prompt_len), max_new_tokens=2,
        ))
    router.run(timeout_s=600)
    router.request_latency = LatencyTracker()
    router.ttft = LatencyTracker()
    pre = router.stats()
    for _ in range(n_requests):
        router.submit(Request(
            prompt=rng.integers(0, vocab, prompt_len),
            max_new_tokens=max_new,
        ))
    t0 = time.perf_counter()
    finished = router.run(timeout_s=600)
    dt = time.perf_counter() - t0
    router.stop_hosts()
    for t in threads:
        t.join(timeout=60)
    stats = router.stats()
    total_tokens = sum(len(f.tokens) for f in finished)
    return {
        "platform": jax.devices()[0].platform,
        "n_hosts": n_hosts,
        "cache_kind": workers[0].scheduler.engine.cache_kind,
        "n_slots_per_host": n_slots,
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "tokens_per_sec": round(total_tokens / dt, 1),
        "request_p50_ms": round(stats["request_p50_s"] * 1e3, 1),
        "request_p99_ms": round(stats["request_p99_s"] * 1e3, 1),
        # deltas over the warmup pass: only the timed batch counts
        "routed": stats["routed"] - pre["routed"],
        "rebalances": stats["rebalances"] - pre["rebalances"],
        "per_host_routed": {
            h: n - pre["per_host_routed"].get(h, 0)
            for h, n in stats["per_host_routed"].items()
        },
    }


def _redistribute_bench(model, variables, n_swaps: int = 5) -> dict:
    """Planner cost model + measured wall time of the two redistribution
    moves serving actually makes: the train→serve reshard (FSDP-style
    dim-0/dp layout → Megatron-TP serving layout, the reshard-on-load
    transfer) and the reshard-while-serving weight swap
    (``InferenceEngine.swap_params``, dp layout → the engine's current
    placement, timed over ``n_swaps`` repeats). The cost numbers come
    straight from ``plan_tree`` — bytes moved and peak live bytes per
    device against the naive gather-then-slice baseline the planner
    displaces — so the report can assert the planner's peak advantage
    with the same numbers the tests do. Stamped with ``platform``."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.mesh import init_device_mesh
    from pytorch_distributed_tpu.observability import LatencyTracker
    from pytorch_distributed_tpu.redistribute import (
        plan_tree, redistribute_tree,
    )
    from pytorch_distributed_tpu.serving import (
        InferenceEngine, gpt2_param_shardings, serving_mesh,
    )

    n_dev = len(jax.devices())
    train_mesh = init_device_mesh((n_dev,), ("dp",))

    def fsdp_place(x):
        if x.ndim >= 1 and x.shape[0] % n_dev == 0:
            return NamedSharding(train_mesh.jax_mesh, P("dp"))
        return NamedSharding(train_mesh.jax_mesh, P())

    params = variables["params"]
    src_shardings = jax.tree_util.tree_map(fsdp_place, params)
    train_params = redistribute_tree(params, src_shardings)

    # train→serve reshard: the reshard-on-load transfer, planned
    smesh = serving_mesh(dp=1, tp=n_dev)
    dst_shardings = gpt2_param_shardings(params, smesh)
    plan = plan_tree(train_params, dst_shardings)
    ops: dict = {}
    for p in plan.leaves:
        for op in p.ops:
            ops[op] = ops.get(op, 0) + 1

    # reshard-while-serving: timed swap_params onto a live engine
    eng = InferenceEngine(model, variables, n_slots=2,
                          max_len=32, prefill_len=8)
    swap_cost = eng.swap_params({"params": train_params})  # warm
    lat = LatencyTracker()
    for _ in range(n_swaps):
        t0 = time.perf_counter()
        eng.swap_params({"params": train_params})
        lat.add(time.perf_counter() - t0)

    mib = 1 / (1024 * 1024)
    return {
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev,
        "reshard_ops": ops,
        "reshard_bytes_moved_mib": round(plan.cost.bytes_moved * mib, 3),
        "reshard_peak_mib": round(plan.cost.peak_bytes * mib, 3),
        "reshard_naive_peak_mib": round(
            plan.cost.naive_gather_bytes * mib, 3
        ),
        "reshard_peak_over_naive": round(
            plan.cost.peak_bytes / max(1, plan.cost.naive_gather_bytes), 3
        ),
        "swap_bytes_moved_mib": round(swap_cost.bytes_moved * mib, 3),
        "swap_p50_ms": round(lat.percentile(50) * 1e3, 2),
        "swap_p99_ms": round(lat.percentile(99) * 1e3, 2),
        "n_swaps": n_swaps,
    }


def _paged_capacity_bench(model, variables, vocab: int, *, page_size: int,
                          budget_pages: int, max_len: int, prefill_len: int,
                          prompt_lens, max_new: int,
                          n_requests: int) -> dict:
    """Concurrent sequences at a FIXED KV page budget, slotted vs paged.

    Both engines get the same physical budget (``budget_pages`` pages of
    ``page_size`` positions per layer). The slotted cache spends it in
    whole-``max_len`` slot reservations, so its concurrency is
    ``budget_pages // pages(max_len)`` no matter how short the requests
    are; the paged cache reserves each request's worst-case span
    (prompt + budget), so mixed-length traffic packs strictly more
    sequences into the same HBM. Peak concurrency is read off the live
    scheduler each step — same admission code production runs, not a
    formula."""
    import numpy as np

    from pytorch_distributed_tpu.serving import (
        InferenceEngine, Request, Scheduler,
    )

    max_pages = -(-max_len // page_size)

    def run(kind: str) -> dict:
        if kind == "slotted":
            n_slots = max(1, budget_pages // max_pages)
            eng = InferenceEngine(
                model, variables, n_slots=n_slots, max_len=max_len,
                prefill_len=prefill_len, cache_kind="slotted",
            )
        else:
            eng = InferenceEngine(
                model, variables, n_slots=n_requests, max_len=max_len,
                prefill_len=prefill_len, cache_kind="paged",
                page_size=page_size, n_pages=budget_pages + 1,  # + trash
            )
        sched = Scheduler(eng, emit_events=False)
        rng = np.random.default_rng(0)
        for i in range(n_requests):
            sched.submit(Request(
                prompt=rng.integers(0, vocab, prompt_lens[i % len(prompt_lens)]),
                max_new_tokens=max_new,
            ))
        peak = 0
        t0 = time.perf_counter()
        finished = []
        while sched.has_work:
            finished.extend(sched.step())
            peak = max(peak, sched.n_active)
        dt = time.perf_counter() - t0
        toks = sum(len(f.tokens) for f in finished)
        return {"cache_kind": kind, "peak_concurrent": peak,
                "wall_s": round(dt, 3), "tokens": toks}

    slotted = run("slotted")
    paged = run("paged")
    return {
        "budget_pages": budget_pages, "page_size": page_size,
        "max_len": max_len, "prompt_lens": list(prompt_lens),
        "max_new_tokens": max_new, "n_requests": n_requests,
        "slotted": slotted, "paged": paged,
        "capacity_ratio": round(
            paged["peak_concurrent"] / max(1, slotted["peak_concurrent"]), 2
        ),
    }


def _cached_prefix_ttft_bench(model, variables, vocab: int, *,
                              page_size: int, max_len: int,
                              prefill_len: int, prompt_len: int,
                              n_repeats: int) -> dict:
    """TTFT of a radix-cached prompt vs the same prompt cold (paged cache).

    Warmup admissions compile BOTH prefill buckets first (the full-prompt
    bucket and the uncached-tail bucket a radix hit shrinks to), so the
    cold/cached delta measures the prefill compute + admission path, not
    jit. The cached figure is the shared-system-prompt serving win: the
    hit skips the shared span's forward entirely and pads only the tail
    to its (much smaller) power-of-two bucket."""
    import numpy as np

    from pytorch_distributed_tpu.observability import LatencyTracker
    from pytorch_distributed_tpu.serving import (
        InferenceEngine, Request, Scheduler,
    )

    max_pages = -(-max_len // page_size)
    chain_pages = prompt_len // page_size
    # pool sized so the radix-pinned cold chains never force reclaim
    # into the timed admissions
    n_pages = 1 + 2 * max_pages + (n_repeats + 2) * chain_pages
    eng = InferenceEngine(
        model, variables, n_slots=2, max_len=max_len,
        prefill_len=prefill_len, cache_kind="paged", page_size=page_size,
        n_pages=n_pages,
    )
    sched = Scheduler(eng, emit_events=False)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, vocab, prompt_len)
    cached_len = max(0, (prompt_len // page_size) * page_size)
    if cached_len >= prompt_len:
        cached_len = prompt_len - 1
    tail = prompt_len - cached_len

    def admit(p) -> float:
        rid = sched.submit(Request(prompt=p, max_new_tokens=2))
        done = sched.run()
        return next(f.ttft_s for f in done if f.request_id == rid)

    # compile the full bucket and the tail bucket outside the timed part
    admit(rng.integers(0, vocab, prompt_len))
    admit(rng.integers(0, vocab, tail))
    cold_lat, hit_lat = LatencyTracker(), LatencyTracker()
    for _ in range(n_repeats):  # distinct prompts: full-bucket prefill
        cold_lat.add(admit(rng.integers(0, vocab, prompt_len)))
    cold_lat.add(admit(prompt))  # first sight of THE measured prompt
    for _ in range(n_repeats):
        hit_lat.add(admit(prompt))  # radix hit: tail-bucket prefill only
    cold = cold_lat.percentile(50)
    hit = hit_lat.percentile(50)
    return {
        "cache_kind": "paged", "page_size": page_size,
        "prompt_len": prompt_len, "cached_len": cached_len,
        "ttft_cold_p50_ms": round(cold * 1e3, 3),
        "ttft_cached_p50_ms": round(hit * 1e3, 3),
        "ttft_speedup": round(cold / max(hit, 1e-9), 2),
        "radix_hits": sched.radix.hits,
        "n_repeats": n_repeats,
    }


def config9_gpt2_decode(smoke: bool = False) -> dict:
    """Serving-path decode: tokens/s + per-token latency percentiles of the
    KV-cached engine at several slot (batch) counts, plus a speculative
    (self-drafting) sweep at the largest slot count. Throughput should grow
    near-linearly with slots while per-token latency stays near-flat until
    the chip saturates — the continuous-batching capacity curve. The spec
    rows report accept-rate and target-forwards-per-token (<1 is the spec
    win; note the random-init weights make drafts easy to predict only
    insofar as the truncated stack agrees with the full stack).

    The result dict is stamped with ``platform`` so a CPU smoke number can
    never be quoted as TPU serving throughput downstream."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import GPT2, GPT2Config

    tpu = _full_size(smoke)
    if tpu:
        cfg = GPT2Config(dtype=jnp.bfloat16)  # the 125M serving shape
        slot_counts = (1, 8, 32)
        max_len, prefill_len, prompt_len, steps = 384, 128, 96, 128
        spec_variants = ((2, 3), (3, 3))     # (spec_k, draft_layers of 12)
        spec_slots, spec_steps = 32, 64      # k+1 positions/step: fits 384
    else:
        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4)
        slot_counts = (1, 4)
        max_len, prefill_len, prompt_len, steps = 64, 16, 8, 12
        spec_variants = ((2, 1), (3, 1))     # (spec_k, draft_layers of 2)
        spec_slots, spec_steps = 4, 12

    model = GPT2(cfg)
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    sweeps = [
        _decode_bench(model, variables, cfg.vocab_size, s, max_len,
                      prefill_len, prompt_len, steps)
        for s in slot_counts
    ]
    # speculative sweep: size the cache so steps * (k+1) positions fit
    spec_sweeps = []
    for k, dl in spec_variants:
        need = prompt_len + 1 + (spec_steps + 1) * (k + 1)
        spec_sweeps.append(_spec_decode_bench(
            model, variables, cfg.vocab_size, spec_slots,
            max(max_len, need), prefill_len, prompt_len, spec_steps,
            k, dl,
        ))
    # multi-host variant: the same model behind the admission router +
    # two in-process host workers over a HashStore — measures the full
    # control-plane path (routing, chunked streaming, reassembly); read
    # the overhead against the same-slot-count _decode_bench row above
    if tpu:
        mh_slots, mh_requests, mh_max_new = 8, 16, 32
    else:
        mh_slots, mh_requests, mh_max_new = 2, 6, 8
    multihost = _multihost_bench(
        model, variables, cfg.vocab_size, 2, mh_slots, max_len,
        prefill_len, prompt_len, mh_requests, mh_max_new,
    )
    # redistribution: planner cost of the train→serve reshard + timed
    # reshard-while-serving swap (the live weight-update path)
    redistribute = _redistribute_bench(model, variables)
    # paged KV cache: (a) concurrent sequences at a fixed page budget —
    # the memory-capacity win of page-granular reservations over
    # whole-slot ones; (b) TTFT of a radix-cached shared prefix vs the
    # same prompt cold — the prefix-sharing latency win
    if tpu:
        capacity = _paged_capacity_bench(
            model, variables, cfg.vocab_size, page_size=16,
            budget_pages=96, max_len=max_len, prefill_len=prefill_len,
            prompt_lens=(32, 64, 96), max_new=32, n_requests=24,
        )
        cached_ttft = _cached_prefix_ttft_bench(
            model, variables, cfg.vocab_size, page_size=16,
            max_len=max_len, prefill_len=prefill_len, prompt_len=94,
            n_repeats=5,
        )
    else:
        capacity = _paged_capacity_bench(
            model, variables, cfg.vocab_size, page_size=4,
            budget_pages=48, max_len=max_len, prefill_len=prefill_len,
            prompt_lens=(4, 8, 16), max_new=8, n_requests=12,
        )
        # full prefill bucket (64) vs the 8-wide tail bucket a radix hit
        # shrinks to — wide enough asymmetry to measure on CPU
        cached_ttft = _cached_prefix_ttft_bench(
            model, variables, cfg.vocab_size, page_size=4,
            max_len=max_len, prefill_len=max_len, prompt_len=62,
            n_repeats=3,
        )
    return {
        "config": 9, "name": "gpt2_decode",
        "platform": jax.devices()[0].platform,
        "sweeps": sweeps,
        "spec_sweeps": spec_sweeps,
        "multihost": multihost,
        "redistribute": redistribute,
        "paged_capacity": capacity,
        "cached_prefix_ttft": cached_ttft,
        "max_len": max_len, "prefill_len": prefill_len,
        "prompt_len": prompt_len,
    }


CONFIGS = {
    1: config1_resnet18_cifar,
    2: config2_resnet50_dp_scaling,
    3: config3_amp_accum,
    4: config4_gpt2_fsdp,
    5: config5_elastic_restart,
    6: config6_resnet50_from_disk,
    7: config7_gpt2_from_disk,
    8: config8_gpt2_350m,
    9: config9_gpt2_decode,
}


def _dispatch_ms_per_program() -> float:
    """Fixed host cost of launching ONE XLA program, from a tiny
    dependent chain whose compute is ~zero (perf/dispatch_probe.py is
    the full-budget version). Stamped top-level so every config row's
    ``programs_per_step`` can be priced in milliseconds."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda v: v + 1.0)
    v = tiny(jnp.zeros((8,), jnp.float32))
    v.block_until_ready()
    n = 100
    t0 = time.perf_counter()
    for _ in range(n):
        v = tiny(v)
    dt = time.perf_counter() - t0
    v.block_until_ready()  # drain before the configs reuse the device
    return round(dt / n * 1e3, 3)


def _memory_per_chip_stamp(dp: int = 8) -> dict:
    """Per-strategy params/opt/grad bytes per chip for the ResNet-50 path
    (perf/memory_probe.py). Dryrun spec arithmetic — no arrays, so it is
    stamped even on a single-chip host: the dp=8 sharding math is exact
    regardless of what hardware ran the timings."""
    import importlib.util
    import pathlib

    probe_path = (pathlib.Path(__file__).resolve().parent.parent
                  / "perf" / "memory_probe.py")
    spec = importlib.util.spec_from_file_location("memory_probe", probe_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.probe(model="resnet50", dp=dp)


def _ir_audit_stamp() -> dict:
    """graftir (analysis/ir) fast-grid audit summary: were the step
    programs this matrix times actually clean — strategy-signature
    collective budget, donation realized in ``input_output_alias``, one
    program/executable per step — and what tensor-grade bytes they put
    on the wire. The full numbers live in ``analysis/ir/BUDGET.json``;
    this stamp records the platform-local verdict next to the timings
    it vouches for."""
    from pytorch_distributed_tpu.analysis.ir import run_audit

    report = run_audit("fast")
    programs = {}
    for name, entry in report.entries.items():
        tensor = entry["collectives"]["tensor"]
        programs[name] = {
            "tensor_collective_bytes": {
                k: v["bytes"] for k, v in sorted(tensor.items())
            },
            "donation": (
                f"{entry['donation']['realized']}"
                f"/{entry['donation']['donated']}"
            ),
            "programs_per_step": entry["runner"]["programs_per_step"],
            "executables": entry["runner"]["executables"],
        }
    return {
        "platform": report.platform,
        "clean": report.clean,
        "findings": len(report.findings),
        "programs": programs,
    }


def run_matrix(only=None, *, smoke: bool = False) -> dict:
    import platform as _platform

    import jax

    try:
        memory_stamp = _memory_per_chip_stamp()
    except Exception as e:  # never let the stamp sink the matrix
        memory_stamp = {"error": f"{type(e).__name__}: {e}"}
    try:
        ir_stamp = _ir_audit_stamp()
    except Exception as e:
        ir_stamp = {"error": f"{type(e).__name__}: {e}"}
    results = {
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "n_devices": len(jax.devices()),
        "host": _platform.node(),
        "dispatch_ms_per_program": _dispatch_ms_per_program(),
        "memory_per_chip": memory_stamp,
        "ir_audit": ir_stamp,
        "configs": {},
    }
    for idx, fn in CONFIGS.items():
        if only and idx not in only:
            continue
        try:
            results["configs"][str(idx)] = fn(smoke=smoke)
        except Exception as e:  # recorded; __main__ exits non-zero on any
            results["configs"][str(idx)] = {
                "config": idx, "error": f"{type(e).__name__}: {e}",
            }
    return results


def main(argv=None) -> int:
    import argparse
    import pathlib

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*", type=int,
                    help="config numbers to run (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="harness-only smoke shapes (any platform)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    only = set(args.configs) or None
    res = run_matrix(only, smoke=args.smoke)
    failed = sorted(
        k for k, c in res["configs"].items() if "error" in c
    )
    out = (pathlib.Path(__file__).parent
           / f"results_{res['platform']}.json")
    if only:
        # merge into an existing file rather than dropping other configs
        if out.exists():
            prev = json.loads(out.read_text())
            prev["configs"].update(res["configs"])
            prev.update({k: v for k, v in res.items() if k != "configs"})
            res = prev
    out.write_text(json.dumps(res, indent=2) + "\n")
    print(json.dumps(res, indent=2))
    if failed:
        print(f"benchmarks.matrix: configs {failed} failed",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
