"""DDP ResNet training — BASELINE.json configs #1/#2/#3/#5.

The reference's ``train.py`` equivalent (SURVEY.md L7), composing every
layer: jax-distributed bootstrap -> DeviceMesh -> DistributedSampler ->
DataLoader -> Trainer (DP strategy, optional AMP + grad accumulation) ->
CheckpointManager save/resume -> tpurun restart contract.

Single process (config #1)::

    python examples/train_resnet_ddp.py --model resnet18 --dataset cifar10

Multi-process / multi-node elastic (configs #2/#5) — workers join one XLA
runtime via the tpurun env contract, each feeding its sampler shard::

    tpurun --standalone --nproc-per-node 1 examples/train_resnet_ddp.py
    tpurun --nnodes 2 ... examples/train_resnet_ddp.py

AMP + accumulation (config #3)::

    python examples/train_resnet_ddp.py --policy bf16 --grad-accum 2

On restart (TPURUN_RESTART_COUNT > 0) training resumes from the newest
checkpoint in --ckpt-dir; resume is idempotent so fresh runs may point at
an empty directory.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet18",
                   choices=["resnet18", "resnet34", "resnet50", "resnet101"])
    p.add_argument("--dataset", default="cifar10",
                   choices=["cifar10", "imagenet"])
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=None,
                   help="cap steps per epoch (synthetic data is infinite-ish)")
    p.add_argument("--global-batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--policy", default="fp32",
                   choices=["fp32", "bf16", "fp16"])
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--clip-norm", type=float, default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--dataset-size", type=int, default=512)
    p.add_argument("--data-dir", default=None,
                   help="ImageFolder root (class-per-subdir of JPEGs) — "
                        "real decode+augment path; default is synthetic")
    p.add_argument("--num-workers", type=int, default=0,
                   help="DataLoader worker processes (JPEG decode)")
    p.add_argument("--mp-context", default="spawn",
                   choices=["fork", "spawn"],
                   help="worker start method. This script initialises JAX "
                        "before it builds the loader, so the default is "
                        "spawn: fork()ing a process that holds the chip "
                        "copies its runtime threads' locks into the child")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prefetch", type=int, default=2,
                   help="loader prefetch depth (0 = synchronous)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)

    import pytorch_distributed_tpu.distributed as dist

    # joins the global XLA runtime under tpurun (no-op single-process);
    # MUST run before any other jax API touches the backend
    dist.initialize_jax_distributed()

    import jax
    import jax.numpy as jnp
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_tpu.compile_cache import enable_compile_cache
    from pytorch_distributed_tpu.data import (
        DataLoader,
        DistributedSampler,
        SyntheticCIFAR10,
        SyntheticImageNet,
        shard_batch_for_mesh,
    )
    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.observability import IterationLogger
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.trainer import Trainer, classification_loss

    enable_compile_cache()
    nproc = jax.process_count()
    pid = jax.process_index()
    restart_count = int(os.environ.get("TPURUN_RESTART_COUNT", "0"))

    mesh = ptd.init_device_mesh((len(jax.devices()),), ("dp",))
    print(f"[rank {pid}] {nproc} process(es), {len(jax.devices())} "
          f"{jax.devices()[0].platform} device(s), local "
          f"{[d.id for d in jax.local_devices()]}", flush=True)

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if (on_tpu and args.policy != "fp32") else jnp.float32
    if args.data_dir:
        from pytorch_distributed_tpu.data import (
            ImageFolderDataset,
            make_image_transform,
        )

        size = 32 if args.dataset == "cifar10" else 224
        dataset = ImageFolderDataset(
            args.data_dir,
            transform=make_image_transform(size, train=True,
                                           seed=args.seed),
        )
        n_classes = len(dataset.classes)
        model = getattr(models, args.model)(
            num_classes=n_classes,
            cifar_stem=args.dataset == "cifar10", dtype=dtype,
        )
    elif args.dataset == "cifar10":
        dataset = SyntheticCIFAR10(args.dataset_size, seed=args.seed)
        model = getattr(models, args.model)(
            num_classes=10, cifar_stem=True, dtype=dtype
        )
        n_classes = 10
    else:
        dataset = SyntheticImageNet(args.dataset_size, seed=args.seed)
        model = getattr(models, args.model)(num_classes=1000, dtype=dtype)
        n_classes = 1000

    trainer = Trainer(
        model,
        optax.sgd(args.lr, momentum=args.momentum),
        DataParallel(mesh),
        loss_fn=classification_loss,
        policy=args.policy,
        grad_accum_steps=args.grad_accum,
        clip_norm=args.clip_norm,
    )

    sampler = DistributedSampler(
        dataset, num_replicas=nproc, rank=pid, shuffle=True, seed=args.seed
    )
    if args.global_batch % (nproc * args.grad_accum):
        raise SystemExit(
            "--global-batch must divide by process count * grad accum"
        )
    loader = DataLoader(
        dataset, batch_size=args.global_batch // nproc,
        sampler=sampler, drop_last=True,
        prefetch_factor=args.prefetch,
        num_workers=args.num_workers,
        mp_context=args.mp_context,
    )

    sample = dataset[0]
    state = trainer.init(jax.random.key(args.seed),
                         tuple(np.asarray(a)[None] for a in sample))

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, max_to_keep=3)
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(
                state, shardings=trainer.state_shardings
            )
            print(f"[rank {pid}] resumed from step {latest} "
                  f"(restart #{restart_count})", flush=True)

    log = IterationLogger(sample_rate=args.log_every)
    step = int(state.step)
    steps_per_epoch = args.steps_per_epoch or (
        len(sampler) // (args.global_batch // nproc)
    )
    if steps_per_epoch < 1:
        raise SystemExit(
            f"dataset shard ({len(sampler)} examples) smaller than the "
            f"per-process batch ({args.global_batch // nproc}) — nothing "
            f"to train on; grow --dataset-size or shrink --global-batch"
        )
    start_epoch = step // max(steps_per_epoch, 1)
    metrics = None

    for epoch in range(start_epoch, args.epochs):
        loader.set_epoch(epoch)  # forwards to sampler + dataset (augmentation redraw)
        for i, batch in enumerate(loader):
            if i >= steps_per_epoch:
                break
            placed = shard_batch_for_mesh(
                batch, mesh, "dp", global_batch=(nproc == 1)
            )
            log.start_iteration()
            state, metrics = trainer.step(state, placed)
            step = int(state.step)
            log.end_iteration(loss=float(metrics["loss"]))
            if step % args.log_every == 0:
                print(f"[rank {pid}] step {step} "
                      f"loss {float(metrics['loss']):.4f}", flush=True)
            if ckpt and step % args.ckpt_every == 0:
                ckpt.save(step, state)
        if metrics is not None:
            print(f"[rank {pid}] epoch {epoch} done at step {step} "
                  f"loss {float(metrics['loss']):.4f}", flush=True)

    if ckpt:
        ckpt.save(step, state)
        ckpt.wait_until_finished()
        ckpt.close()
    dist.shutdown_jax_distributed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
