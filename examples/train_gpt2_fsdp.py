"""FSDP GPT-2 language-model training — BASELINE.json config #4.

The reference's FSDP ``main.py`` equivalent: GPT-2 (125M by default) with
params/grads/optimizer state sharded over the ``fsdp`` mesh axis (torch
FULL_SHARD semantics, expressed as GSPMD shardings), AdamW, LM loss over
synthetic WikiText-shaped token streams, sharded checkpoints with
reshard-on-load, tpurun restart contract.

Single host (all local devices on the fsdp axis)::

    python examples/train_gpt2_fsdp.py --layers 2 --embd 128 --seq-len 128

Multi-process (each worker joins the global runtime; mesh spans hosts)::

    tpurun --nnodes 2 ... examples/train_gpt2_fsdp.py
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--embd", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--vocab", type=int, default=50257)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--policy", default="bf16", choices=["fp32", "bf16"])
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (HBM for FLOPs)")
    p.add_argument("--dp", type=int, default=1,
                   help="extra pure-DP axis size (mesh = dp x fsdp)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--dataset-size", type=int, default=2048)
    p.add_argument("--data-bin", default=None,
                   help="binary token corpus (TokenBinDataset format: raw "
                        "little-endian uint16 tokens); default synthetic")
    p.add_argument("--num-workers", type=int, default=0,
                   help="DataLoader worker processes")
    p.add_argument("--mp-context", default="spawn",
                   choices=["fork", "spawn"],
                   help="worker start method. This script initialises JAX "
                        "before it builds the loader, so the default is "
                        "spawn: fork()ing a process that holds the chip "
                        "copies its runtime threads' locks into the child")
    p.add_argument("--chunked-loss", type=int, default=0, metavar="N",
                   help="use the vocab-chunked CE with N chunks (memory "
                        "path: long-T / big-V / B beyond the dense-loss "
                        "compile limit — see BASELINE.md r4 decomposition)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prefetch", type=int, default=2,
                   help="loader prefetch depth (0 = synchronous)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="routed experts per MoE block (0 = dense); expert "
                        "params shard over an 'ep' axis when --ep > 1")
    p.add_argument("--moe-top-k", type=int, default=2)
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel axis size (MoE only)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)

    import pytorch_distributed_tpu.distributed as dist

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
        SyntheticLMDataset,
        shard_batch_for_mesh,
    )
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.trainer import Trainer, lm_loss

    enable_compile_cache()
    nproc = jax.process_count()
    pid = jax.process_index()
    restart_count = int(os.environ.get("TPURUN_RESTART_COUNT", "0"))

    n_dev = len(jax.devices())
    if args.moe_experts:
        if args.moe_top_k > args.moe_experts:
            raise SystemExit(
                f"--moe-top-k {args.moe_top_k} > --moe-experts "
                f"{args.moe_experts}"
            )
    elif args.ep > 1:
        raise SystemExit("--ep needs --moe-experts > 0 (dense model)")
    if args.moe_experts and args.ep > 1:
        if n_dev % args.ep:
            raise SystemExit("--ep must divide the device count")
        if args.moe_experts % args.ep:
            raise SystemExit(
                f"--moe-experts {args.moe_experts} must divide by "
                f"--ep {args.ep} (expert dim shards over the ep axis)"
            )
        if args.dp not in (1, n_dev // args.ep):
            raise SystemExit(
                f"--dp {args.dp} conflicts with the MoE mesh: dp axis is "
                f"device_count/ep = {n_dev // args.ep}"
            )
        mesh = ptd.init_device_mesh(
            (n_dev // args.ep, args.ep), ("dp", "ep")
        )
    else:
        if n_dev % args.dp:
            raise SystemExit("--dp must divide the device count")
        mesh = ptd.init_device_mesh(
            (args.dp, n_dev // args.dp), ("dp", "fsdp")
        )

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = GPT2Config(
        vocab_size=args.vocab,
        n_positions=args.seq_len,
        n_embd=args.embd,
        n_layer=args.layers,
        n_head=args.heads,
        dtype=jnp.bfloat16 if (on_tpu and args.policy == "bf16")
        else jnp.float32,
        remat=args.remat,
        moe_experts=args.moe_experts,
        moe_top_k=args.moe_top_k,
    )
    if args.moe_experts and args.ep > 1:
        from pytorch_distributed_tpu.parallel import ExpertDataParallel

        strategy = ExpertDataParallel(mesh)
    else:
        strategy = FullyShardedDataParallel(
            mesh, dp_axis="dp" if args.dp > 1 else None, min_shard_size=8
        )
    if args.chunked_loss:
        from pytorch_distributed_tpu.trainer import make_chunked_lm_loss

        loss_fn = make_chunked_lm_loss(args.chunked_loss)
    else:
        loss_fn = lm_loss
    trainer = Trainer(
        GPT2(cfg),
        optax.adamw(args.lr, weight_decay=args.weight_decay),
        strategy,
        loss_fn=loss_fn,
        policy=args.policy if on_tpu else "fp32",
    )

    if args.data_bin:
        from pytorch_distributed_tpu.data import TokenBinDataset

        # vocab_size triggers the corpus/tokenizer range check (jit
        # gathers clamp out-of-range ids silently)
        dataset = TokenBinDataset(
            args.data_bin, seq_len=args.seq_len, vocab_size=args.vocab
        )
    else:
        dataset = SyntheticLMDataset(
            args.dataset_size, seq_len=args.seq_len, seed=args.seed
        )
        dataset.vocab_size = min(args.vocab, dataset.vocab_size)
    sampler = DistributedSampler(
        dataset, num_replicas=nproc, rank=pid, shuffle=True, seed=args.seed
    )
    loader = DataLoader(
        dataset, batch_size=args.global_batch // nproc,
        sampler=sampler, drop_last=True,
        prefetch_factor=args.prefetch,
        num_workers=args.num_workers,
        mp_context=args.mp_context,
    )

    sample = dataset[0]
    state = trainer.init(
        jax.random.key(args.seed),
        tuple(np.asarray(a)[None] for a in sample),
    )
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(state.params)
    )
    if pid == 0:
        print(f"GPT-2: {n_params / 1e6:.1f}M params, mesh "
              f"{mesh.shape}", flush=True)

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, max_to_keep=3)
        if ckpt.latest_step() is not None:
            state = ckpt.restore(state, shardings=trainer.state_shardings)
            print(f"[rank {pid}] resumed from step "
                  f"{int(state.step)} (restart #{restart_count})",
                  flush=True)

    step = int(state.step)
    epoch = 0
    while step < args.steps:
        loader.set_epoch(epoch)  # forwards to sampler + dataset (augmentation redraw)
        for batch in loader:
            if step >= args.steps:
                break
            placed = shard_batch_for_mesh(
                batch, mesh, trainer.strategy.batch_axes,
                global_batch=(nproc == 1),
            )
            state, metrics = trainer.step(state, placed)
            step = int(state.step)
            if step % args.log_every == 0 and pid == 0:
                print(f"step {step} loss {float(metrics['loss']):.4f} "
                      f"ppl {float(metrics['perplexity']):.1f}", flush=True)
            if ckpt and step % args.ckpt_every == 0:
                ckpt.save(step, state)
        epoch += 1

    if ckpt:
        ckpt.save(step, state)
        ckpt.wait_until_finished()
        ckpt.close()
    dist.shutdown_jax_distributed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
