"""K-EXAONE serving demo: window and full attention layers over a cache of
two depths, 8 query heads on 2 K/V heads, a share of the routed experts,
through the same ``InferenceEngine`` + ``Scheduler`` that serve GPT-2.

The engine learns the cache's class from the model
(``ExaoneMoE.cache_class`` is ``serving.WindowedKVCache``: whole rows for the
full layer, a ring of ``--window`` rows for each window layer); short and
long prompts share one queue, and a long one wraps its rings many times.
Random weights at a small size on the CPU (the published widths are the
``k-exaone-236b-a23b.serve-mixed-len`` cell of ``chipbench/``, on the
chip)::

    python examples/serve_exaone_moe.py --requests 6 --slots 3

Every greedy token is checked against the argmax of the uncached forward.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--experts", type=int, default=16)
    p.add_argument("--held", type=int, default=4,
                   help="experts this model holds, from the first on")
    p.add_argument("--window", type=int, default=8)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--slots", type=int, default=3)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import ExaoneMoE, ExaoneMoEConfig
    from pytorch_distributed_tpu.serving import (
        InferenceEngine,
        Request,
        Scheduler,
    )

    kinds = ("sliding_attention",) * 3 + ("full_attention",
                                          "sliding_attention")
    cfg = ExaoneMoEConfig(
        vocab_size=args.vocab, n_layer=5, hidden_size=args.hidden,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=32,
        num_experts=args.experts, num_experts_per_tok=4,
        held_experts=(0, args.held), sliding_window=args.window,
        layer_types=kinds, mlp_layer_types=("dense",) + ("sparse",) * 4)
    model = ExaoneMoE(cfg)
    variables = jax.jit(model.init)(jax.random.key(args.seed),
                                    jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(model, variables, n_slots=args.slots,
                             max_len=args.max_len)
    sched = Scheduler(engine, emit_events=False)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, args.vocab, int(np.exp(rng.uniform(
        np.log(3), np.log(args.max_len - 16)))), np.int32)
        for _ in range(args.requests)]
    t0 = time.perf_counter()
    ids = [sched.submit(Request(prompt=prompt, max_new_tokens=12))
           for prompt in prompts]
    done = {f.request_id: f.tokens for f in sched.run()}
    seconds = time.perf_counter() - t0
    cache = engine.init_cache()
    print(f"{len(done)} requests through {args.slots} slots of a "
          f"{type(cache).__name__} ({cache.k_full.shape[0]} full layer(s) "
          f"of {cache.max_len} rows, {cache.k_ring.shape[0]} rings of "
          f"{cache.window}) in {seconds:.1f} s")
    wrong = 0
    for rid, prompt in zip(ids, prompts):
        # teacher forcing: one uncached forward over prompt and answer
        seq = np.concatenate([prompt, done[rid][:-1]]).astype(np.int32)
        best = jnp.argmax(model.apply(variables, jnp.asarray(seq[None]))[0],
                          axis=-1)[len(prompt) - 1:]
        wrong += int((np.asarray(best) != np.asarray(done[rid])).sum())
        print(f"  request {rid}: prompt {len(prompt):3d} -> {done[rid]}")
    print("every token is the uncached forward's argmax" if not wrong
          else f"{wrong} tokens differ from the uncached forward")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
