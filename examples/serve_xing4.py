"""Xing4.0 serving demo: a latent (MLA) cache, dropless experts and a
four-stream residual through the same ``InferenceEngine`` + ``Scheduler``
that serve GPT-2.

The engine learns the cache's class from the model (``Xing4.cache_class`` is
``serving.LatentCache``: one 576-wide latent row a token a layer, stored 640
wide); prefill expands K and V from the latents, decode absorbs ``W_kvb``
into the queries and reads the rows a slot holds. Random weights at a small
size on the CPU (the published widths are the ``xing4.0-29b-a4b.serve-docqa``
cell of ``chipbench/``, on the chip)::

    python examples/serve_xing4.py --requests 6 --slots 3

Every greedy token is checked against the argmax of the uncached forward.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--layers", type=int, default=3)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--slots", type=int, default=3)
    p.add_argument("--max-len", type=int, default=96)
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import Xing4, Xing4Config
    from pytorch_distributed_tpu.serving import (
        InferenceEngine,
        Request,
        Scheduler,
    )

    cfg = Xing4Config(
        vocab_size=args.vocab, n_layer=args.layers, hidden_size=args.hidden,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=96, first_k_dense_replace=1,
        moe_intermediate_size=32, n_routed_experts=args.experts,
        num_experts_per_tok=2, rope_original_max_position_embeddings=64)
    model = Xing4(cfg)
    variables = jax.jit(model.init)(jax.random.key(args.seed),
                                    jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(model, variables, n_slots=args.slots,
                             max_len=args.max_len)
    sched = Scheduler(engine, emit_events=False)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, args.vocab, rng.integers(4, 40), np.int32)
               for _ in range(args.requests)]
    t0 = time.perf_counter()
    ids = [sched.submit(Request(prompt=prompt, max_new_tokens=8))
           for prompt in prompts]
    done = {f.request_id: f.tokens for f in sched.run()}
    seconds = time.perf_counter() - t0
    cache = type(engine.init_cache()).__name__
    print(f"{len(done)} requests through {args.slots} slots of a {cache} in "
          f"{seconds:.1f} s")
    wrong = 0
    for rid, prompt in zip(ids, prompts):
        seq = list(prompt)
        for tok in done[rid]:
            wrong += tok != int(jnp.argmax(
                model.apply(variables, jnp.asarray([seq]))[0, -1]))
            seq.append(tok)
        print(f"  request {rid}: prompt {len(prompt):3d} -> {done[rid]}")
    print("every token is the uncached forward's argmax" if not wrong
          else f"{wrong} tokens differ from the uncached forward's argmax")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
