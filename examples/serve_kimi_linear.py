"""Kimi-Linear serving demo: a hybrid stack (three KDA layers that keep a
fixed-size recurrent state, then one MLA layer over latent rows without
positions, twice), a share of the routed experts, through the same
``InferenceEngine`` + ``Scheduler`` that serve GPT-2.

The engine learns the cache's class from the model
(``KimiLinear.cache_class`` is ``serving.HybridStateCache``: a float32 state
and a convolution tail a slot for each KDA layer, a ``LatentCache``'s rows
for the MLA layers); a prompt is prefilled by the chunked form of the gated
delta rule (``ops.kda.CHUNK`` tokens a chunk), a decode step updates the live
slots' states by the recurrent form. Random weights at a small size on the
CPU (the published widths are the ``kimi-linear-48b-a3b.serve-long-answer``
cell of ``chipbench/``, on the chip)::

    python examples/serve_kimi_linear.py --requests 6 --slots 3

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
    p.add_argument("--periods", type=int, default=2,
                   help="periods KDA, KDA, KDA, MLA of the stack")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--slots", type=int, default=3)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import KimiLinear, KimiLinearConfig
    from pytorch_distributed_tpu.serving import (
        InferenceEngine,
        Request,
        Scheduler,
    )

    layers = tuple(range(1, 4 * args.periods + 1))
    cfg = KimiLinearConfig(
        vocab_size=args.vocab, n_layer=len(layers), hidden_size=args.hidden,
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=args.experts,
        num_experts_per_token=4, held_experts=(0, args.held),
        kda_layers=tuple(i for i in layers if i % 4),
        full_attn_layers=layers[3::4],
        kda_num_heads=4, kda_head_dim=16)
    model = KimiLinear(cfg)
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
          f"{type(cache).__name__} ({len(cache.state)} states of "
          f"{'x'.join(map(str, cache.state[0].shape[1:]))} a slot, "
          f"{cache.latent.n_layers} layers of {cache.max_len} latent rows) "
          f"in {seconds:.1f} s")
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
