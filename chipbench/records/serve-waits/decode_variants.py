"""ON THE CHIP, one process: what a decode step of gpt2-125m.serve-chat's
engine costs the host clock under four bodies of ``InferenceEngine.decode``
that run the SAME compiled program (a jitted function is looked up by its
arguments' shapes, not by who calls it):

  parent   the body of commit 88a606f: the three inputs are temporaries of
           the call expression, released when the call returns, while the
           device still computes
  held     the inputs made under ``.dispatch.inputs`` and kept in locals to
           the end of the function, after ``.read`` has waited for the device
  released the same, the locals deleted right after the call returns
  tree     ``InferenceEngine.decode`` as the tree this is run from has it

Three slots decode from 64-token prompts; the bodies take turns in blocks of
``BLOCK`` steps (every body meets every cache length equally often), the
slots are prefilled anew when they near ``max_len``. Prints, a body, the
mean and the median step over all its steps and the median of its blocks'
means, in microseconds.

    python3 chipbench/records/serve-waits/decode_variants.py <epochs> [steps a block]
"""

import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cells
from chipbench.drivers.serve_open_loop import build_engine
from pytorch_distributed_tpu.observability import span
from pytorch_distributed_tpu.serving.engine import InferenceEngine

BLOCK, ROUNDS, LIVE = 30, 7, 3


def parent(self, cache, last_tokens, active):
    with span("engine.decode") as whole:
        with span("engine.decode.dispatch") as dispatch:
            cache, toks = self._decode(
                self.params, cache,
                jnp.asarray(np.asarray(last_tokens, np.int32)),
                jnp.asarray(np.asarray(active, bool)),
                self._next_rng(),
            )
            dispatch.set_metadata(executables=self._decode._cache_size())
        with span("engine.decode.read"):
            toks = np.asarray(toks)
        if self._step_stats:
            whole.set_metadata(**{
                name: int(n) for name, n in zip(
                    self._step_stats, toks[self.n_slots:])})
    return cache, toks[:self.n_slots]


def split(release):
    def decode(self, cache, last_tokens, active):
        with span("engine.decode") as whole:
            with span("engine.decode.dispatch") as dispatch:
                with span("engine.decode.dispatch.inputs"):
                    last = jnp.asarray(np.asarray(last_tokens, np.int32))
                    act = jnp.asarray(np.asarray(active, bool))
                    rng = self._next_rng()
                call = span("engine.decode.dispatch.call").__enter__()
                cache, toks = self._decode(
                    self.params, cache,
                    last,
                    act,
                    rng,
                )
                if release:
                    del last, act, rng
                call.__exit__(None, None, None)
                dispatch.set_metadata(executables=self._decode._cache_size())
            with span("engine.decode.read"):
                toks = np.asarray(toks)
            if self._step_stats:
                whole.set_metadata(**{
                    name: int(n) for name, n in zip(
                        self._step_stats, toks[self.n_slots:])})
        return cache, toks[:self.n_slots]
    return decode


BODIES = {"parent": parent, "held": split(False), "released": split(True),
          "tree": InferenceEngine.decode}


def main(epochs, block=BLOCK):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = cells.resolve(cells.load_benchmark(), "gpt2-125m.serve-chat")
    engine, _, _ = build_engine(cell, 2147470123, jax.devices()[:1])
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, cell.config["vocab_size"], (LIVE, 64))
    active = np.zeros((engine.n_slots,), bool)
    active[:LIVE] = True
    cache = engine.init_cache()
    steps = {name: [] for name in BODIES}
    blocks = {name: [] for name in BODIES}
    names = list(BODIES)
    for epoch in range(epochs + 1):           # epoch 0 warms up, not kept
        last = np.zeros((engine.n_slots,), np.int32)
        for slot in range(LIVE):
            cache, last[slot] = engine.prefill(cache, slot, prompts[slot])
        for turn in range(ROUNDS):
            order = names[(epoch + turn) % len(names):] + \
                names[:(epoch + turn) % len(names)]
            for name in order:
                body, mine = BODIES[name], []
                for _ in range(block):
                    t0 = time.perf_counter()
                    cache, toks = body(engine, cache, last, active)
                    mine.append(time.perf_counter() - t0)
                    last[:LIVE] = toks[:LIVE]
                if epoch:
                    steps[name] += mine
                    blocks[name].append(sum(mine) / len(mine))
    for name in names:
        print(f"{name:9s} steps={len(steps[name])} "
              f"mean={1e6 * statistics.fmean(steps[name]):.1f} "
              f"median={1e6 * statistics.median(steps[name]):.1f} "
              f"median_of_block_means={1e6 * statistics.median(blocks[name]):.1f} "
              f"p90={1e6 * statistics.quantiles(steps[name], n=10)[8]:.1f}",
              flush=True)


if __name__ == "__main__":
    main(*map(int, sys.argv[1:]))
