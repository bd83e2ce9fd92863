"""What ``Scheduler.step`` costs the HOST with no profiler session, from the
tree this file is given (``PYTHONPATH``), without the compiled program: a
real ``InferenceEngine`` (a two-layer GPT-2 of width 48) whose jitted decode
is replaced by a function that returns at once, and whose copies of the
inputs and key (``jnp.asarray``, ``_next_rng``: 200 us of runtime calls on a
CPU, the same on both sides) are replaced by nothing, so that a step is the
scheduler's and ``engine.decode``'s own Python with the spans (no-ops
here): what this PR could have made dearer. One request decodes for
``steps`` steps in one slot of two; the time of every block of 1,000 steps
is kept.

    PYTHONPATH=<tree> JAX_PLATFORMS=cpu python3 step_overhead.py <blocks>

prints the least, the lower quartile and the median microseconds a step over
the blocks. ``step_overhead.sh`` runs parent and change in turn, several
times. A host measurement on the machine it is run on: never a device time."""

import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.serving import InferenceEngine, Request, Scheduler

blocks, per_block = int(sys.argv[1]), 1000
model = GPT2(GPT2Config(vocab_size=97, n_positions=48, n_embd=48, n_layer=2,
                        n_head=4, dtype=jnp.float32))
variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                         prefill_len=8)
sched = Scheduler(engine, emit_events=False)
sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
sched.run()                                  # everything compiled


class Returns:
    """Stands for the compiled decode program: no work, no device."""
    toks = np.zeros((engine.n_slots,), np.int32)

    def __call__(self, params, cache, last, act, rng):
        return cache, self.toks

    def _cache_size(self):
        return 1


engine._decode = Returns()
engine.max_len = 1 << 30                     # the stub never fills a slot
sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=blocks * per_block + 8))
sched.step()                                 # admitted: decode steps follow
import types  # noqa: E402

from pytorch_distributed_tpu.serving import engine as engine_module  # noqa: E402

engine_module.jnp = types.SimpleNamespace(asarray=lambda a: a)
engine._next_rng = lambda: None
times = []
for _ in range(blocks):
    t0 = time.perf_counter()
    for _ in range(per_block):
        sched.step()
    times.append((time.perf_counter() - t0) / per_block * 1e6)
q = statistics.quantiles(times, n=4)
print(f"us_a_step min={min(times):.3f} q1={q[0]:.3f} median={q[1]:.3f} "
      f"blocks={blocks}", flush=True)
