"""The serving programs of cells 2, 5 and 6 as the BENCHMARK'S WARM-UP
traces them: ``drivers/serve_open_loop.py::instrument`` + ``warm_programs``
-> ``Scheduler.run`` -> ``step`` -> ``_admit`` -> ``engine.prefill`` /
``engine.decode`` -> the jitted program, lowered for a described v5e from the
tree this file is run in (``PYTHONPATH``). A Mosaic kernel's serialized body
records the call stack of each of its operations (ten frames,
``jax_traceback_in_locations_limit``), the compile cache's key hashes that
body, and ``../k-exaone/programs_text.py`` lowers from its own top level: it
cannot see a line that moves in ``engine.decode`` or in the scheduler. This
does (ISSUE 42: spans added inside ``engine.decode`` / ``engine.prefill`` and
around ``Scheduler.submit``).

    PYTHONPATH=<tree> JAX_PLATFORMS=cpu python3 programs_through_scheduler.py <tree>

prints one line a program: ``key=`` the SHA-256 of its text WITHOUT op
locations (what JAX's cache key sees: it strips them, the kernels' bodies
stay), ``strict=`` with them; a kernel's body holds the run's own
temporary path, so ``key=`` compares within one run of the ``.sh``, not
between two. Slots are 2 (the stack does not depend on the
batch; the cache is real memory here), every other size is the cell's.
``programs_through_scheduler.sh`` runs it in the parent and in the change,
unpacked at ONE path, and compares."""

import hashlib
import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies

from lowering_shim import Lowering

root = os.path.realpath(sys.argv[1])
sys.path.insert(0, root)

from chipbench import cells, loadgen
from chipbench.drivers import serve_open_loop as driver
from chipbench.measure import Spans
from pytorch_distributed_tpu.ops import decode_attention
from pytorch_distributed_tpu.serving import InferenceEngine, Scheduler

decode_attention._platform = lambda: "tpu"
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = jax.sharding.SingleDeviceSharding(topo.devices[0])
bench = cells.load_benchmark(cells.ROOT)


def through_scheduler(cell_name):
    cell = cells.resolve(bench, cell_name)
    config, traffic = cell.config, cell.traffic
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    model = family.build_model(config)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=2,
                             max_len=traffic["max_len"],
                             cache_kind=traffic["cache_kind"])
    found = {}
    engine._decode = Lowering(engine._decode, dev, found, "decode")
    engine._prefill = Lowering(engine._prefill, dev, found, "prefill")
    driver.instrument(engine, Spans())
    lens = traffic["prompt_len"]
    # one prompt a bucket the mix can reach, as a long enough run holds
    arrivals = [loadgen.Arrival(0.0, np.ones((min(b, lens["max"]),), np.int32),
                                2, False)
                for b in engine.prefill_buckets
                if b >= lens["min"] and b // 2 < lens["max"]]
    driver.warm_programs(engine, Scheduler(engine, emit_events=False),
                         arrivals)
    for which in sorted(found, key=lambda k: (k != "decode", len(k), k)):
        low = found[which]
        plain, strict = (
            low.as_text(debug_info=debug).replace(root, "<tree>").replace(
                os.path.dirname(root), "<work>") for debug in (False, True))
        print(f"{cell_name} {which} lines={plain.count(chr(10))} "
              f"kernels={plain.count('tpu_custom_call')} "
              f"key={hashlib.sha256(plain.encode()).hexdigest()[:16]} "
              f"strict={hashlib.sha256(strict.encode()).hexdigest()[:16]}",
              flush=True)


for name in sys.argv[2:] or ("gpt2-125m.serve-chat",
                             "xing4.0-29b-a4b.serve-docqa",
                             "k-exaone-236b-a23b.serve-mixed-len"):
    through_scheduler(name)
