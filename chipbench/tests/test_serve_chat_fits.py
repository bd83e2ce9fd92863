"""``gpt2-125m.serve-chat`` as its files state it compiles for the chip and
fits it, without the chip.

The TPU compiler is installed in the sandbox and compiles for a ``v5e:2x2``
that is described, not attached (``tests/test_chip_compile.py`` holds the
same two programs at 64 slots). Here the slots, the depth of a slot and the
prompt lengths are READ from ``chipbench/traffic/serve-chat.json`` and the
widths from the cell's configuration file, so an edit of either that no
longer fits fails here and not on the chip. Nothing executes: a pass is a
compile result, never a chip run.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from chipbench import cells
from chipbench.families import gpt2 as family

CELL = "gpt2-125m.serve-chat"
#: ``memory_stats()["bytes_limit"]`` of one v5e chip (my chip run, PR 33)
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a described v5e:2x2, or skip where the installed
    stack cannot describe it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def described(v5e_device):
    """The cell's engine over described shapes: ``(engine, params, cache,
    rng, traffic)``; the engine takes the chip's branch of the cache read."""
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    cell = cells.resolve(cells.load_benchmark(), CELL)
    traffic = cell.traffic
    model = family.build_model(cell.config)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    patch = pytest.MonkeyPatch()
    # the described chip's program: ``jax.devices()`` here still says CPU
    patch.setattr(decode_attention, "_platform", lambda: "tpu")
    try:
        engine = InferenceEngine(
            model, params, n_slots=traffic["n_slots"],
            max_len=traffic["max_len"], cache_kind=traffic["cache_kind"])
        cache = on_chip(jax.eval_shape(engine.init_cache))
        rng = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
        yield engine, on_chip(params), cache, rng, traffic
    finally:
        patch.undo()


def _bytes(tree):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


@pytest.fixture(scope="module")
def decode_compiled(described, v5e_device):
    """The decode program at the file's ``n_slots``, compiled once."""
    engine, params, cache, rng, traffic = described
    n = traffic["n_slots"]
    return engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()


def _prefill(described, v5e_device, bucket):
    engine, params, cache, rng, _ = described
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    return engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=v5e_device),
        i32, i32, rng).compile()


def test_decode_program_keeps_the_cache_donated_and_in_place(described,
                                                             decode_compiled):
    """At the file's ``n_slots``: every cache leaf aliases an output, the
    step keeps under a tenth of the cache's bytes in temporaries, and K and
    V are read by one Mosaic kernel a layer."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, _, _ = described
    compiled = decode_compiled
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < _bytes(cache) / 10, memory
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    leaves = jax.tree_util.tree_leaves(cache)
    assert aliased_param_indices(text) == list(
        range(first, first + len(leaves)))
    assert (text.count('custom_call_target="tpu_custom_call"')
            == engine.model.cfg.n_layer)


@pytest.mark.parametrize("which", ["longest_prompt", "longest_bucket"])
def test_prefill_fits_beside_the_decode_program(described, v5e_device,
                                                decode_compiled, which):
    """The resident state (weights, cache) with the temporaries of the
    decode program and of a prefill bucket fits one chip with half a GB to
    spare: at the bucket of the longest prompt the traffic sends, and at the
    engine's longest bucket (``max_len``). No least share is asked: the
    slots are what the cell's rate occupies with headroom, and a cache
    reserved only to fill the chip is padding (PERF.md section 6, PR 46,
    after review)."""
    engine, params, cache, _, traffic = described
    bucket = (engine.prefill_bucket(traffic["prompt_len"]["max"])
              if which == "longest_prompt" else max(engine.prefill_buckets))
    assert bucket <= traffic["max_len"]
    decode = decode_compiled.memory_analysis()
    prefill = _prefill(described, v5e_device, bucket).memory_analysis()
    resident = _bytes(params) + _bytes(cache)
    # arguments of both programs ARE the resident state (the cache donated)
    assert decode.argument_size_in_bytes >= resident
    most = resident + max(decode.temp_size_in_bytes,
                          prefill.temp_size_in_bytes)
    assert most < V5E_BYTES_LIMIT - 0.5e9, (resident, decode, prefill)
