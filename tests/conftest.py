"""Test harness: N-rank simulation on a virtual CPU device mesh.

The reference test ladder (SURVEY.md §4) runs multi-process tests without a
cluster; the JAX-native equivalent is a single process with
``xla_force_host_platform_device_count=8`` virtual CPU devices — real XLA
collectives, no hardware. Environment must be set before jax initializes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite never uses the persistent compilation cache: entry points turn
# it on (compile_cache.enable_compile_cache), and the example scripts the
# tests start inherit this environment. Set before jax is imported.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from __graft_entry__ import _provision_virtual_devices  # noqa: E402

_provision_virtual_devices(8)

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    from pytorch_distributed_tpu.mesh import init_device_mesh

    return init_device_mesh((8,), ("dp",))


@pytest.fixture()
def mesh24():
    from pytorch_distributed_tpu.mesh import init_device_mesh

    return init_device_mesh((2, 4), ("dp", "tp"))


@pytest.fixture()
def keys_folded_on_the_host():
    """``keys_folded_on_the_host(engine)`` makes an ``InferenceEngine`` hand
    its programs each step's key as it did before the programs folded it
    themselves: ``fold_in(base key, n)`` run eagerly, for n = 1, 2, ... in
    call order. The programs take such a lone key as it is, so the engine is
    the reference for the stream of keys: same seed, same tokens."""
    def patch(engine):
        def next_rng():
            engine._rng_calls += 1
            return jax.random.fold_in(engine._rng, engine._rng_calls)

        engine._next_rng = next_rng
        return engine

    return patch


@pytest.fixture()
def served_tokens():
    """``served_tokens(engine, n_requests, longest_prompt)``: that many
    requests of mixed lengths through the engine's slots under a scheduler
    (joins and evictions, every slot reused); each request's tokens by id."""
    import numpy as np

    from pytorch_distributed_tpu.serving import Request, Scheduler

    def serve(engine, n_requests, longest_prompt):
        rng = np.random.default_rng(3)
        sched = Scheduler(engine, emit_events=False)
        for _ in range(n_requests):
            sched.submit(Request(
                prompt=rng.integers(
                    0, 97, int(rng.integers(2, longest_prompt + 1))),
                max_new_tokens=int(rng.integers(3, 9))))
        return {f.request_id: f.tokens for f in sched.run()}

    return serve
