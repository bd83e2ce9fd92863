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
