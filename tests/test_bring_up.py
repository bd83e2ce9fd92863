"""Bring-up contracts: what must hold for the program to start on the chip
and for a missing chip to be an error, never a quiet CPU run.

  * one process per chip — importing the package or the launcher touches
    no backend (``tpurun`` is a parent of the workers that need the chip);
  * the compile cache is placed from outside or at one fixed path;
  * ``chip_smoke.py`` refuses a machine without a TPU;
  * the kernels never turn into the interpreter because a backend failed;
  * utilization divides by a published peak or not at all;
  * the native library is rebuilt from source CONTENT, not file times.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).parent.parent


def _run(code_or_args, **env):
    """Run python on a ``-c`` string or an argv list from the repo root;
    ``NAME=None`` removes NAME from the child's environment."""
    full = dict(os.environ)
    full["PYTHONPATH"] = str(REPO) + os.pathsep + full.get("PYTHONPATH", "")
    full.update(env)
    full = {k: v for k, v in full.items() if v is not None}
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else list(code_or_args))
    return subprocess.run([sys.executable, *args], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


# -- one process per chip --------------------------------------------------
_IMPORTED = [
    "pytorch_distributed_tpu.compile_cache",
    "pytorch_distributed_tpu",
    "pytorch_distributed_tpu.ops",
    "pytorch_distributed_tpu.serving",
    "pytorch_distributed_tpu.elastic.agent",
    "pytorch_distributed_tpu.elastic.run",
    "chip_smoke",
]


@pytest.fixture(scope="module")
def backend_after_import():
    """{module: was a backend live after importing it} — one interpreter
    imports the list in order, so the first True names the culprit."""
    r = _run(
        "import importlib, json\n"
        "from jax._src import xla_bridge\n"
        "out = {}\n"
        f"for m in {_IMPORTED!r}:\n"
        "    importlib.import_module(m)\n"
        "    out[m] = xla_bridge.backends_are_initialized()\n"
        "print(json.dumps(out))"
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("module", _IMPORTED)
def test_import_initialises_no_backend(backend_after_import, module):
    assert backend_after_import[module] is False


# -- the compile cache -----------------------------------------------------
_CACHE_PROBE = (
    "from pytorch_distributed_tpu.compile_cache import enable_compile_cache\n"
    "import jax, json\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "used = enable_compile_cache()\n"
    "print(json.dumps([before, used, jax.config.jax_compilation_cache_dir]))"
)


def test_compile_cache_placed_from_outside_sets_nothing(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and the code sets
    no directory: the config is what it was before the call."""
    placed = str(tmp_path / "placed")
    r = _run(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=placed)
    assert r.returncode == 0, r.stderr
    before, used, after = json.loads(r.stdout.strip().splitlines()[-1])
    assert before == used == after == placed


def test_compile_cache_default_is_fixed_in_checkout():
    r = _run(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=None)
    assert r.returncode == 0, r.stderr
    before, used, after = json.loads(r.stdout.strip().splitlines()[-1])
    assert before is None
    assert used == after == str(REPO / ".jax_cache")


# -- no chip is an error ---------------------------------------------------
def test_chip_smoke_refuses_the_cpu():
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in r.stdout


# -- kernels never fall back to the interpreter ----------------------------
@pytest.mark.parametrize("module", ["flash_attention", "paged_attention"])
def test_interpret_default_propagates_backend_error(module, monkeypatch):
    import importlib

    import jax

    mod = importlib.import_module(f"pytorch_distributed_tpu.ops.{module}")
    assert mod._interpret_default() is True  # the CPU the tests run on

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        mod._interpret_default()


# -- the peaks table -------------------------------------------------------
def test_peak_table_knows_v5e_and_rejects_unknown_kinds():
    from chipbench.peaks import peak_bf16_flops

    assert peak_bf16_flops("TPU v5 lite") == peak_bf16_flops("TPU v5e") == 197e12
    for kind in ("cpu", "TPU v9 hypothetical", ""):
        with pytest.raises(KeyError, match="no published peak"):
            peak_bf16_flops(kind)


# -- the native library ----------------------------------------------------
@pytest.fixture()
def native_sandbox(tmp_path, monkeypatch):
    """_native pointed at a scratch source dir and a scratch lib dir."""
    from pytorch_distributed_tpu import _native

    src, lib = tmp_path / "native", tmp_path / "_lib"
    src.mkdir()
    (src / "a.cpp").write_text('extern "C" int a() { return 1; }\n')
    monkeypatch.setattr(_native, "_SRC_DIR", src)
    monkeypatch.setattr(_native, "_LIB_DIR", lib)
    monkeypatch.setattr(_native, "_LIB_PATH", lib / "libtpudist.so")
    monkeypatch.setattr(_native, "_STAMP_PATH", lib / "libtpudist.so.sha256")
    return _native, src


def test_native_freshness_is_decided_by_source_content(native_sandbox):
    native, src = native_sandbox
    assert native._needs_build()
    native.build()
    assert not native._needs_build()
    # same bytes, newer mtime: still fresh (a copy may change every time)
    os.utime(src / "a.cpp", (2_000_000_000, 2_000_000_000))
    assert not native._needs_build()
    # a library without its stamp, or from other sources, is never loaded
    (src / "a.cpp").write_text('extern "C" int a() { return 2; }\n')
    os.utime(src / "a.cpp", (1, 1))  # OLDER than the library
    assert native._needs_build()
    native.build()
    assert not native._needs_build()
    native._STAMP_PATH.unlink()
    assert native._needs_build()


def test_native_build_without_compiler_says_so(native_sandbox, monkeypatch):
    native, _ = native_sandbox
    monkeypatch.setenv("PATH", "")
    with pytest.raises(RuntimeError, match=r"no `g\+\+` is on PATH"):
        native.build()
