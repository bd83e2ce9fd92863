"""Native library loader — builds and binds the C++ runtime.

The C++ sources live in ``native/`` at the repo root (tpustore.cpp: Store
engine + TCP server/client; flightrecorder.cpp: collective ring buffer). They
compile to one shared library, ``_lib/libtpudist.so``, loaded via ctypes (no
pybind11 in the image — SURVEY.md environment notes).

Build is on-demand. Freshness is decided from the CONTENT of
``native/*.cpp``: a sha256 of the sources is stored beside the library
(``libtpudist.so.sha256``) and the library is loaded only when that stamp
matches the sources on disk. File times say nothing here — ``_lib/`` is
ignored by git, so a checkout copied to another machine can carry a
library built from other sources with any timestamps. A lock file
serializes concurrent builders (multi-process test runs).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
from pathlib import Path
from typing import Optional

_PKG_DIR = Path(__file__).resolve().parent
_REPO_ROOT = _PKG_DIR.parent
_SRC_DIR = _REPO_ROOT / "native"
_LIB_DIR = _PKG_DIR / "_lib"
_LIB_PATH = _LIB_DIR / "libtpudist.so"
_STAMP_PATH = _LIB_DIR / "libtpudist.so.sha256"

_lib: Optional[ctypes.CDLL] = None


def _source_digest() -> str:
    h = hashlib.sha256()
    for src in sorted(_SRC_DIR.glob("*.cpp")):
        h.update(src.name.encode())
        h.update(b"\0")
        h.update(src.read_bytes())
        h.update(b"\0")
    return h.hexdigest()


def _needs_build() -> bool:
    if not (_LIB_PATH.exists() and _STAMP_PATH.exists()):
        return True
    return _STAMP_PATH.read_text().strip() != _source_digest()


def build(force: bool = False) -> Path:
    """Compile native/*.cpp → _lib/libtpudist.so (no-op when fresh)."""
    if not force and not _needs_build():
        return _LIB_PATH
    _LIB_DIR.mkdir(exist_ok=True)
    sources = sorted(str(p) for p in _SRC_DIR.glob("*.cpp"))
    if not sources:
        raise FileNotFoundError(f"no C++ sources under {_SRC_DIR}")
    if shutil.which("g++") is None:
        raise RuntimeError(
            "the native runtime (TCPStore, native backend, flight recorder "
            "— what tpurun and the eager process groups use) must be "
            f"compiled from {_SRC_DIR} and no `g++` is on PATH; install a "
            "C++17 compiler. The single-process train and serve paths do "
            "not need it."
        )
    lock = _LIB_DIR / ".build.lock"
    import fcntl

    with open(lock, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            if not force and not _needs_build():  # built while we waited
                return _LIB_PATH
            digest = _source_digest()
            with tempfile.NamedTemporaryFile(
                suffix=".so", dir=_LIB_DIR, delete=False
            ) as tmp:
                tmp_path = tmp.name
            cmd = [
                "g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread",
                "-Wall", "-o", tmp_path, *sources,
            ]
            try:
                subprocess.run(
                    cmd, check=True, capture_output=True, text=True
                )
                # stamp last: a crash between the two leaves a mismatch,
                # which rebuilds — never a stamp vouching for the wrong
                # library
                os.replace(tmp_path, _LIB_PATH)  # atomic publish
                _STAMP_PATH.write_text(digest + "\n")
            finally:
                if os.path.exists(tmp_path):  # the build did not publish
                    os.unlink(tmp_path)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build failed:\n{e.stderr}"
            ) from e
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)
    return _LIB_PATH


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    u8p = c.POINTER(c.c_uint8)

    sigs = {
        "tpustore_server_create": ([c.c_uint16], c.c_void_p),
        "tpustore_server_port": ([c.c_void_p], c.c_uint16),
        "tpustore_server_free": ([c.c_void_p], None),
        "tpustore_client_create": (
            [c.c_char_p, c.c_uint16, c.c_double], c.c_void_p),
        "tpustore_client_free": ([c.c_void_p], None),
        "tpustore_client_shutdown": ([c.c_void_p], None),
        "tpustore_buf_free": ([u8p], None),
        "tpustore_client_set": (
            [c.c_void_p, c.c_char_p, u8p, c.c_size_t], c.c_int),
        "tpustore_client_get": (
            [c.c_void_p, c.c_char_p, c.c_long, c.POINTER(u8p),
             c.POINTER(c.c_size_t)], c.c_int),
        "tpustore_client_get_nowait": (
            [c.c_void_p, c.c_char_p, c.POINTER(u8p), c.POINTER(c.c_size_t)],
            c.c_int),
        "tpustore_client_add": (
            [c.c_void_p, c.c_char_p, c.c_long, c.POINTER(c.c_long)], c.c_int),
        "tpustore_client_wait": (
            [c.c_void_p, c.POINTER(c.c_char_p), c.c_int, c.c_long], c.c_int),
        "tpustore_client_check": (
            [c.c_void_p, c.POINTER(c.c_char_p), c.c_int,
             c.POINTER(c.c_long)], c.c_int),
        "tpustore_client_compare_set": (
            [c.c_void_p, c.c_char_p, u8p, c.c_size_t, u8p, c.c_size_t,
             c.POINTER(u8p), c.POINTER(c.c_size_t)], c.c_int),
        "tpustore_client_delete": ([c.c_void_p, c.c_char_p], c.c_int),
        "tpustore_client_num_keys": (
            [c.c_void_p, c.POINTER(c.c_long)], c.c_int),
        "tpustore_client_ping": ([c.c_void_p], c.c_int),
        # -- native eager backend (tpubackend.cpp) --
        "tpubackend_create": (
            [c.c_char_p, c.c_uint16, c.c_int, c.c_int, c.c_double,
             c.c_char_p],
            c.c_void_p),
        "tpubackend_free": ([c.c_void_p], None),
        "tpubackend_all_gather": (
            [c.c_void_p, c.c_long, u8p, c.c_size_t, u8p], c.c_int),
        "tpubackend_all_reduce": (
            [c.c_void_p, c.c_long, c.c_int, c.c_int, u8p, c.c_size_t, u8p],
            c.c_int),
        "tpubackend_reduce": (
            [c.c_void_p, c.c_long, c.c_int, c.c_int, c.c_int, u8p,
             c.c_size_t, u8p], c.c_int),
        "tpubackend_gather": (
            [c.c_void_p, c.c_long, c.c_int, u8p, c.c_size_t, u8p], c.c_int),
        "tpubackend_bc_post": (
            [c.c_void_p, c.c_long, c.c_int, u8p, c.c_size_t, u8p,
             c.c_size_t], c.c_int),
        "tpubackend_bc_recv": (
            [c.c_void_p, c.c_long, c.c_int, c.POINTER(u8p),
             c.POINTER(c.c_size_t)], c.c_int),
        "tpubackend_scatter_post": (
            [c.c_void_p, c.c_long, u8p, c.POINTER(c.c_size_t)], c.c_int),
        "tpubackend_scatter_recv": (
            [c.c_void_p, c.c_long, u8p, c.c_size_t], c.c_int),
        "tpubackend_reduce_scatter": (
            [c.c_void_p, c.c_long, c.c_int, c.c_int, u8p, c.c_size_t, u8p],
            c.c_int),
        "tpubackend_a2a_post": (
            [c.c_void_p, c.c_long, c.c_int, u8p, c.c_size_t, u8p,
             c.c_size_t], c.c_int),
        "tpubackend_a2a_recv": (
            [c.c_void_p, c.c_long, c.c_int, c.POINTER(u8p),
             c.POINTER(c.c_size_t)], c.c_int),
        "tpubackend_barrier": ([c.c_void_p, c.c_long], c.c_int),
        "tpubackend_broadcast_coalesced": (
            [c.c_void_p, c.c_long, c.c_int, u8p, c.c_size_t, c.c_size_t],
            c.c_int),
        "tpubackend_send": (
            [c.c_void_p, c.c_int, c.c_long, u8p, c.c_size_t, u8p,
             c.c_size_t], c.c_int),
        "tpubackend_recv": (
            [c.c_void_p, c.c_int, c.c_long, c.POINTER(u8p),
             c.POINTER(c.c_size_t)], c.c_int),
        "tpubackend_all_reduce_start": (
            [c.c_void_p, c.c_long, c.c_int, c.c_int, u8p, c.c_size_t, u8p],
            c.c_void_p),
        "tpubackend_all_gather_start": (
            [c.c_void_p, c.c_long, u8p, c.c_size_t, u8p], c.c_void_p),
        "tpubackend_work_done": ([c.c_void_p], c.c_int),
        "tpubackend_work_wait": ([c.c_void_p], c.c_int),
        "tpubackend_work_free": ([c.c_void_p], None),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def get_lib() -> ctypes.CDLL:
    """Load (building if needed) the native library."""
    global _lib
    if _lib is None:
        _lib = _bind(ctypes.CDLL(str(build())))
    return _lib
