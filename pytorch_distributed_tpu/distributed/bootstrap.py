"""Worker-side JAX runtime bootstrap for multi-process execution.

The reference's workers call ``init_process_group`` and NCCL forms the
communicator; the TPU-native analog is joining every worker process into ONE
global JAX/XLA runtime via ``jax.distributed.initialize`` — after which
``jax.devices()`` spans all processes, a ``Mesh`` can cover the whole slice,
and in-jit collectives ride ICI/DCN (SURVEY.md §5.8; torch env contract
``run.py:187-238``).

``initialize_jax_distributed()`` reads the tpurun/torchrun env contract:

  MASTER_ADDR / MASTER_PORT   — coordination endpoint. The JAX coordinator
      listens on MASTER_PORT+1 by default (MASTER_PORT carries the TCPStore)
      or on TPURUN_JAX_COORDINATOR_PORT when set.
  RANK / WORLD_SIZE           — process_id / num_processes.
  LOCAL_RANK                  — selects this process's accelerator(s) when
      processes share a host (``local_device_ids``).

Call it once at worker start, BEFORE any other jax API touches the backend
(device enumeration pins the runtime). Single-process runs (WORLD_SIZE
absent or 1) are a no-op, so scripts can call it unconditionally.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

__all__ = [
    "initialize_jax_distributed",
    "is_jax_distributed_initialized",
    "shutdown_jax_distributed",
]

_initialized = False


def is_jax_distributed_initialized() -> bool:
    return _initialized


#: how libtpu lays N one-chip processes over the chips of one host
_TPU_PROCESS_BOUNDS = {2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def _pin_tpu_chip(local_rank: int, local_world_size: int) -> None:
    """One chip per co-hosted process, and the processes wired back into
    one slice: libtpu's multi-process-per-host environment. Visibility
    alone (``TPU_VISIBLE_CHIPS``) gives each process a chip but no ICI
    peers; the bounds, addresses and task id let the N runtimes find each
    other. Must be in the environment before the backend initializes;
    ``setdefault`` respects an operator's explicit topology. Ports follow
    MASTER_PORT so that every worker derives the same list."""
    env = {"TPU_VISIBLE_CHIPS": str(local_rank)}
    bounds = _TPU_PROCESS_BOUNDS.get(local_world_size)
    if bounds is not None:  # another count: visibility only, as before
        base = int(os.environ["MASTER_PORT"]) + 16
        env.update({
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_ADDRESSES": ",".join(
                f"localhost:{base + i}" for i in range(local_world_size)
            ),
            "TPU_PROCESS_PORT": str(base + local_rank),
            "CLOUD_TPU_TASK_ID": str(local_rank),
        })
    for key, value in env.items():
        os.environ.setdefault(key, value)


def initialize_jax_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> bool:
    """Join this process into the global JAX runtime.

    Arguments default from the tpurun env contract (see module docstring).
    Returns True when the distributed runtime was initialized, False for a
    single-process no-op. Idempotent: a second call returns True without
    re-initializing.
    """
    global _initialized
    if _initialized:
        return True

    if num_processes is None:
        num_processes = int(os.environ.get("WORLD_SIZE", "1"))
    if num_processes <= 1:
        return False
    if process_id is None:
        process_id = int(os.environ["RANK"])
    if coordinator_address is None:
        addr = os.environ["MASTER_ADDR"]
        port = os.environ.get("TPURUN_JAX_COORDINATOR_PORT")
        if port is None:
            # the TCPStore owns MASTER_PORT; the JAX coordinator takes +1
            port = str(int(os.environ["MASTER_PORT"]) + 1)
        coordinator_address = f"{addr}:{port}"

    import jax

    kwargs = {}
    local_ws = int(os.environ.get("LOCAL_WORLD_SIZE", "1"))
    if local_ws > 1 and "LOCAL_RANK" in os.environ:
        # Co-hosted workers (tpurun nproc-per-node > 1): each process must
        # take its LOCAL_RANK-th accelerator, else every process claims all
        # local chips. local_device_ids is what the CUDA backend honors;
        # libtpu reads its own environment (below). The CPU backend ignores
        # both, harmlessly: its virtual devices are private per process.
        if local_device_ids is None:
            local_device_ids = [int(os.environ["LOCAL_RANK"])]
        _pin_tpu_chip(int(os.environ["LOCAL_RANK"]), local_ws)
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _initialized = True
    return True


def shutdown_jax_distributed() -> None:
    """Tear the distributed runtime down (end of worker main)."""
    global _initialized
    if not _initialized:
        return
    import jax

    jax.distributed.shutdown()
    _initialized = False
