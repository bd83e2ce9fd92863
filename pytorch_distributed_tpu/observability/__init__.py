"""Observability & debug — SURVEY.md §2.6 / §5.1-§5.5 parity.

  * ``span``            — the ONE way to open a host span in the program:
    ``pdt.<name>`` in the profiler's trace, a no-op with no profiler
    session (the runner, the scheduler, the engine and the process group
    say what the host was doing; ``jax.named_scope`` says which section
    the device was in)
  * ``profile_trace``   — a profiler session around a block: turns the
    spans on and writes the ``.xplane.pb``
  * ``register_program`` / ``programs`` — lazy thunks to the compiled
    programs (``"step"``, ``"decode"``, ``"prefill/<bucket>"``)
  * ``FlightRecorder``  — C++ ring buffer of eager collectives + stall
    watchdog with dump-on-hang (c10d FlightRecorder + NCCL watchdog roles)
  * ``fr_trace``        — dump analyzer (torch ``flight_recorder/fr_trace.py``)
  * ``Event`` / ``record_event`` / ``recent_events`` — structured events
    (torch ``elastic/events``): what the multihost control plane and an
    operator's log read. There is no counter registry beside them: a count
    is an attribute of the object that makes it (``Scheduler.tokens_generated``,
    ``Router.stats()``) or a stat on its span
  * ``debug_level``     — OFF/INFO/DETAIL from $TPU_DISTRIBUTED_DEBUG
    (``debug.h:18`` role; DETAIL also switches on the shadow-verification
    wrapper in pytorch_distributed_tpu.distributed)
  * ``nan_check``       — host-side NaN scan hook (NanCheck.hpp role)
  * ``IterationLogger`` — per-iteration DDP-style stats (C++ logger.hpp role)
  * ``LatencyTracker`` / ``RatioTracker`` — the scheduler's running
    percentiles and ratios (``Scheduler.stats()``)
"""

from pytorch_distributed_tpu.observability.flight_recorder import (
    FlightRecorder,
    get_flight_recorder,
    fr_trace,
)
from pytorch_distributed_tpu.observability.logging_utils import (
    DebugLevel,
    Event,
    IterationLogger,
    LatencyTracker,
    RatioTracker,
    debug_level,
    nan_check,
    recent_events,
    record_event,
)
from pytorch_distributed_tpu.observability.profiler import (
    profile_trace,
    programs,
    register_program,
    shapes_of,
    span,
)

__all__ = [
    "FlightRecorder",
    "get_flight_recorder",
    "fr_trace",
    "DebugLevel",
    "debug_level",
    "Event",
    "record_event",
    "recent_events",
    "nan_check",
    "IterationLogger",
    "LatencyTracker",
    "RatioTracker",
    "span",
    "profile_trace",
    "register_program",
    "programs",
    "shapes_of",
]
