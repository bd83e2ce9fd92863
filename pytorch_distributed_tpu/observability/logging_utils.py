"""Structured logging, events, metrics, debug levels, NaN check, iteration
stats — the Python observability roles of SURVEY.md §2.6/§5.5.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from enum import Enum
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger("pytorch_distributed_tpu")

__all__ = [
    "DebugLevel",
    "debug_level",
    "Event",
    "record_event",
    "recent_events",
    "nan_check",
    "IterationLogger",
    "LatencyTracker",
    "RatioTracker",
]


# -- debug level (debug.h:18 role) -----------------------------------------
class DebugLevel(Enum):
    OFF = "OFF"
    INFO = "INFO"
    DETAIL = "DETAIL"


def debug_level() -> DebugLevel:
    raw = os.environ.get("TPU_DISTRIBUTED_DEBUG", "OFF").upper()
    try:
        return DebugLevel(raw)
    except ValueError:
        return DebugLevel.OFF


# -- structured events (elastic/events role) -------------------------------
@dataclasses.dataclass
class Event:
    name: str
    source: str = "agent"
    metadata: Optional[Dict[str, Any]] = None
    timestamp: float = 0.0

    def serialize(self) -> str:
        return json.dumps(dataclasses.asdict(self))


_event_handlers: List[Callable[[Event], None]] = []
_recorded_events: List[Event] = []


def add_event_handler(handler: Callable[[Event], None]) -> None:
    _event_handlers.append(handler)


def record_event(
    name: str, source: str = "agent", **metadata
) -> Event:
    ev = Event(name=name, source=source, metadata=metadata or None,
               timestamp=time.time())
    _recorded_events.append(ev)
    if len(_recorded_events) > 10_000:
        del _recorded_events[:5_000]
    for h in _event_handlers:
        try:
            h(ev)
        except Exception:
            logger.exception("event handler failed for %s", name)
    if logger.isEnabledFor(logging.DEBUG):   # else nothing is serialised
        logger.debug("event: %s", ev.serialize())
    return ev


def recent_events(n: int = 100) -> List[Event]:
    return _recorded_events[-n:]


# -- NaN check (NanCheck.hpp role) -----------------------------------------
def nan_check(tree, *, name: str = "tensor") -> None:
    """Raise if any array in the pytree holds NaN/Inf. Host-side hook for
    outgoing eager collectives and checkpoint payloads; the in-jit training
    path exposes non-finiteness via the GradScaler's all_finite metric."""
    import jax.tree_util as jtu
    import numpy as np

    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            raise FloatingPointError(
                f"non-finite values in {name}[{key}]"
            )


# -- latency percentiles (serving-path SLO stats) --------------------------
class LatencyTracker:
    """Streaming latency samples with percentile summaries.

    The serving scheduler feeds per-token decode times and per-request
    TTFT/total latencies in here; ``percentile``/``summary`` give the
    p50/p99 numbers that the decode benchmark and request-finished events
    report. Bounded memory: keeps the most recent ``max_samples``.
    """

    def __init__(self, max_samples: int = 100_000):
        self.max_samples = max(1, max_samples)
        self.count = 0
        self.total = 0.0
        self._samples: List[float] = []

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total += seconds
        self._samples.append(seconds)
        if len(self._samples) > self.max_samples:
            del self._samples[: self.max_samples // 2]

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile, ``q`` in [0, 100]. 0.0 when empty."""
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        rank = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
        return s[rank]

    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def summary(self) -> Dict[str, float]:
        return {
            "count": float(self.count),
            "mean_s": self.mean(),
            "p50_s": self.percentile(50),
            "p99_s": self.percentile(99),
            "max_s": max(self._samples) if self._samples else 0.0,
        }


# -- streaming ratio counters (serving accept-rate / efficiency stats) -----
class RatioTracker:
    """Streaming numerator / denominator counter.

    The speculative-decoding stats live here: accept-rate (accepted draft
    tokens / proposed draft tokens) and tokens-per-target-forward
    (generated tokens / model invocations) are both running ratios whose
    numerator and denominator accumulate at different granularities.
    """

    def __init__(self):
        self.num = 0.0
        self.den = 0.0

    def add(self, num: float, den: float = 1.0) -> None:
        self.num += num
        self.den += den

    def rate(self, default: float = 0.0) -> float:
        return self.num / self.den if self.den else default


# -- per-iteration stats (C++ logger.hpp role) -----------------------------
class IterationLogger:
    """Collects per-iteration timing stats with sampling (torch DDP Logger:
    construction stats + per-iteration stats at a sample rate)."""

    def __init__(self, sample_rate: int = 1):
        self.sample_rate = max(1, sample_rate)
        self.iterations = 0
        self.samples: List[Dict[str, float]] = []
        self._t_start: Optional[float] = None

    def start_iteration(self) -> None:
        self._t_start = time.perf_counter()

    def end_iteration(self, **extra: float) -> None:
        self.iterations += 1
        if self._t_start is None:
            return
        if self.iterations % self.sample_rate == 0:
            self.samples.append({
                "iteration": self.iterations,
                "step_time_s": time.perf_counter() - self._t_start,
                **extra,
            })
            if len(self.samples) > 10_000:
                del self.samples[:5_000]
        self._t_start = None

    def summary(self) -> Dict[str, float]:
        if not self.samples:
            return {"iterations": self.iterations}
        times = [s["step_time_s"] for s in self.samples]
        return {
            "iterations": self.iterations,
            "avg_step_time_s": sum(times) / len(times),
            "max_step_time_s": max(times),
            "min_step_time_s": min(times),
        }
