"""What every driver measures with: the compile counter, host spans that
also sit on the profiler's clock, chunk arithmetic, and the ``Result`` a
driver hands back to ``run.py``."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

#: prefix of the benchmark's own spans in the profiler's trace
SPAN_PREFIX = "cb."


def emit(record: Dict[str, Any]) -> None:
    """One earlier line of the run's output (never the last)."""
    print(json.dumps(record), flush=True)


def fail(message: str) -> None:
    print(f"chipbench: {message}", file=sys.stderr, flush=True)


class CompileCounter:
    """Programs XLA compiled or loaded from the persistent cache, and the
    cache's hits, from ``jax.monitoring``'s own events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == self._COMPILE:
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits}


class Spans:
    """Host spans around the benchmark's calls into the program. Each is
    kept with the host's clock (``time.perf_counter``) and, while the
    profiler runs, written into its trace as ``cb.<name>`` so that device
    gaps can be laid against it on one clock."""

    def __init__(self):
        self.records: List[Tuple[str, float, float, int]] = []
        self._depth = 0

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        depth = self._depth
        self._depth += 1
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            self._depth = depth
            self.records.append((name, t0, time.perf_counter(), depth))

    def durations(self, name: str, since: float = 0.0) -> List[float]:
        return [t1 - t0 for n, t0, t1, _ in self.records
                if n == name and t0 >= since]

    def self_times(self, name: str, since: float = 0.0) -> List[float]:
        """Each ``name`` span's duration minus what its direct children
        cover. A span is recorded when it ends, so its descendants are the
        records just before it that lie deeper."""
        out = []
        for j, (n, t0, t1, depth) in enumerate(self.records):
            if n != name or t0 < since:
                continue
            inner, i = 0.0, j - 1
            while i >= 0 and self.records[i][3] > depth:
                if self.records[i][3] == depth + 1:
                    inner += self.records[i][2] - self.records[i][1]
                i -= 1
            out.append((t1 - t0) - inner)
        return out


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    rank = max(0, min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1)))))
    return s[rank]


def chunk_summary(times: List[float]) -> Dict[str, float]:
    """Median, mean and extremes of a window's chunk times. The rate is
    taken over their sum; a mean above the median shows a stall inside the
    window, a run slow in every chunk shows only against other runs."""
    return {
        "n": len(times),
        "median_s": statistics.median(times),
        "mean_s": statistics.fmean(times),
        "min_s": min(times),
        "max_s": max(times),
    }


def resident_bytes(devices) -> int:
    """Bytes the arrays hold now on the fullest of ``devices``."""
    return max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in devices)


def window_rate(units_per_chunk: float, times: List[float]) -> float:
    """All the window's work over all its time. ``times`` are the chunks'
    seconds, each counted from the end of the one before it."""
    return units_per_chunk * len(times) / sum(times)


@dataclasses.dataclass
class Result:
    """What a driver hands to ``run.py``. ``end_to_end`` holds the cell's
    end-to-end values by metric name (without ``setup_s``: ``run.py`` owns
    that clock). ``context`` is what the per-layer readers read: ``spans``
    (a :class:`Spans`), ``window_t0`` (host clock), ``counters``,
    ``programs`` (memory analyses by name), ``trace`` (a reduced trace or
    None). ``resident_bytes`` is :func:`resident_bytes` when the window
    opens, from a driver whose set-up holds more arrays than its window
    does (a reference trained beside the program); see ``run.peak_bytes``."""

    correct: bool
    attempted: int
    failed: int
    setup_end: float                       # host clock at the window's start
    end_to_end: Dict[str, float]
    context: Dict[str, Any]
    why_incorrect: Optional[str] = None
    resident_bytes: Optional[int] = None
