"""From the profiler's ``.xplane.pb`` to the few facts the per-layer readers
use: when an operation ran on each device, the envelope of each program,
the collectives, and the benchmark's own host spans, all on the trace's one
clock. Read with ``jax.profiler.ProfileData`` and nothing else.

A TPU plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event an
operation (they do not overlap: the core runs one at a time) and ``XLA
Modules`` one event a program run. Host spans are the ``cb.*`` events that
``measure.Spans`` wrote through ``TraceAnnotation`` on the host's planes.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Sequence, Tuple

from chipbench.measure import SPAN_PREFIX

Interval = Tuple[float, float]                 # start, end in seconds
Event = Tuple[str, float, float]               # name, start, end in seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
WINDOW_SPAN = "window"
NO_SPAN = "no_span"


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Event]                   # the core's line: one at a time
    modules: List[Event]
    async_ops: List[Event]             # copies and collectives in flight


@dataclasses.dataclass
class Reduced:
    devices: List[DeviceTrace]
    spans: List[Event]                 # names without the ``cb.`` prefix
    window: Interval                   # the ``cb.window`` span, else all ops


@contextlib.contextmanager
def tracing(trace_dir: str):
    """The profiler on for the body, without the Python tracer (it slows the
    host and fills the file with frames nobody reads)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)   # one run, one trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def from_profile(profile) -> Reduced:
    """Reduce a ``ProfileData``."""
    devices, spans = [], []
    for plane in profile.planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            lines = {line.name: line for line in plane.lines}

            def of(name):
                return (sorted(_events(lines[name]), key=lambda e: e[1])
                        if name in lines else [])

            devices.append(DeviceTrace(
                ordinal=int(found.group(1)), ops=of(OPS_LINE),
                modules=of(MODULES_LINE), async_ops=of(ASYNC_LINE)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (name[len(SPAN_PREFIX):], t0, t1)
                    for name, t0, t1 in _events(line)
                    if name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d.ordinal)
    spans.sort(key=lambda e: e[1])
    windows = [(t0, t1) for name, t0, t1 in spans if name == WINDOW_SPAN]
    if windows:
        window = windows[0]
    else:
        every = [e for d in devices for e in d.ops]
        window = ((min(e[1] for e in every), max(e[2] for e in every))
                  if every else (0.0, 0.0))
    return Reduced(devices=devices, spans=spans, window=window)


def reduce(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(newest_xplane(trace_dir)))


# -- interval arithmetic ----------------------------------------------------
def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals that cover the same instants."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """What of ``window`` the disjoint sorted ``busy`` leaves uncovered."""
    out, at = [], window[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def subtract(intervals: Sequence[Interval],
             other: Sequence[Interval]) -> List[Interval]:
    """The part of ``intervals`` that ``other`` does not cover (both
    disjoint and sorted)."""
    out = []
    for a, b in intervals:
        out.extend(gaps(clip(other, (a, b)), (a, b)))
    return out


# -- what the readers ask ----------------------------------------------------
def busy_intervals(device: DeviceTrace, window: Interval) -> List[Interval]:
    return union(clip(((t0, t1) for _, t0, t1 in device.ops), window))


def busy_seconds(reduced: Reduced) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = [length(busy_intervals(d, reduced.window)) for d in reduced.devices]
    return sum(per) / len(per) if per else 0.0


def window_seconds(reduced: Reduced) -> float:
    return reduced.window[1] - reduced.window[0]


def idle_gaps(reduced: Reduced, device: int = 0) -> List[Interval]:
    dev = reduced.devices[device]
    return gaps(busy_intervals(dev, reduced.window), reduced.window)


def attribute(intervals: Sequence[Interval],
              spans: Sequence[Event]) -> Dict[str, float]:
    """Seconds of ``intervals`` by the host span they fall in: each instant
    goes to the innermost (latest begun) span that covers it, else to
    ``no_span``. The window span itself is not a place the host was."""
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    edges = sorted({t for _, t0, t1 in spans for t in (t0, t1)})
    owner: List[str] = []                  # of [edges[i], edges[i+1])
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        inner = [s for s in spans if s[1] <= mid < s[2]]
        owner.append(max(inner, key=lambda s: s[1])[0] if inner else NO_SPAN)
    out: Dict[str, float] = {}
    for a, b in intervals:
        at = a
        i = bisect.bisect_right(edges, a) - 1
        while at < b:
            nxt = edges[i + 1] if 0 <= i + 1 < len(edges) else float("inf")
            name = owner[i] if 0 <= i < len(owner) else NO_SPAN
            upto = min(b, nxt)
            out[name] = out.get(name, 0.0) + (upto - at)
            at, i = upto, i + 1
    return out


def longest_idle_by_span(reduced: Reduced, top: int = 10):
    by = attribute(idle_gaps(reduced), reduced.spans)
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")


def op_label(name: str) -> str:
    """A short label under which runs of one kernel add up. The chip names
    an operation by its whole HLO line (``%fusion.993 = bf16[16,12,1024,64]
    {...} fusion(...)``): keep the name without its instance number and the
    (first) output's type and shape, ``fusion bf16[16,12,1024,64]``."""
    hlo = _HLO.match(name)
    if hlo:
        return f"{hlo.group(1)} {hlo.group(2)}"
    return re.sub(r"\.\d+$", "", name.lstrip("%")) or name


def top_ops(reduced: Reduced, top: int = 10, device: int = 0):
    totals: Dict[str, float] = {}
    lo, hi = reduced.window
    for name, t0, t1 in reduced.devices[device].ops:
        if t1 > lo and t0 < hi:
            key = op_label(name)
            totals[key] = totals.get(key, 0.0) + (min(t1, hi) - max(t0, lo))
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def collective_seconds(reduced: Reduced, device: int = 0) -> Tuple[float, float]:
    """``(total, exposed)`` seconds of collective operations on one device
    in the window: the union of their intervals (on the core's line and in
    flight on the asynchronous one), and the part of it during which no
    other operation runs on the core."""
    dev = reduced.devices[device]

    def is_collective(name):           # the operation, not its operands
        return COLLECTIVE.search(name.split("(", 1)[0].split("=")[0])

    coll = union(clip(((t0, t1) for n, t0, t1 in dev.ops + dev.async_ops
                       if is_collective(n)), reduced.window))
    rest = union(clip(((t0, t1) for n, t0, t1 in dev.ops
                       if not is_collective(n)), reduced.window))
    return length(coll), length(subtract(coll, rest))


def module_durations(reduced: Reduced, pattern: str,
                     device: int = 0) -> List[float]:
    """Device seconds of each run of the programs whose name matches
    ``pattern`` and that lie wholly inside the window."""
    lo, hi = reduced.window
    rx = re.compile(pattern)
    return [t1 - t0 for name, t0, t1 in reduced.devices[device].modules
            if rx.search(name) and t0 >= lo and t1 <= hi]


def memory_of(compiled) -> Dict[str, int]:
    """Bytes a compiled program needs on one device: arguments + outputs +
    temporaries - what outputs alias of the arguments."""
    m = compiled.memory_analysis()
    parts = {
        "argument": int(m.argument_size_in_bytes),
        "output": int(m.output_size_in_bytes),
        "temp": int(m.temp_size_in_bytes),
        "alias": int(m.alias_size_in_bytes),
    }
    parts["total"] = (parts["argument"] + parts["output"] + parts["temp"]
                      - parts["alias"])
    return parts
