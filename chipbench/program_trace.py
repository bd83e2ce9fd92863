"""What the PROGRAM wrote into a traced run, beside what the benchmark
wrote around it: the ``pdt.*`` host spans of ``observability.span`` with
their statistics and nesting, and for each operation on the device the
section of the program it belongs to (the ``op_name`` that
``jax.named_scope``, Flax's module names and JAX's own ``jvp(...)`` /
``transpose(...)`` leave on the compiled instruction).

``trace_reduce.from_profile`` keeps only the benchmark's ``cb.*`` spans, so
the host spans are read again here from the newest ``.xplane.pb`` under
``.chipbench_trace`` (where ``run.py`` has a traced run write), once per
process; the window is ``context["trace"].window``, on the same clock.

On the v5e an ``XLA Ops`` event carries no name-scope statistic (its stats
are ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``: my chip run, PR 24); its NAME is the instruction's HLO line
(``%fusion.993 = bf16[...] fusion(...)``). So the section comes from the
compiled step's text, which the program hands out lazily
(``observability.programs()["step"]``): the line of ``%fusion.993`` there
carries ``metadata={op_name="jit(pstep)/jvp(GPT2)/h_0/attn/..."}``.

A program that has no such spans or registry (the parent of the PR that
brought them) gives empty answers here, and every reader then returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import mmap
import os
import re
from typing import Any, Dict, List, Optional, Sequence

from chipbench import cells, trace_reduce
from chipbench.trace_reduce import Interval

#: prefix of the program's own spans in the profiler's trace
SPAN_PREFIX = "pdt."
TRACE_DIR = cells.ROOT / ".chipbench_trace"


@dataclasses.dataclass
class HostSpan:
    name: str                    # without the ``pdt.`` prefix
    t0: float                    # seconds on the trace's clock
    t1: float
    stats: Dict[str, Any]
    #: the span that encloses it on its thread, if any
    parent: Optional["HostSpan"] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def spans_of_profile(profile) -> List[HostSpan]:
    """The ``pdt.*`` events of every host thread of a ``ProfileData``, in
    order of their start. Nesting is by time on the thread: a span's parent
    is the latest begun span of its thread that has not ended."""
    out: List[HostSpan] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = sorted(
                (HostSpan(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                 for e in line.events if e.name.startswith(SPAN_PREFIX)),
                key=lambda s: (s.t0, -s.t1))
            stack: List[HostSpan] = []     # the spans open at this instant
            for s in found:
                while stack and stack[-1].t1 <= s.t0:
                    stack.pop()
                s.parent = stack[-1] if stack else None
                stack.append(s)
            out.extend(found)
    return sorted(out, key=lambda s: s.t0)


def _mentions(path: str, what: bytes) -> bool:
    """Whether the file holds ``what`` anywhere. An ``.xplane.pb`` keeps each
    event's name once, as plain bytes, so a trace without one ``pdt.`` name
    is told in the time it takes to read the file, and is not parsed a
    second time (``trace_reduce`` has parsed it once already)."""
    if os.path.getsize(path) == 0:
        return False
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as held:
        return held.find(what) >= 0


@functools.lru_cache(maxsize=1)
def _spans_of_newest_trace() -> List[HostSpan]:
    from jax.profiler import ProfileData

    try:
        path = trace_reduce.newest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return []
    if not _mentions(path, SPAN_PREFIX.encode()):
        return []                  # a program without the spans: no parse
    return spans_of_profile(ProfileData.from_file(path))


def host_spans(context) -> List[HostSpan]:
    """The program's host spans of the traced run behind ``context``
    (``context["program_spans"]`` where a test put hand-made ones)."""
    if "program_spans" in context:
        return context["program_spans"]
    if context.get("trace") is None:
        return []
    return _spans_of_newest_trace()


def in_window(context, name: str) -> List[HostSpan]:
    """The spans called ``name`` that begin inside the traced window."""
    reduced = context.get("trace")
    if reduced is None:
        return []
    lo, hi = reduced.window
    return [s for s in host_spans(context)
            if s.name == name and lo <= s.t0 < hi]


def intervals(spans: Sequence[HostSpan]) -> List[Interval]:
    return trace_reduce.union((s.t0, s.t1) for s in spans)


# -- the section of a device operation --------------------------------------
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)


def op_names_of_text(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` from a compiled module's text."""
    return dict(_HLO_LINE.findall(hlo_text))


@functools.lru_cache(maxsize=1)
def _op_names_of_step() -> Dict[str, str]:
    try:
        from pytorch_distributed_tpu import observability

        step = observability.programs().get("step")
    except (ImportError, AttributeError):
        return {}                  # a program without the registry
    return op_names_of_text(step().as_text()) if step else {}


def instruction_of(event_name: str) -> str:
    """``%fusion.993 = bf16[...] fusion(...)`` -> ``fusion.993``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_names(context) -> Dict[str, str]:
    """``{instruction name: op_name}`` of the train step the traced run
    made (``context["op_names"]`` where a test put hand-made ones)."""
    if "op_names" in context:
        return context["op_names"]
    return _op_names_of_step()
