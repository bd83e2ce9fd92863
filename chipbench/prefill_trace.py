"""The device operations of the serving engine's PREFILL programs in a
traced run, each with the section of the program it belongs to: what
``decode_trace`` does for the decode program, for a program that exists
once a bucket. A run of a prefill program (its envelope on the ``XLA
Modules`` line) lies inside the ``pdt.engine.prefill`` host span of the
call that made it, whose ``bucket`` names the program; that bucket's text
(``observability.programs()["prefill/<bucket>"]``) gives each operation's
``op_name``. A loop's own event spans its body's and is left out. A program
without the span, the statistic or the registry
entry, or a trace without such runs, gives nothing."""

from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, List, Tuple

from chipbench import program_trace

MODULE = "prefill_fn"
#: an event that spans the events of its body (a prefill's loops over
#: blocks of queries and chunks of tokens): the body's own events count
CONTAINER = re.compile(r" (while|conditional|call)\(")


@functools.lru_cache(maxsize=None)
def _op_names_of_bucket(bucket: int) -> Dict[str, str]:
    try:
        from pytorch_distributed_tpu import observability

        prefill = observability.programs().get(f"prefill/{bucket}")
    except (ImportError, AttributeError):
        return {}
    return (program_trace.op_names_of_text(prefill().as_text())
            if prefill else {})


def prefill_runs(context) -> List[Tuple[int, List[Tuple[str, float]]]]:
    """``[(bucket, [(op_name, seconds), ...]), ...]``: a run of a prefill
    program on device 0 that begins in the traced window, and its
    operations."""
    if "prefill_runs" not in context:      # every reader of a run asks once
        context["prefill_runs"] = _prefill_runs(context)
    return context["prefill_runs"]


def _prefill_runs(context):
    reduced = context.get("trace")
    if reduced is None or not reduced.devices:
        return []
    spans = [s for s in program_trace.in_window(context, "engine.prefill")
             if "bucket" in s.stats]
    lo, hi = reduced.window
    runs = sorted((t0, t1) for name, t0, t1 in reduced.devices[0].modules
                  if MODULE in name and lo <= t0 < hi)
    if not spans or not runs:
        return []
    names_of = context.get("prefill_op_names") or _op_names_of_bucket
    begun = [s.t0 for s in spans]
    found: List[Tuple[int, List[Tuple[str, float]]]] = []
    starts = []
    for t0, t1 in runs:
        i = bisect.bisect_right(begun, t0) - 1
        if i < 0 or t0 >= spans[i].t1:
            continue                       # a run no call in the window made
        starts.append((t0, t1))
        found.append((int(spans[i].stats["bucket"]), []))
    first = [t0 for t0, _ in starts]
    for name, t0, t1 in reduced.devices[0].ops:
        i = bisect.bisect_right(first, t0) - 1
        if i < 0 or t0 >= starts[i][1] or CONTAINER.search(name):
            continue
        op_name = names_of(found[i][0]).get(
            program_trace.instruction_of(name))
        if op_name is not None:
            found[i][1].append((op_name, t1 - t0))
    return [run for run in found if run[1]]
