"""Milliseconds in which the first device ran operations of one section of
a PREFILL program (``prefill_trace``), a run of such a program: those whose
``op_name`` matches the regular expression ``include``; the ``percentile``
over the runs that begin in the traced window."""

import re

from chipbench import measure, prefill_trace


def read(context, include: str, percentile: float = 50):
    wanted = re.compile(include)
    times = []
    for _, ops in prefill_trace.prefill_runs(context):
        seconds = [s for op_name, s in ops if wanted.search(op_name)]
        if seconds:
            times.append(1e3 * sum(seconds))
    return measure.percentile(times, percentile) if times else None
