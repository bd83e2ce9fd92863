"""Median device time, in milliseconds, of the runs of a program (the
envelope on the trace's ``XLA Modules`` line whose name matches
``pattern``) inside the traced window, on the first device."""

import statistics

from chipbench import trace_reduce


def read(context, pattern: str):
    reduced = context.get("trace")
    if reduced is None or not reduced.devices:
        return None
    durations = trace_reduce.module_durations(reduced, pattern)
    return 1e3 * statistics.median(durations) if durations else None
