"""Milliseconds of the program's own host span ``pdt.<span>`` over the
traced window, on the profiler's clock: the mean of its durations, or a
``percentile`` of them. With ``minus_child`` each duration is taken without
the spans of that name directly inside it (``runner.submit`` without its
``runner.fence`` is what the host itself spends a step)."""

import statistics

from chipbench import measure, program_trace


def read(context, span: str, percentile: float = None,
         minus_child: str = None):
    inside = {}                        # id of a span -> seconds of the child
    if minus_child:
        for s in program_trace.host_spans(context):
            if s.name == minus_child and s.parent is not None:
                inside[id(s.parent)] = inside.get(id(s.parent), 0.0) + s.seconds
    own = [s.seconds - inside.get(id(s), 0.0)
           for s in program_trace.in_window(context, span)]
    if not own:
        return None
    if percentile is None:
        return 1e3 * statistics.fmean(own)
    return 1e3 * measure.percentile(own, percentile)
