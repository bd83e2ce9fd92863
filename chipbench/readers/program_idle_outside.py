"""Milliseconds in which the first device ran nothing, inside the traced
window but OUTSIDE the program's host spans named in ``outside``, for each
occurrence of the span ``per``. With ``outside`` the spans in which the
host waits for the device (``engine.decode.read``), this is the idle time
the host causes a step: the device waits while the host does something
else than wait for it. Host and device clocks agree to about a millisecond,
so a gap at the edge of a short span is blurred by that much."""

from chipbench import program_trace, trace_reduce


def read(context, outside, per: str):
    reduced = context.get("trace")
    if reduced is None or not reduced.devices:
        return None
    steps = len(program_trace.in_window(context, per))
    spans = [s for s in program_trace.host_spans(context)
             if s.name in outside]
    if not steps or not spans:
        return None
    idle = trace_reduce.subtract(trace_reduce.idle_gaps(reduced),
                                 program_trace.intervals(spans))
    return 1e3 * trace_reduce.length(idle) / steps
