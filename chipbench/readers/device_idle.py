"""Share of the traced window in which no operation ran on the device
(1 - union of the device-op intervals over the window, averaged over the
chips), or that idle time per step in milliseconds (``per_step``)."""

from chipbench import trace_reduce


def read(context, per_step: bool = False):
    reduced = context.get("trace")
    if reduced is None or not reduced.devices:
        return None
    window = trace_reduce.window_seconds(reduced)
    idle = window - trace_reduce.busy_seconds(reduced)
    if not per_step:
        return 100.0 * idle / window
    steps = context.get("steps_in_trace")
    return 1e3 * idle / steps if steps else None
