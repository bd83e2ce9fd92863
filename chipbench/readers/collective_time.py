"""Milliseconds per step of collective operations (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute) on the first device in the
traced window; with ``exposed`` only the part of them during which no other
operation runs there."""

from chipbench import trace_reduce


def read(context, exposed: bool = False):
    reduced, steps = context.get("trace"), context.get("steps_in_trace")
    if reduced is None or not reduced.devices or not steps:
        return None
    total, alone = trace_reduce.collective_seconds(reduced)
    return 1e3 * (alone if exposed else total) / steps
