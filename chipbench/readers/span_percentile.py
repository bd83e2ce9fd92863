"""A percentile, in milliseconds, of the durations of the benchmark's host
span ``span`` (host clock around a call into the program that ends in a
device read) over the window."""

from chipbench import measure


def read(context, span: str, percentile: float):
    durations = context["spans"].durations(span, since=context["window_t0"])
    return 1e3 * measure.percentile(durations, percentile) if durations else None
