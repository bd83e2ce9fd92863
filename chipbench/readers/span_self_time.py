"""Mean self time, in milliseconds, of the host span ``span`` over the
window: its duration minus what its child spans cover (for ``sched.step``:
the scheduler's own host work around the engine's calls)."""

import statistics


def read(context, span: str):
    own = context["spans"].self_times(span, since=context["window_t0"])
    return 1e3 * statistics.fmean(own) if own else None
