"""A ratio of two quantities that the program's host spans ``pdt.<span>``
carry, over the spans that begin in the traced window: ``scale`` times the
sum of ``num`` over the sum of ``den``, each a statistic's name or
``"seconds"`` (the span's own duration). A ratio of sums, not a mean of
ratios: milliseconds a thousand real tokens over whichever prefills the
window holds, real tokens over the tokens their buckets computed, the share
of a time that one cause filled. Two runs whose windows hold other prompts
can be compared by it, where a median over the window's spans cannot.

With ``tail_of`` only the spans whose statistic ``tail_of`` lies at or above
the ``tail_percentile`` of the window's spans are summed (the slowest fifth
by time to first token: ``tail_of`` ``ttft_us`` at 80). ``None`` where the
span or a statistic is absent (the parent of the PR that brought it), where
fewer than ``min_spans`` spans are in the window, or where the denominator
sums to nothing."""

from chipbench import measure, program_trace


def _of(span, what):
    return span.seconds if what == "seconds" else span.stats.get(what)


def read(context, span: str, num: str, den: str, scale: float = 1.0,
         tail_of: str = None, tail_percentile: float = None,
         min_spans: int = 1):
    spans = program_trace.in_window(context, span)
    needed = [w for w in (num, den, tail_of) if w and w != "seconds"]
    spans = [s for s in spans if all(w in s.stats for w in needed)]
    if len(spans) < max(1, min_spans):
        return None
    if tail_of is not None:
        edge = measure.percentile([s.stats[tail_of] for s in spans],
                                  tail_percentile)
        spans = [s for s in spans if s.stats[tail_of] >= edge]
    below = sum(_of(s, den) for s in spans)
    if not below:
        return None
    return scale * sum(_of(s, num) for s in spans) / below
