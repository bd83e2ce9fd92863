"""``program_span_ratio`` or a percentile, over those of the program's host
spans ``pdt.<span>`` in the traced window whose statistics lie in the
ranges ``where`` gives (``{stat: [lowest, highest]}``, either end ``null``
for open): the prefills that found nothing cached apart from the tails
behind shared pages, which are two programs with two costs. With ``num``
and ``den``: ``scale`` times the ratio of their sums (``"seconds"`` is a
span's own duration); with ``of`` and ``percentile``: that percentile of
``of``. ``None`` where the span or a statistic is absent (the parent of the
PR that brought it) or no span is left."""

from chipbench import measure, program_trace


def _of(span, what):
    return span.seconds if what == "seconds" else span.stats.get(what)


def selected(context, span: str, where):
    """The window's spans ``span`` that carry every statistic of ``where``
    within its range."""
    def inside(s):
        return all(
            stat in s.stats and (lo is None or s.stats[stat] >= lo)
            and (hi is None or s.stats[stat] <= hi)
            for stat, (lo, hi) in where.items())

    return [s for s in program_trace.in_window(context, span) if inside(s)]


def read(context, span: str, where, num: str = None, den: str = None,
         of: str = None, percentile: float = None, scale: float = 1.0):
    spans = selected(context, span, where)
    needed = [w for w in (num, den, of) if w and w != "seconds"]
    spans = [s for s in spans if all(w in s.stats for w in needed)]
    if not spans:
        return None
    if of is not None:
        return scale * measure.percentile([_of(s, of) for s in spans],
                                          percentile)
    below = sum(_of(s, den) for s in spans)
    return scale * sum(_of(s, num) for s in spans) / below if below else None
