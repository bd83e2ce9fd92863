"""A statistic that the program put on its host span ``pdt.<span>``, over
the traced window, times ``scale``: a ``percentile`` of its values, or
(``per``) their sum over the number of spans called ``per`` (tokens
consumed for each forward of the decode program)."""

from chipbench import measure, program_trace


def read(context, span: str, stat: str, percentile: float = None,
         per: str = None, scale: float = 1.0):
    values = [s.stats[stat] for s in program_trace.in_window(context, span)
              if stat in s.stats]
    if not values:
        return None
    if per is not None:
        count = len(program_trace.in_window(context, per))
        return scale * sum(values) / count if count else None
    return scale * measure.percentile(values, percentile)
