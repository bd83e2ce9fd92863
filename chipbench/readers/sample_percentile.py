"""A percentile of a list of per-request or per-step samples that the
driver kept (``context['samples'][samples]``), times ``scale``."""

from chipbench import measure


def read(context, samples: str, percentile: float, scale: float = 1.0):
    values = context.get("samples", {}).get(samples)
    return scale * measure.percentile(values, percentile) if values else None
