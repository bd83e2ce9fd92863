"""The mean of a list of samples the driver kept, times ``scale``."""

import statistics


def read(context, samples: str, scale: float = 1.0):
    values = context.get("samples", {}).get(samples)
    return scale * statistics.fmean(values) if values else None
