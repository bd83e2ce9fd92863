"""Model FLOP/s utilization: the operations the forward and backward passes
need for one token or image (``flops.py``, from shapes) times the rate of
the chunks this run measured with the profiler off, over one chip's
published bf16 peak (``peaks.py``). Recomputation is not counted."""

from chipbench import peaks


def read(context):
    c = context["counters"]
    if "rate_per_chip" not in c:
        return None
    peak = peaks.peak_bf16_flops(c["device_kind"])
    return 100.0 * c["flops_per_unit"] * c["rate_per_chip"] / peak
