"""``prefill_kernel_roofline`` over the prefills whose ``pdt.engine.prefill``
span lies in the ranges ``where`` gives (``program_span_where``): a paged
engine runs a cold prompt and a tail behind shared pages as two programs,
and only the first runs the kernel. The FLOPs it must spend
(``<costs>.<flops>`` of each such prompt's ``n_real``) over the device time
of the operations whose ``op_name`` names ``kernel`` in the prefill
programs' runs, over the chip's published bf16 peak, in percent."""

import importlib

from chipbench import peaks, prefill_trace
from chipbench.readers import program_span_where


def read(context, kernel: str, costs: str, flops: str, where):
    config = context.get("counters", {}).get("config")
    seconds = sum(s for _, ops in prefill_trace.prefill_runs(context)
                  for op_name, s in ops if kernel in op_name)
    prompts = [s.stats["n_real"] for s in program_span_where.selected(
        context, "engine.prefill", where)
        if "n_real" in s.stats and "bucket" in s.stats]
    if not seconds or not prompts or config is None:
        return None
    cost = getattr(importlib.import_module(f"chipbench.{costs}"), flops)
    spent = sum(cost(int(n), config) for n in prompts)
    peak = peaks.PEAKS[context["counters"]["device_kind"]]["bf16_flops"]
    return 100.0 * spent / seconds / peak
