"""A prefill kernel's share of the chip's bf16 peak, in percent: the FLOPs
it must spend (``<costs>.<flops>`` of each prompt's real tokens, the
``n_real`` the program put on its ``pdt.engine.prefill`` span) over the
device time of the operations whose ``op_name`` names ``kernel`` in the
prefill programs' runs (``prefill_trace``), over the chip's published
peak."""

import importlib

from chipbench import peaks, prefill_trace, program_trace


def read(context, kernel: str, costs: str, flops: str):
    config = context.get("counters", {}).get("config")
    seconds = sum(s for _, ops in prefill_trace.prefill_runs(context)
                  for op_name, s in ops if kernel in op_name)
    prompts = [s.stats["n_real"]
               for s in program_trace.in_window(context, "engine.prefill")
               if "n_real" in s.stats and "bucket" in s.stats]
    if not seconds or not prompts or config is None:
        return None
    cost = getattr(importlib.import_module(f"chipbench.{costs}"), flops)
    spent = sum(cost(int(n), config) for n in prompts)
    peak = peaks.PEAKS[context["counters"]["device_kind"]]["bf16_flops"]
    return 100.0 * spent / seconds / peak
