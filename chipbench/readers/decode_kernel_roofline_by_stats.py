"""A decode kernel's share of the chip's memory roofline, in percent, where
the bytes it must read are a function (``<costs>.<bytes>``, a module beside
``kernel_costs.py``) of statistics the program put on its
``pdt.engine.decode`` span (``stats``, in the function's order; each summed
over the decode steps of the traced window): those bytes over the device
time of the operations whose ``op_name`` names ``kernel`` in the decode
program's runs, over the chip's published bandwidth."""

import importlib

from chipbench import decode_trace, peaks, program_trace


def read(context, kernel: str, costs: str, bytes: str, stats):
    ops, _ = decode_trace.decode_ops(context)
    seconds = sum(s for op_name, s in ops if kernel in op_name)
    config = context.get("counters", {}).get("config")
    steps = [s.stats for s in program_trace.in_window(context, "engine.decode")
             if all(name in s.stats for name in stats)]
    if not seconds or not steps or config is None:
        return None
    cost = getattr(importlib.import_module(f"chipbench.{costs}"), bytes)
    moved = cost(*(sum(step[name] for step in steps) for name in stats),
                 config)
    peak = peaks.PEAKS[context["counters"]["device_kind"]]["hbm_bytes_per_sec"]
    return 100.0 * moved / seconds / peak
