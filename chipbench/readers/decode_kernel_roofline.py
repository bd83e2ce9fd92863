"""A decode kernel's share of the chip's memory roofline, in percent: the
bytes it must read (``kernel_costs.<bytes>`` of the cache rows the active
sequences hold, the program's ``kv_rows`` on the ``pdt.sched.step`` around
each ``pdt.engine.decode``) over the device time of the operations whose
``op_name`` names ``kernel`` in the decode program's runs, over the chip's
published bandwidth."""

from chipbench import decode_trace, kernel_costs, peaks, program_trace


def read(context, kernel: str, bytes: str):
    ops, _ = decode_trace.decode_ops(context)
    seconds = sum(s for op_name, s in ops if kernel in op_name)
    config = context.get("counters", {}).get("config")
    rows = [s.parent.stats["kv_rows"]
            for s in program_trace.in_window(context, "engine.decode")
            if s.parent is not None and "kv_rows" in s.parent.stats]
    if not seconds or not rows or config is None:
        return None
    moved = sum(getattr(kernel_costs, bytes)(n, config) for n in rows)
    peak = peaks.PEAKS[context["counters"]["device_kind"]]["hbm_bytes_per_sec"]
    return 100.0 * moved / seconds / peak
