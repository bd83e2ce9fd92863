"""The device operations of the serving engine's DECODE program in a traced
run, each with the section of the program it belongs to. The v5e's events
carry only their HLO line as name (``program_trace``), and a prefill
program's ``fusion.12`` is another operation than the decode program's, so
an operation counts only while a run of the decode program (its envelope
on the ``XLA Modules`` line) is on the device, and its section is looked up
in ``observability.programs()["decode"]``'s text. A program without that
registry entry, or a trace without such runs, gives nothing."""

from __future__ import annotations

import bisect
import functools
from typing import Dict, List, Tuple

from chipbench import program_trace

MODULE = "decode_fn"


@functools.lru_cache(maxsize=1)
def _op_names_of_decode() -> Dict[str, str]:
    try:
        from pytorch_distributed_tpu import observability

        decode = observability.programs().get("decode")
    except (ImportError, AttributeError):
        return {}
    return program_trace.op_names_of_text(decode().as_text()) if decode else {}


def decode_ops(context) -> Tuple[List[Tuple[str, float]], int]:
    """``([(op_name, seconds), ...], runs)``: the operations of device 0
    inside the decode program's runs that begin in the traced window, and
    how many such runs there were."""
    if "decode_ops" not in context:       # every reader of a run asks once
        context["decode_ops"] = _decode_ops(context)
    return context["decode_ops"]


def _decode_ops(context):
    reduced = context.get("trace")
    if reduced is None or not reduced.devices:
        return [], 0
    names = context.get("decode_op_names") or _op_names_of_decode()
    lo, hi = reduced.window
    runs = sorted((t0, t1) for name, t0, t1 in reduced.devices[0].modules
                  if MODULE in name and lo <= t0 < hi)
    if not names or not runs:
        return [], 0
    starts = [t0 for t0, _ in runs]
    found = []
    for name, t0, t1 in reduced.devices[0].ops:
        i = bisect.bisect_right(starts, t0) - 1
        if i < 0 or t0 >= runs[i][1]:
            continue
        op_name = names.get(program_trace.instruction_of(name))
        if op_name is not None:
            found.append((op_name, t1 - t0))
    return found, len(runs)
