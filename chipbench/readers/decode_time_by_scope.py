"""Milliseconds a decode step in which the first device ran operations of
one section of the decode program (``decode_trace``): those whose
``op_name`` matches the regular expression ``include``."""

import re

from chipbench import decode_trace


def read(context, include: str):
    ops, runs = decode_trace.decode_ops(context)
    wanted = re.compile(include)
    seconds = [s for op_name, s in ops if wanted.search(op_name)]
    return 1e3 * sum(seconds) / runs if seconds else None
