"""Milliseconds per step in which the first device ran operations of one
section of the train step, in the traced window: those whose ``op_name``
(named scopes, Flax module names, JAX's ``jvp(...)`` / ``transpose(...)``;
see ``program_trace``) matches the regular expression ``include`` and not
``exclude``. A fused operation counts under the one ``op_name`` the
compiler kept for it."""

import re

from chipbench import program_trace


def read(context, include: str, exclude: str = None):
    reduced, steps = context.get("trace"), context.get("steps_in_trace")
    if reduced is None or not reduced.devices or not steps:
        return None
    op_names = program_trace.op_names(context)
    wanted = re.compile(include)
    unwanted = re.compile(exclude) if exclude else None
    lo, hi = reduced.window
    seconds, found = 0.0, False
    for name, t0, t1 in reduced.devices[0].ops:
        op_name = op_names.get(program_trace.instruction_of(name))
        if (op_name is None or not wanted.search(op_name)
                or (unwanted and unwanted.search(op_name))):
            continue
        if t1 > lo and t0 < hi:
            seconds += min(t1, hi) - max(t0, lo)
            found = True
    return 1e3 * seconds / steps if found else None
