"""``../serve-waits/programs_through_scheduler.py`` with EVERY location
stripped, the Mosaic kernels' serialized bodies included (ROADMAP C19: the
check that a refactor of shared code leaves another configuration's
programs alone is of the text with locations stripped, and ``key=`` there
still hashes the call stacks inside a kernel's body): no operation, in the
program or in a kernel, is given its traceback, so ``key=`` no longer moves
with a line or a column, only with what is computed.

    PYTHONPATH=<tree> JAX_PLATFORMS=cpu python3 programs_stripped.py \
        <serve-waits dir> <tree> [cell ...]
"""

import os
import runpy
import sys

from jax._src.interpreters import mlir

_with_traceback = mlir.source_info_to_location
mlir.source_info_to_location = (
    lambda ctx, primitive, name_stack, traceback:
    _with_traceback(ctx, primitive, name_stack, None))

tools = os.path.realpath(sys.argv[1])
sys.path.insert(0, tools)
sys.argv = [os.path.join(tools, "programs_through_scheduler.py")] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
