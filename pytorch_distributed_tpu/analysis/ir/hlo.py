"""graftir IR parsing: collective inventory and donation aliasing pulled
out of lowered StableHLO / compiled optimized-HLO text.

Pure text parsing over the artifacts ``jit(f).lower(...)`` and
``.compile()`` expose — no XLA bindings beyond what the repo already
uses for the dryrun gate. Two artifact layers matter:

* **StableHLO** (``lowered.as_text()``) carries donation *intent*: each
  donated leaf that CAN legally alias an output is annotated
  ``tf.aliasing_output``; leaves jax had to demote (shape/dtype
  mismatch) fall back to ``jax.buffer_donor``.
* **Optimized HLO** (``compiled.as_text()``) carries donation *reality*:
  the ``input_output_alias={ {out}: (param, {}), ... }`` header names
  exactly the parameters whose buffers the runtime will reuse — an
  intent entry missing here is the silent 2× memory regression the
  audit exists to catch — plus the post-optimization collective set
  (what actually goes on the wire, after SPMD partitioning and any
  combining/expansion passes).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Iterable, List, Sequence, Set, Tuple

__all__ = [
    "CollectiveOp",
    "COLLECTIVE_FAMILIES",
    "REDUCE_FAMILIES",
    "GATHER_FAMILIES",
    "dtype_bytes",
    "collective_inventory",
    "aliased_param_indices",
    "intended_alias_count",
    "summarize_collectives",
    "parameter_element_counts",
    "activation_collectives",
]

#: instruction families the auditor inventories (``-start``/``-done``
#: async variants fold into their base family)
COLLECTIVE_FAMILIES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

#: families implementing a gradient reduction. CPU's HLO pipeline
#: expands reduce-scatter into all-reduce(+slice), so a per-strategy
#: contract must accept either spelling of "the grads got reduced".
REDUCE_FAMILIES = frozenset({"all-reduce", "reduce-scatter"})

#: families implementing a parameter/activation gather
GATHER_FAMILIES = frozenset({"all-gather"})

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def dtype_bytes(dtype: str) -> int:
    return _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction in an HLO module."""

    family: str          # base family ("all-reduce", never "-start")
    # element type and shape of the LARGEST element of the result: the
    # array that crosses the wire when the result is a -start tuple
    # ((operand, result) of an all-gather, (operand, result, u32[], u32[])
    # of a collective-permute) or a combined collective's
    dtype: str
    shape: Tuple[int, ...]
    bytes: int           # total result bytes (summed over tuple elements)
    scalar: bool         # every result element is rank-0 (loss/metric/
                         # grad-norm reductions, not tensor traffic)

    def describe(self) -> str:
        dims = ",".join(map(str, self.shape))
        return f"{self.family} {self.dtype}[{dims}] ({self.bytes} B)"


# `%name = <result-type> all-reduce(...)`; result-type is one
# `dtype[dims]{layout}` or a tuple of them for -start variants and
# variadic (combined) collectives. TPU layouts carry their own
# parentheses (`f32[64]{0:T(128)S(1)}`), so the tuple form admits one
# nested level — without it every combined collective of a TPU module
# goes uncounted.
_SHAPE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _parse_result_type(token: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for m in _SHAPE.finditer(token):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append((m.group(1), dims))
    return out


def collective_inventory(hlo_text: str) -> List[CollectiveOp]:
    """Every collective instruction definition in ``hlo_text`` (optimized
    HLO or any HLO-syntax dump); ``-done`` consumers are skipped so async
    pairs count once."""
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        shapes = _parse_result_type(m.group(1))
        if not shapes:
            continue
        total = 0
        for dtype, dims in shapes:
            total += math.prod(dims) * dtype_bytes(dtype)
        moved = max(shapes, key=lambda s: math.prod(s[1]))
        ops.append(CollectiveOp(
            family=m.group(2),
            dtype=moved[0],
            shape=moved[1],
            bytes=total,
            scalar=all(not dims for _, dims in shapes),
        ))
    return ops


def summarize_collectives(ops: Sequence[CollectiveOp]) -> Dict[str, Dict]:
    """``{"tensor": {family: {count, bytes}}, "scalar": {...}}`` — the
    budget-entry form. Scalar-grade ops (rank-0 results: loss/metric
    reductions) are tracked separately so they never mask tensor-traffic
    regressions."""
    out: Dict[str, Dict] = {"tensor": {}, "scalar": {}}
    for op in ops:
        grade = "scalar" if op.scalar else "tensor"
        row = out[grade].setdefault(op.family, {"count": 0, "bytes": 0})
        row["count"] += 1
        row["bytes"] += op.bytes
    return out


def parameter_element_counts(
    shapes: Iterable[Tuple[int, ...]], shard_counts: Iterable[int]
) -> Set[int]:
    """Element counts a collective on a parameter can have: each
    parameter's own (its gather, its gradient's reduction) and that count
    over each way the mesh can shard it (a reduce-scatter's result, one
    step of a ring)."""
    counts: Set[int] = set()
    shard_counts = [k for k in shard_counts if k > 1]
    for shape in shapes:
        n = math.prod(shape)
        counts.add(n)
        counts.update(n // k for k in shard_counts if n % k == 0)
    return counts


def activation_collectives(
    ops: Sequence[CollectiveOp], param_counts: Set[int]
) -> List[CollectiveOp]:
    """The tensor-grade collectives that move no parameter, gradient or
    shard of one (by element count, which a reshape on the way to the
    wire keeps): what is left is an activation."""
    return [op for op in ops
            if not op.scalar and math.prod(op.shape) not in param_counts]


_ALIAS_BLOCK = re.compile(r"input_output_alias=\{(.*?)\s\}", re.S)
_ALIAS_PARAM = re.compile(r"\(\s*(\d+)\s*,")


def aliased_param_indices(compiled_hlo_text: str) -> List[int]:
    """Parameter indices the compiled executable actually aliases to an
    output (the module-header ``input_output_alias`` map). Empty when the
    header is absent — no donation was realized at all."""
    m = _ALIAS_BLOCK.search(compiled_hlo_text)
    if not m:
        return []
    return sorted({int(i) for i in _ALIAS_PARAM.findall(m.group(1))})


def intended_alias_count(stablehlo_text: str) -> int:
    """Donated leaves the lowering marked as aliasable
    (``tf.aliasing_output`` attrs in the StableHLO entry signature)."""
    return stablehlo_text.count("tf.aliasing_output")
