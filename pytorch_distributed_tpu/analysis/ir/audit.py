"""graftir checks: the four IR-level audits over a step program.

1. **collective budget** (``ir-collective-budget``) — the optimized
   HLO's tensor-grade collective set must match the strategy's declared
   :meth:`~pytorch_distributed_tpu.parallel.ShardingStrategy.collective_signature`:
   a gradient reduction where one is promised, no parameter all-gathers
   under pure DP, delta-gather bytes exactly the sharded-update leaves
   under ZeRO1, per-param (never monolithic) gathers under FSDP.
2. **donation realized** (``ir-donation-aliasing``) — every donated
   argument leaf must appear in the compiled executable's
   ``input_output_alias`` map; a donation the compiler quietly dropped
   is a silent 2× memory regression no AST rule can see.
3. **program count** (``ir-program-count``) — drive a real
   :class:`~pytorch_distributed_tpu.pipeline_exec.AsyncRunner` and
   assert one dispatch per submit against ONE compiled executable:
   ``programs_per_step == 1`` as structure, not as a stamped number.
4. **sharding propagation** (``ir-sharding-propagation``) — compiled
   output shardings vs the strategy's declared specs: a leaf the
   strategy shards that comes back fully replicated means propagation
   fell over (or an ``out_shardings`` pin went missing); declared
   replication fallbacks (``shard_spec_with_reason``) are surfaced into
   the budget so they can't silently grow.

Findings reuse graftlint's :class:`~..core.Finding`, so the reporters,
JSON schema, and fingerprint identity are shared across both tiers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from pytorch_distributed_tpu.analysis.core import Finding
from pytorch_distributed_tpu.analysis.ir import hlo as hlo_mod
from pytorch_distributed_tpu.analysis.ir.programs import (
    StepProgram,
    build_grid,
)

__all__ = [
    "CHECKS",
    "ProgramAudit",
    "AuditReport",
    "donation_findings",
    "audit_program",
    "run_audit",
]

#: the check catalog (rule name -> one-line description); RULES.md "IR
#: tier" documents each with the failure it guards against
CHECKS = {
    "ir-collective-budget": (
        "tensor-grade collective set matches the strategy's declared "
        "signature (reduction present, gather policy, no forbidden ops)"
    ),
    "ir-donation-aliasing": (
        "every donate_argnums leaf is realized in the compiled "
        "executable's input_output_alias map"
    ),
    "ir-program-count": (
        "AsyncRunner path dispatches exactly one program per step "
        "against one compiled executable"
    ),
    "ir-sharding-propagation": (
        "no state leaf the strategy shards falls back to full "
        "replication in the compiled output shardings"
    ),
    "ir-budget-drift": (
        "collective bytes/counts, aliasing, or sharding changed vs the "
        "committed BUDGET.json without regeneration"
    ),
}


def _finding(rule: str, program: str, message: str) -> Finding:
    return Finding(
        rule=rule, path=f"ir:{program}", line=1, col=1,
        message=message, symbol=program,
    )


@dataclasses.dataclass
class ProgramAudit:
    """Outcome of auditing one step program: the budget entry (the facts
    the baseline pins) plus any contract violations."""

    name: str
    entry: Dict
    findings: List[Finding]


@dataclasses.dataclass
class AuditReport:
    grid: str
    platform: str
    device_count: int
    audits: List[ProgramAudit]

    @property
    def findings(self) -> List[Finding]:
        out: List[Finding] = []
        for a in self.audits:
            out.extend(a.findings)
        return out

    @property
    def entries(self) -> Dict[str, Dict]:
        return {a.name: a.entry for a in self.audits}

    @property
    def clean(self) -> bool:
        return not self.findings


# -- check 1: collective budget -------------------------------------------
def _delta_gather_leaves(program: StepProgram) -> List[Tuple[str, int]]:
    import jax.tree_util as jtu

    strategy = program.strategy
    out = []
    for path, leaf in jtu.tree_leaves_with_path(program.state.params):
        pstr = jtu.keystr(path)
        update = strategy.update_pspec(pstr, leaf.shape)
        param = strategy.param_pspec(pstr, leaf.shape)
        if any(e is not None for e in tuple(update)) and not any(
            e is not None for e in tuple(param)
        ):
            out.append((pstr, leaf.size * leaf.dtype.itemsize))
    return out


def collective_findings(
    program: StepProgram, ops: Sequence[hlo_mod.CollectiveOp]
) -> List[Finding]:
    import jax.tree_util as jtu

    name = program.name
    sig = program.strategy.collective_signature()
    findings: List[Finding] = []
    tensor = [op for op in ops if not op.scalar]

    for op in tensor:
        if op.family in sig["forbid"]:
            findings.append(_finding(
                "ir-collective-budget", name,
                f"forbidden collective in train step: {op.describe()}",
            ))

    if sig.get("activations") == "local":
        mesh_shape = program.strategy.mesh.jax_mesh.shape
        param_counts = hlo_mod.parameter_element_counts(
            (leaf.shape for leaf in jtu.tree_leaves(program.state.params)),
            list(mesh_shape.values()) + [math.prod(mesh_shape.values())],
        )
        for op in hlo_mod.activation_collectives(tensor, param_counts):
            findings.append(_finding(
                "ir-collective-budget", name,
                f"collective on an activation: {op.describe()} is no "
                f"parameter, gradient or shard of one — the strategy "
                f"pins activations to the batch layout, the partitioner "
                f"must gather the parameter instead",
            ))

    reduces = [op for op in tensor if op.family in hlo_mod.REDUCE_FAMILIES]
    gathers = [op for op in tensor if op.family in hlo_mod.GATHER_FAMILIES]

    if sig["grad_reduce"] and not reduces:
        findings.append(_finding(
            "ir-collective-budget", name,
            "strategy promises a gradient reduction but the compiled "
            "step has no tensor-grade all-reduce/reduce-scatter — "
            "gradients are not being synchronized",
        ))
    if not sig["grad_reduce"] and reduces:
        findings.append(_finding(
            "ir-collective-budget", name,
            f"unexpected tensor-grade reduction(s) for a no-sync "
            f"strategy: {', '.join(op.describe() for op in reduces)}",
        ))

    total_param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jtu.tree_leaves(program.state.params)
    )
    policy = sig["param_gather"]
    if policy == "none":
        for op in gathers:
            findings.append(_finding(
                "ir-collective-budget", name,
                f"tensor-grade all-gather in a replicated-param "
                f"strategy: {op.describe()} — params should never be "
                f"gathered under pure DP",
            ))
    elif policy == "delta":
        delta = _delta_gather_leaves(program)
        expected = sum(b for _, b in delta)
        got = sum(op.bytes for op in gathers)
        if got != expected:
            findings.append(_finding(
                "ir-collective-budget", name,
                f"delta all-gather bytes {got} != {expected} expected "
                f"for {len(delta)} sharded-update leaves "
                f"({', '.join(p for p, _ in delta)})",
            ))
        biggest_leaf = max((b for _, b in delta), default=0)
        for op in gathers:
            if op.bytes > biggest_leaf:
                findings.append(_finding(
                    "ir-collective-budget", name,
                    f"monolithic all-gather {op.describe()} exceeds the "
                    f"largest sharded-update leaf ({biggest_leaf} B) — "
                    f"the delta gather must stay per-leaf",
                ))
    elif policy == "per_param":
        if not gathers:
            findings.append(_finding(
                "ir-collective-budget", name,
                "FSDP-style strategy compiled with zero tensor-grade "
                "all-gathers — sharded params are never reassembled, "
                "the step cannot be computing full-precision updates",
            ))
        for op in gathers:
            if op.bytes >= total_param_bytes:
                findings.append(_finding(
                    "ir-collective-budget", name,
                    f"monolithic all-gather {op.describe()} >= total "
                    f"param bytes ({total_param_bytes} B) — FSDP must "
                    f"gather per-param, not FlatParameter-style",
                ))
    return findings


# -- check 2: donation realized -------------------------------------------
def donation_findings(
    target: str,
    stablehlo_text: str,
    compiled_hlo_text: str,
    donated_paths: Sequence[str],
    *,
    offset: int = 0,
) -> Tuple[Dict, List[Finding]]:
    """Shared donation audit: ``donated_paths`` are the flattened leaf
    paths of the donated arguments in call order. They occupy flat
    parameter indices ``[offset, offset + len(donated_paths))`` — offset
    is 0 when the donated args lead the signature (the trainer/runner
    steps), or the flat-leaf count of the preceding args otherwise (e.g.
    the serving decode donates the cache *after* the params). Returns
    (budget sub-entry, findings). Also used directly by the donation
    sweep over non-trainer jit sites (``fork_pages``, the redistribute
    chunked-copy update, the serving decode)."""
    donated = len(donated_paths)
    lo, hi = offset, offset + donated
    intended = hlo_mod.intended_alias_count(stablehlo_text)
    realized = hlo_mod.aliased_param_indices(compiled_hlo_text)
    realized_donated = [i for i in realized if lo <= i < hi]
    entry = {
        "donated": donated,
        "intended": intended,
        "realized": len(realized_donated),
    }
    findings: List[Finding] = []
    missing = sorted(set(range(lo, hi)) - set(realized_donated))
    for i in missing:
        findings.append(_finding(
            "ir-donation-aliasing", target,
            f"donated leaf {donated_paths[i - lo]} (param {i}) is not "
            f"in the compiled input_output_alias map — its buffer is "
            f"NOT reused, costing a full extra copy",
        ))
    if intended < donated and not missing:
        # lowering demoted some leaves but the backend aliased anyway —
        # report nothing, reality is what counts
        pass
    return entry, findings


# -- check 3: program count (runner path) ---------------------------------
def runner_audit(
    program: StepProgram, submits: int = 3
) -> Tuple[Dict, List[Finding]]:
    import jax.tree_util as jtu

    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner

    name = program.name
    findings: List[Finding] = []
    # the fused step donates its input state, so the runner gets its own
    runner = AsyncRunner(program.trainer, depth=2, drain_every=4)
    runner.start(program.fresh_state(), program.batch)
    for _ in range(submits):
        runner.submit(program.batch)
    entry = {
        "submits": submits,
        "dispatches": runner.dispatch_count,
        "executables": runner.executable_count,
        "programs_per_step": AsyncRunner.programs_per_step,
    }
    if runner.dispatch_count != submits:
        findings.append(_finding(
            "ir-program-count", name,
            f"{runner.dispatch_count} program dispatches for {submits} "
            f"submits — the step is not one fused program",
        ))
    if runner.executable_count != 1:
        findings.append(_finding(
            "ir-program-count", name,
            f"{runner.executable_count} compiled executables behind the "
            f"pipelined step after {submits} same-shape submits — "
            f"recompilation inside the steady-state loop",
        ))
    if AsyncRunner.programs_per_step != 1.0:
        findings.append(_finding(
            "ir-program-count", name,
            f"AsyncRunner.programs_per_step is "
            f"{AsyncRunner.programs_per_step}, expected 1.0",
        ))
    # the runner's own donation contract: state AND metric ring leaves
    lowered, compiled = runner.step_artifacts(program.batch)
    paths = [
        f"state{jtu.keystr(p)}"
        for p, _ in jtu.tree_leaves_with_path(runner._state)
    ] + [
        f"ring{jtu.keystr(p)}"
        for p, _ in jtu.tree_leaves_with_path(runner._ring)
    ]
    dentry, dfindings = donation_findings(
        f"{name}[runner]", lowered.as_text(), compiled.as_text(), paths
    )
    entry["donation"] = dentry
    findings.extend(dfindings)
    return entry, findings


# -- check 4: sharding propagation ----------------------------------------
def sharding_findings(
    program: StepProgram,
) -> Tuple[Dict, List[Finding]]:
    import jax.tree_util as jtu
    from jax.sharding import PartitionSpec

    from pytorch_distributed_tpu.parallel import shard_spec_with_reason

    name = program.name
    findings: List[Finding] = []
    declared = program.declared_state_specs()
    out_state = program.compiled().output_shardings[0]
    spec_leaves = jtu.tree_leaves_with_path(
        declared, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    sharding_leaves = {
        jtu.keystr(p): s for p, s in jtu.tree_leaves_with_path(out_state)
    }
    declared_sharded = realized_sharded = 0
    for path, spec in spec_leaves:
        pstr = jtu.keystr(path)
        sharding = sharding_leaves.get(pstr)
        if sharding is None:
            continue
        is_declared_sharded = any(e is not None for e in tuple(spec))
        if is_declared_sharded:
            declared_sharded += 1
            if sharding.is_fully_replicated:
                findings.append(_finding(
                    "ir-sharding-propagation", name,
                    f"state leaf {pstr} declared {spec} but the "
                    f"compiled output is fully replicated — the "
                    f"sharding constraint was dropped",
                ))
            else:
                realized_sharded += 1
        elif not sharding.is_fully_replicated:
            realized_sharded += 1
    entry: Dict = {
        "declared_sharded": declared_sharded,
        "realized_sharded": realized_sharded,
    }
    # replication fallbacks the strategy itself declared: named, counted,
    # and pinned by the budget so a silent loss of sharding is visible
    strategy = program.strategy
    axis = getattr(strategy, "fsdp_axis", None) or getattr(
        strategy, "dp_axis", None
    )
    if axis is not None and hasattr(strategy, "min_shard_size"):
        counts: Dict[str, int] = {}
        for _, leaf in jtu.tree_leaves_with_path(program.state.params):
            _, reason = shard_spec_with_reason(
                tuple(leaf.shape), axis, strategy.mesh.size(axis),
                strategy.min_shard_size,
            )
            counts[reason] = counts.get(reason, 0) + 1
        entry["fallbacks"] = dict(sorted(counts.items()))
    return entry, findings


# -- driver ----------------------------------------------------------------
def audit_program(
    program: StepProgram, *, runner_submits: int = 3
) -> ProgramAudit:
    lowered = program.lowered()
    compiled = program.compiled()
    hlo_text = compiled.as_text()
    ops = hlo_mod.collective_inventory(hlo_text)

    findings = collective_findings(program, ops)
    donation_entry, dfindings = donation_findings(
        program.name, lowered.as_text(), hlo_text,
        program.donated_leaf_paths(),
    )
    findings.extend(dfindings)
    sharding_entry, sfindings = sharding_findings(program)
    findings.extend(sfindings)
    runner_entry, rfindings = runner_audit(
        program, submits=runner_submits
    )
    findings.extend(rfindings)

    entry = {
        "strategy": program.strategy_name,
        "amp": program.amp,
        "collectives": hlo_mod.summarize_collectives(ops),
        "donation": donation_entry,
        "sharding": sharding_entry,
        "runner": runner_entry,
    }
    return ProgramAudit(name=program.name, entry=entry, findings=findings)


def run_audit(
    grid: str = "fast", *, runner_submits: int = 3,
    programs: Optional[List[StepProgram]] = None,
) -> AuditReport:
    """Audit the strategy × AMP grid of the repo's own step programs."""
    import jax

    if programs is None:
        programs = build_grid(grid)
    audits = [
        audit_program(p, runner_submits=runner_submits) for p in programs
    ]
    return AuditReport(
        grid=grid,
        platform=jax.default_backend(),
        device_count=len(jax.devices()),
        audits=audits,
    )
