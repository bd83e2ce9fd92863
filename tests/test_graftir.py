"""graftir: IR-tier audit tests.

Three layers: pure text-parsing units over canned HLO (no jax work),
in-process audits of real compiled step programs (the checks must pass
on the repo's own trainers AND catch deliberately broken variants —
dropped donation, budget drift), and the tier-1 subprocess gate that
runs ``graftir --grid fast --diff`` against the committed BUDGET.json
exactly as CI does. The donation sweep at the bottom lowers every
in-tree ``donate_argnums`` site the auditor does not already cover
(``fork_pages``, the redistribute chunked-copy update, the serving
decode step) and asserts the compiler realizes each donation.
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from pytorch_distributed_tpu.analysis.ir import (
    CHECKS,
    AuditReport,
    audit_program,
    build_program,
    collective_inventory,
    donation_findings,
    summarize_collectives,
)
from pytorch_distributed_tpu.analysis.ir import budget as budget_mod
from pytorch_distributed_tpu.analysis.ir import hlo as hlo_mod

pytestmark = pytest.mark.ir

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- HLO text parsing (no compilation) -------------------------------------

SAMPLE_HLO = textwrap.dedent("""\
    HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={...}

    ENTRY %main (p0: f32[256,10], p1: f32[10]) -> (f32[256,10], f32[]) {
      %ar = f32[256,10]{1,0} all-reduce(f32[256,10]{1,0} %g), replica_groups={}
      %ag.s = (f32[10]{0}, f32[80]{0}) all-gather-start(f32[10]{0} %shard), dimensions={0}
      %ag.d = f32[80]{0} all-gather-done((f32[10]{0}, f32[80]{0}) %ag.s)
      %loss = f32[] all-reduce(f32[] %l), replica_groups={}
      ROOT %t = (f32[256,10]{1,0}, f32[]) tuple(%ar, %loss)
    }
""")


def test_collective_inventory_families_and_bytes():
    ops = collective_inventory(SAMPLE_HLO)
    # all-gather-done is a consumer, not a second collective
    assert [op.family for op in ops] == [
        "all-reduce", "all-gather", "all-reduce"
    ]
    ar, ag, loss = ops
    assert ar.bytes == 256 * 10 * 4 and not ar.scalar
    # -start result tuples sum every element (in-flight + result)
    assert ag.bytes == (10 + 80) * 4 and not ag.scalar
    assert loss.scalar and loss.bytes == 4
    assert "all-reduce f32[256,10]" in ar.describe()


def test_collective_inventory_reads_tpu_tiled_tuple_layouts():
    """Lines as the TPU compiler prints them (from the ResNet-50 DP step
    compiled for a described v5e:2x2): a combined all-reduce has a TUPLE
    result whose tiled layouts carry their own parentheses. A tuple
    pattern that stops at the first ``)`` counts none of them."""
    text = textwrap.dedent("""\
      %all-reduce.415 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}) all-reduce(%gte.1372, %gte.1371), channel_id=2, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%region_1.0.clone
      %all-reduce.7 = bf16[7,7,3,64]{3,2,1,0:T(8,128)(2,1)} all-reduce(%fusion.9), channel_id=9, replica_groups=[1,4]<=[4], to_apply=%add
      %ag = (bf16[192,768]{1,0:T(8,128)(2,1)}, bf16[768,768]{1,0:T(8,128)(2,1)}) all-gather-start(%p), dimensions={0}
    """)
    ops = collective_inventory(text)
    assert [(op.family, op.bytes) for op in ops] == [
        ("all-reduce", 2 * 64 * 4),
        ("all-reduce", 7 * 7 * 3 * 64 * 2),
        ("all-gather", (192 + 768) * 768 * 2),
    ]
    assert not any(op.scalar for op in ops)


def test_collective_inventory_names_the_array_a_start_tuple_moves():
    """A ``-start`` result is a tuple: (operand, result) for an all-gather,
    (operand, result, u32[], u32[]) for a collective-permute. The op's
    ``shape`` is the array that crosses the wire, not the tuple's last
    element (lines from the FSDP step compiled for a described v5e:2x2)."""
    text = textwrap.dedent("""\
      %cp = (bf16[1280,960]{0,1:T(8,128)(2,1)}, bf16[1280,960]{0,1:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%b), channel_id=45, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
      %ag = (bf16[1280,960]{0,1:T(8,128)(2,1)}, bf16[1280,3840]{0,1:T(8,128)(2,1)}) all-gather-start(%p), dimensions={1}
      %ar = (f32[1280]{0:T(1024)}, f32[3840]{0:T(1024)}) all-reduce(%a, %b), channel_id=3
    """)
    ops = collective_inventory(text)
    assert [(op.family, op.dtype, op.shape) for op in ops] == [
        ("collective-permute", "bf16", (1280, 960)),
        ("all-gather", "bf16", (1280, 3840)),
        ("all-reduce", "f32", (3840,)),
    ]


@pytest.mark.parametrize("shape,is_activation", [
    ((1280, 3840), False),        # a parameter, gathered
    ((1280, 960), False),         # its shard: one step of a ring
    ((4, 1, 1280), False),        # a bias of 5120, reshaped on the way
    ((16, 1024, 1280), True),     # the residual stream
    ((16, 1024, 320), True),      # ... a shard of it
    ((4, 4, 1024, 320), True),    # the embedding's row exchange
])
def test_activation_collectives_are_told_by_element_count(
        shape, is_activation):
    counts = hlo_mod.parameter_element_counts(
        [(1280, 3840), (5120,), (50257, 1280)], [1, 4])
    op = hlo_mod.CollectiveOp("all-gather", "bf16", shape, 2, False)
    assert (hlo_mod.activation_collectives([op], counts) == [op]) \
        == is_activation


def test_fsdp_signature_refuses_a_collective_on_an_activation():
    """``FullyShardedDataParallel.collective_signature()["activations"]``
    is ``"local"``: the auditor reports a tensor-grade collective that is
    no parameter, gradient or shard of one, and passes the FSDP set."""
    import types

    from pytorch_distributed_tpu.analysis.ir.audit import collective_findings
    from pytorch_distributed_tpu.mesh import init_device_mesh
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel

    strategy = FullyShardedDataParallel(
        init_device_mesh((8,), ("fsdp",)), min_shard_size=8)
    assert strategy.collective_signature()["activations"] == "local"
    params = {"kernel": jax.ShapeDtypeStruct((64, 256), jnp.float32),
              "head": jax.ShapeDtypeStruct((256, 16), jnp.float32)}
    program = types.SimpleNamespace(
        name="fsdp:stub", strategy=strategy,
        state=types.SimpleNamespace(params=params))
    gather = hlo_mod.CollectiveOp("all-gather", "f32", (64, 256),
                                  64 * 256 * 4, False)
    reduce = hlo_mod.CollectiveOp("all-reduce", "f32", (64, 32),
                                  64 * 32 * 4, False)
    assert collective_findings(program, [gather, reduce]) == []
    moved = hlo_mod.CollectiveOp("all-gather", "f32", (32, 256),
                                 32 * 256 * 4, False)
    found = collective_findings(program, [gather, reduce, moved])
    assert len(found) == 1 and "activation" in found[0].message


def test_summarize_separates_scalar_grade():
    summary = summarize_collectives(collective_inventory(SAMPLE_HLO))
    assert summary["tensor"]["all-reduce"] == {
        "count": 1, "bytes": 256 * 10 * 4,
    }
    assert summary["scalar"]["all-reduce"] == {"count": 1, "bytes": 4}
    assert "all-gather" not in summary["scalar"]


def test_dtype_bytes_table():
    assert hlo_mod.dtype_bytes("f32") == 4
    assert hlo_mod.dtype_bytes("bf16") == 2
    assert hlo_mod.dtype_bytes("pred") == 1
    assert hlo_mod.dtype_bytes("mystery") == 4  # conservative default


def test_aliased_param_indices_reads_module_header():
    assert hlo_mod.aliased_param_indices(SAMPLE_HLO) == [0, 2]
    assert hlo_mod.aliased_param_indices("HloModule bare\n") == []


def test_intended_alias_count_reads_stablehlo_attr():
    text = (
        'func.func public @main(%arg0: tensor<4xf32> '
        '{tf.aliasing_output = 0 : i32}, %arg1: tensor<4xf32> '
        '{tf.aliasing_output = 1 : i32}) -> ...'
    )
    assert hlo_mod.intended_alias_count(text) == 2
    assert hlo_mod.intended_alias_count("no annotations") == 0


# -- real step programs: the audits pass on the repo's own trainers --------

@pytest.fixture(scope="module")
def dp_program():
    return build_program("dp", "fp32")


@pytest.fixture(scope="module")
def zero1_program():
    return build_program("zero1", "fp32")


@pytest.fixture(scope="module")
def dp_audit(dp_program):
    return audit_program(dp_program)


@pytest.fixture(scope="module")
def zero1_audit(zero1_program):
    return audit_program(zero1_program)


def _param_bytes(program):
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jtu.tree_leaves(program.state.params)
    )


def test_dp_audit_clean_with_expected_budget(dp_program, dp_audit):
    assert not dp_audit.findings, [f.render() for f in dp_audit.findings]
    tensor = dp_audit.entry["collectives"]["tensor"]
    # pure DP: the grad all-reduce moves the parameter bytes, and params
    # are never gathered. The step's scalar metrics (loss, accuracy) are
    # all-reduced too, and whether they ride in the gradients' combined
    # all-reduce (8 bytes on cpu x 8) or in their own is the compiler's
    # choice: all-reduced bytes of both grades, held to the parameters'
    # plus at most sixteen f32 scalars.
    scalar = dp_audit.entry["collectives"]["scalar"].get("all-reduce", {})
    moved = tensor["all-reduce"]["bytes"] + scalar.get("bytes", 0)
    assert 0 <= moved - _param_bytes(dp_program) <= 16 * 4
    assert "all-gather" not in tensor
    donation = dp_audit.entry["donation"]
    assert donation["donated"] == donation["realized"] > 0


def test_zero1_audit_clean_with_delta_gather_budget(
    zero1_program, zero1_audit
):
    assert not zero1_audit.findings, (
        [f.render() for f in zero1_audit.findings]
    )
    tensor = zero1_audit.entry["collectives"]["tensor"]
    # the delta all-gather reassembles exactly the sharded-update
    # leaves: both Dense kernels + the 256-wide bias; the 10-wide head
    # bias is below min_shard_size and replicates (the `indivisible`
    # fallback the sharding entry pins)
    assert tensor["all-gather"]["count"] == 3
    assert tensor["all-gather"]["bytes"] == (
        8 * 8 * 256 * 4 + 256 * 4 + 256 * 10 * 4
    )
    sharding = zero1_audit.entry["sharding"]
    assert sharding["declared_sharded"] == sharding["realized_sharded"] == 3
    assert sharding["fallbacks"] == {"indivisible": 1, "sharded": 3}


def test_runner_path_is_one_program_per_step(zero1_audit):
    runner = zero1_audit.entry["runner"]
    assert runner["dispatches"] == runner["submits"]
    assert runner["executables"] == 1
    assert runner["programs_per_step"] == 1.0
    # the fused pipelined step donates state AND metric ring, all realized
    d = runner["donation"]
    assert d["donated"] == d["realized"] == 14


def test_dropped_donation_is_caught():
    """The teeth: rebuild the zero1 step WITHOUT donate_argnums (the
    scratch-copy perturbation from the acceptance criteria) and the
    donation audit must name every un-aliased leaf."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    program = build_program("zero1", "fp32")
    trainer = program.trainer
    trainer._ensure_built(program.state)
    mesh = trainer.strategy.mesh.jax_mesh
    trainer._step_fn = jax.jit(
        trainer._make_step_fn(),
        out_shardings=(trainer.state_shardings, NamedSharding(mesh, P())),
    )
    lowered, compiled = trainer.step_artifacts(
        program.state, program.batch, program.rng
    )
    entry, findings = donation_findings(
        program.name, lowered.as_text(), compiled.as_text(),
        program.donated_leaf_paths(),
    )
    assert entry["realized"] == 0
    assert len(findings) == program.donated_leaf_count() == 9
    assert all(f.rule == "ir-donation-aliasing" for f in findings)
    assert any("Dense_0" in f.message for f in findings)


def test_budget_diff_names_the_drift(zero1_audit):
    report = AuditReport(
        grid="fast", platform=jax.default_backend(),
        device_count=len(jax.devices()), audits=[zero1_audit],
    )
    payload = budget_mod.budget_payload(report)
    same, diffs = budget_mod.diff_budget(payload, report)
    assert same and not diffs

    mutated = copy.deepcopy(payload)
    mutated["programs"]["zero1:fp32"]["donation"]["realized"] = 0
    comparable, diffs = budget_mod.diff_budget(mutated, report)
    assert comparable
    assert any(
        "donation.realized" in d and "0 -> 9" in d for d in diffs
    ), diffs

    foreign = dict(payload, platform="tpu")
    comparable, notes = budget_mod.diff_budget(foreign, report)
    assert not comparable and notes


def test_budget_fingerprint_tracks_content(zero1_audit):
    report = AuditReport(
        grid="fast", platform="cpu", device_count=8, audits=[zero1_audit],
    )
    a = budget_mod.budget_payload(report)
    b = budget_mod.budget_payload(report)
    assert a["fingerprint"] == b["fingerprint"]
    report.audits[0].entry["donation"]["realized"] = 0
    try:
        c = budget_mod.budget_payload(report)
    finally:
        report.audits[0].entry["donation"]["realized"] = 9
    assert c["fingerprint"] != a["fingerprint"]


# -- donation sweep: every other in-tree donate_argnums site ---------------

def test_fork_pages_donation_realized():
    """The paged COW fork donates the whole cache pytree (arg 0): all
    four leaves must alias, or every fork would copy the page pool."""
    from pytorch_distributed_tpu.models import GPT2Config
    from pytorch_distributed_tpu.serving.paging import (
        PagedKVCache, fork_pages,
    )

    cfg = GPT2Config(vocab_size=32, n_positions=32, n_embd=16,
                     n_layer=2, n_head=2)
    cache = PagedKVCache.create(cfg, n_slots=2, max_len=16, page_size=4)
    lowered = fork_pages.lower(cache, 1, 2)
    compiled = lowered.compile()
    paths = [
        f"cache{jtu.keystr(p)}"
        for p, _ in jtu.tree_leaves_with_path(cache)
    ]
    entry, findings = donation_findings(
        "fork_pages", lowered.as_text(), compiled.as_text(), paths
    )
    assert not findings, [f.render() for f in findings]
    assert entry["donated"] == entry["realized"] == 4


def test_redistribute_update_donation_realized():
    """The chunked-copy staging buffer (redistribute.executor
    donated_update_jit) must alias in place — an extra copy here doubles
    the bounded staging footprint the chunked path exists to bound."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.mesh import init_device_mesh
    from pytorch_distributed_tpu.redistribute import donated_update_jit

    n = len(jax.devices())
    mesh = init_device_mesh((n,), ("dp",))
    target = NamedSharding(mesh.jax_mesh, P("dp"))
    update = donated_update_jit(target, 0)
    buf = jax.device_put(jnp.zeros((2 * n, 4), jnp.float32), target)
    piece = jax.device_put(jnp.ones((n, 4), jnp.float32), target)
    lowered = update.lower(buf, piece, 0)
    compiled = lowered.compile()
    entry, findings = donation_findings(
        "redistribute.update", lowered.as_text(), compiled.as_text(),
        ["staging buffer"],
    )
    assert not findings, [f.render() for f in findings]
    assert entry["realized"] == 1


def test_serving_decode_donation_realized():
    """The decode step donates the KV cache *after* the params in the
    flat signature — the offset form of the audit. All cache leaves
    must alias or every decode step would copy the whole cache."""
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.serving import InferenceEngine, KVCache

    cfg = GPT2Config(vocab_size=97, n_positions=32, n_embd=32,
                     n_layer=2, n_head=2, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    engine = InferenceEngine(model, variables, n_slots=2, max_len=16)
    cache = KVCache.create(cfg, n_slots=2, max_len=16)
    last = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    lowered = engine._decode.lower(
        engine.params, cache, last, active, engine._next_rng()
    )
    compiled = lowered.compile()
    paths = [
        f"cache{jtu.keystr(p)}"
        for p, _ in jtu.tree_leaves_with_path(cache)
    ]
    entry, findings = donation_findings(
        "serving.decode", lowered.as_text(), compiled.as_text(), paths,
        offset=len(jtu.tree_leaves(engine.params)),
    )
    assert not findings, [f.render() for f in findings]
    assert entry["donated"] == entry["realized"] == len(paths)


def test_donation_site_sweep_is_complete():
    """Every ``donate_argnums=`` site in the tree is either audited by
    graftir (trainer step, runner _pstep) or covered by the sweep tests
    above (fork_pages, redistribute update, serving engine programs).
    Checkpoint restore donates nothing: restored state adopts its
    shardings via Trainer._ensure_shardings and flows into the (donating)
    step like any other state — there is no separate restore jit. A new
    donation site must be added here WITH an aliasing test."""
    audited = {
        "pytorch_distributed_tpu/trainer.py",
        "pytorch_distributed_tpu/pipeline_exec/runner.py",
        "pytorch_distributed_tpu/redistribute/executor.py",
        "pytorch_distributed_tpu/serving/paging/kv_cache.py",
        "pytorch_distributed_tpu/serving/engine.py",
    }
    found = set()
    pkg = os.path.join(REPO_ROOT, "pytorch_distributed_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "analysis"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as fh:
                if "donate_argnums=" in fh.read():
                    found.add(os.path.relpath(path, REPO_ROOT))
    assert found == audited, (
        f"donation sites changed: +{found - audited} -{audited - found} "
        f"— extend the graftir donation sweep for new sites"
    )


# -- the tier-1 gate -------------------------------------------------------

def _run_graftir(*argv):
    return subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_tpu.analysis.ir",
         *argv],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )


def test_repo_ir_is_clean():
    """The CI gate: the fast grid (DP + ZeRO1 × fp32/fp16) audits clean
    AND matches the committed BUDGET.json — collective bytes, donation
    aliasing, programs-per-step, sharding propagation."""
    proc = _run_graftir("--grid", "fast", "--diff", "--format", "json")
    assert proc.returncode == 0, (
        f"graftir found regressions:\n{proc.stdout}\n{proc.stderr}"
    )
    payload = json.loads(proc.stdout)
    assert payload["summary"]["findings"] == 0
    assert payload["summary"]["files"] == 4  # 4 programs in the fast grid
    assert payload["summary"]["rules_run"] == sorted(CHECKS)


def test_cli_list_checks():
    proc = _run_graftir("--list-checks")
    assert proc.returncode == 0
    for name in CHECKS:
        assert name in proc.stdout


@pytest.mark.slow
def test_repo_ir_full_grid_is_clean():
    """Full strategy × AMP grid (adds FSDP + Hybrid) against the same
    committed budget — the grid the baseline was stamped from."""
    proc = _run_graftir("--grid", "full", "--diff", "--format", "json")
    assert proc.returncode == 0, (
        f"graftir found regressions:\n{proc.stdout}\n{proc.stderr}"
    )
    payload = json.loads(proc.stdout)
    assert payload["summary"]["findings"] == 0
    assert payload["summary"]["files"] == 8
