"""graftlint: fixture tests (every rule fires on its bad example and
stays quiet on the good one), suppression semantics, JSON/baseline
plumbing, config parsing — and the tier-1 gate that keeps the repo tree
itself at zero findings."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from pytorch_distributed_tpu.analysis import (
    all_rules,
    analyze_source,
    get_rules,
)
from pytorch_distributed_tpu.analysis import baseline as baseline_mod
from pytorch_distributed_tpu.analysis import config as config_mod
from pytorch_distributed_tpu.analysis.cli import main as cli_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(src, rules=None, require_justification=True):
    cfg = {"enable": list(rules)} if rules else {}
    return analyze_source(
        "fixture.py", textwrap.dedent(src), get_rules(cfg),
        require_justification=require_justification,
    )


def rule_names(result):
    return sorted({f.rule for f in result.findings})


# -- fixtures: each rule fires on bad, stays quiet on good -----------------

HOST_SYNC_BAD = """
    import jax.numpy as jnp

    def train_loop(state, batches):
        losses = []
        for b in batches:
            loss = jnp.mean(b)
            losses.append(float(loss))
        return losses
"""

HOST_SYNC_GOOD = """
    import jax.numpy as jnp

    def train_loop(state, batches):
        losses = []
        for b in batches:
            loss = jnp.mean(b)
            losses.append(loss)
        return [float(l) for l in losses]
"""

HOST_SYNC_DICT_BAD = """
    import jax

    def make_step():
        def f(state, batch):
            return state, {"loss": batch.mean()}
        return f

    step = jax.jit(make_step())

    def train_epoch(state, batches):
        losses = []
        for b in batches:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
        return state, losses
"""

HOST_SYNC_DICT_GOOD = """
    import jax

    def make_step():
        def f(state, batch):
            return state, {"loss": batch.mean()}
        return f

    step = jax.jit(make_step())

    def train_epoch(state, batches):
        metrics = None
        for b in batches:
            state, metrics = step(state, b)
        return state, float(metrics["loss"])
"""

COMM_STAGING_BAD = """
    import numpy as np

    def exchange_sizes(pg, payload):
        return pg.all_gather(np.array([payload.size], np.int64))
"""

COMM_STAGING_GOOD = """
    import numpy as np

    def exchange_sizes(pg, payload, scratch):
        scratch[0] = payload.size
        return pg.all_gather(scratch)
"""

RECOMPILE_BAD = """
    import jax

    def run(params, batches):
        out = None
        for b in batches:
            out = jax.jit(lambda p, x: p + x)(params, b)
        return out
"""

RECOMPILE_GOOD = """
    import jax

    def run(params, batches):
        step = jax.jit(lambda p, x: p + x)
        out = None
        for b in batches:
            out = step(params, b)
        return out
"""

RECOMPILE_TRACED_BRANCH_BAD = """
    import jax

    @jax.jit
    def absval(x):
        if x > 0:
            return x
        return -x
"""

RECOMPILE_SHAPE_BRANCH_GOOD = """
    import jax

    @jax.jit
    def maybe_squeeze(x):
        if x.ndim > 2:
            return x.reshape(x.shape[0], -1)
        return x
"""

AXIS_BAD = """
    import jax
    from jax import lax

    f = jax.pmap(lambda x: lax.psum(x, "bath"), axis_name="batch")
"""

AXIS_GOOD = """
    import jax
    from jax import lax

    f = jax.pmap(lambda x: lax.psum(x, "batch"), axis_name="batch")
"""

DONATION_BAD = """
    import jax

    step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))

    def train(state, batch):
        new_state = step(state, batch)
        return state.mean()
"""

DONATION_GOOD = """
    import jax

    step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))

    def train(state, batch):
        state = step(state, batch)
        return state.mean()
"""

TRACER_LEAK_BAD = """
    import jax

    def make_step():
        losses = []

        @jax.jit
        def step(params, batch):
            loss = (params * batch).sum()
            losses.append(loss)
            return loss

        return step
"""

TRACER_LEAK_GOOD = """
    import jax

    def make_step():
        @jax.jit
        def step(params, batch):
            return (params * batch).sum()

        return step
"""

RNG_BAD = """
    import jax

    def init(d):
        k = jax.random.key(0)
        w1 = jax.random.normal(k, (d, d))
        w2 = jax.random.normal(k, (d, d))
        return w1, w2
"""

RNG_GOOD = """
    import jax

    def init(d):
        k1, k2 = jax.random.split(jax.random.key(0))
        w1 = jax.random.normal(k1, (d, d))
        w2 = jax.random.normal(k2, (d, d))
        return w1, w2
"""

RNG_LOOP_BAD = """
    import jax

    def sample_loop(key, n):
        outs = []
        for i in range(n):
            outs.append(jax.random.normal(key, (2,)))
        return outs
"""

RNG_LOOP_GOOD = """
    import jax

    def sample_loop(key, n):
        outs = []
        for i in range(n):
            k = jax.random.fold_in(key, i)
            outs.append(jax.random.normal(k, (2,)))
        return outs
"""

UNCOALESCED_BAD = """
    import jax

    def sync_grads(pg, grads):
        outs = []
        for leaf in jax.tree_util.tree_leaves(grads):
            outs.append(pg.all_reduce(leaf))
        return outs

    def bcast_params(pg, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return [pg.broadcast(l, src=0) for l in leaves]
"""

UNCOALESCED_GOOD = """
    import jax
    from jax import lax

    def sync_grads(pg, grads):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out = pg.all_reduce_coalesced(leaves)
        return jax.tree_util.tree_unflatten(treedef, out)

    def in_jit_is_fused(xs):
        # lax collectives under jit: XLA coalesces across leaves itself
        return [lax.all_gather(l, "dp")
                for l in jax.tree_util.tree_leaves(xs)]

    def leaf_loop_without_collective(grads):
        for leaf in jax.tree_util.tree_leaves(grads):
            print(leaf.shape)

    def collective_not_on_leaf(pg, grads, staged):
        for leaf in jax.tree_util.tree_leaves(grads):
            pg.all_reduce(staged)
"""

RESHARD_BAD = """
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def reshard_onto_tp(x, mesh):
        return jax.device_put(x, NamedSharding(mesh, P(None, "tp")))

    def gather_then_slice(x, lo, width):
        g = lax.all_gather(x, "tp", tiled=True)
        return lax.dynamic_slice_in_dim(g, lo, width, 1)
"""

RESHARD_GOOD = """
    import jax
    from jax import lax
    from pytorch_distributed_tpu.redistribute import redistribute

    def reshard_onto_tp(x, target_sharding):
        # unknown-provenance parameter: not flagged; the planner is used
        return redistribute(x, target_sharding)

    def plain_placement(x, cpu_device):
        # device_put onto a *device* is placement, not a reshard
        return jax.device_put(x, cpu_device)

    def gather_only(x):
        # gather without the slice-back-down is a legitimate collective
        return lax.all_gather(x, "tp", tiled=True)

    def slice_fresh(x, lo, width):
        # slicing something that was never gathered
        return lax.dynamic_slice_in_dim(x, lo, width, 1)
"""

RESHARD_LOOP_BAD = """
    from jax import lax
    import jax.tree_util as jtu

    def manual_fsdp_sync(grads):
        # FlatParameter-style per-param unshard/reshard, written by hand
        synced = []
        for g in jtu.tree_leaves(grads):
            full = lax.all_gather(g, "fsdp", tiled=True)
            synced.append(lax.psum_scatter(
                full, "fsdp", scatter_dimension=0, tiled=True))
        return synced

    def manual_zero_update(grads):
        return [
            lax.dynamic_slice_in_dim(
                lax.all_gather(g, "dp", tiled=True), 0, 8, 0)
            for g in jtu.tree_leaves(grads)
        ]
"""

RESHARD_LOOP_GOOD = """
    from jax import lax
    import jax.tree_util as jtu

    def in_jit_gather_only(xs):
        # gather WITHOUT the scatter half: a legitimate in-jit collective
        # (and XLA's to fuse) — not an unshard/reshard pair
        return [
            lax.all_gather(l, "fsdp", tiled=True)
            for l in jtu.tree_leaves(xs)
        ]

    def slice_fresh_leaves(xs):
        # slicing leaves that were never gathered
        return [
            lax.dynamic_slice_in_dim(l, 0, 4, 0)
            for l in jtu.tree_leaves(xs)
        ]

    def annotated_update(strategy, grads):
        # the sanctioned form: the layout change is a sharding annotation
        from pytorch_distributed_tpu.parallel import shard_grads
        return shard_grads(strategy, grads)
"""

FIXTURES = [
    ("host-sync-in-hot-loop", HOST_SYNC_BAD, HOST_SYNC_GOOD),
    ("host-sync-in-hot-loop", HOST_SYNC_DICT_BAD, HOST_SYNC_DICT_GOOD),
    ("comm-staging", COMM_STAGING_BAD, COMM_STAGING_GOOD),
    ("recompile-hazard", RECOMPILE_BAD, RECOMPILE_GOOD),
    ("recompile-hazard", RECOMPILE_TRACED_BRANCH_BAD,
     RECOMPILE_SHAPE_BRANCH_GOOD),
    ("collective-axis-mismatch", AXIS_BAD, AXIS_GOOD),
    ("donated-buffer-reuse", DONATION_BAD, DONATION_GOOD),
    ("tracer-leak", TRACER_LEAK_BAD, TRACER_LEAK_GOOD),
    ("rng-key-reuse", RNG_BAD, RNG_GOOD),
    ("rng-key-reuse", RNG_LOOP_BAD, RNG_LOOP_GOOD),
    ("uncoalesced-collective", UNCOALESCED_BAD, UNCOALESCED_GOOD),
    ("hand-rolled-reshard", RESHARD_BAD, RESHARD_GOOD),
    ("hand-rolled-reshard", RESHARD_LOOP_BAD, RESHARD_LOOP_GOOD),
]


@pytest.mark.parametrize(
    "rule,bad,good", FIXTURES,
    ids=[f"{r}-{i}" for i, (r, _, _) in enumerate(FIXTURES)],
)
def test_rule_fires_on_bad_and_not_on_good(rule, bad, good):
    bad_result = run_lint(bad)
    assert rule in rule_names(bad_result), (
        f"{rule} did not fire on its bad fixture; "
        f"got {rule_names(bad_result)}"
    )
    good_result = run_lint(good)
    assert not good_result.findings, (
        f"false positives on the good fixture for {rule}: "
        f"{[f.render() for f in good_result.findings]}"
    )


def test_all_nine_rules_registered():
    assert set(all_rules()) == {
        "host-sync-in-hot-loop", "comm-staging", "recompile-hazard",
        "collective-axis-mismatch", "donated-buffer-reuse",
        "tracer-leak", "rng-key-reuse", "uncoalesced-collective",
        "hand-rolled-reshard",
    }


# -- precision regressions (true stories from this repo's own tree) --------

def test_host_sync_device_step_methods_config():
    """`trainer.step(...)` has no visible jit binding — the
    device_step_methods config key marks such methods device-returning
    so float(m["loss"]) in the loop is still caught."""
    src = """
        def train_epoch(trainer, state, batches):
            losses = []
            for b in batches:
                state, m = trainer.step(state, b)
                losses.append(float(m["loss"]))
            return state, losses
    """
    # without the key: trainer.step is opaque -> no finding
    quiet = analyze_source(
        "fixture.py", textwrap.dedent(src),
        get_rules({"enable": ["host-sync-in-hot-loop"]}),
    )
    assert not quiet.findings
    loud = analyze_source(
        "fixture.py", textwrap.dedent(src),
        get_rules({"enable": ["host-sync-in-hot-loop"],
                   "device_step_methods": ["step"]}),
    )
    assert rule_names(loud) == ["host-sync-in-hot-loop"]


def test_host_sync_literal_tuple_unpack_stays_unknown():
    # `a, b = x, y` swap-style unpack must NOT inherit the tuple's
    # merged provenance per element (elements differ)
    result = run_lint("""
        import jax.numpy as jnp

        def train_epoch(batches):
            out = []
            for b in batches:
                d, h = jnp.mean(b), 3.0
                out.append(float(h))
            return out
    """)
    assert not result.findings


def test_rng_branches_are_alternatives_not_sequence():
    # one sampler call per if/else arm is one draw at runtime
    result = run_lint("""
        import jax

        def apply(key, train):
            if train:
                return jax.random.normal(key, (2,))
            else:
                return jax.random.uniform(key, (2,))
    """)
    assert not result.findings


def test_rng_store_key_param_is_not_a_prng_key():
    # a parameter merely NAMED `key` in code that never touches
    # jax.random (a KV-store key) must not count
    result = run_lint("""
        def put(store, key, value):
            store.set(key, value)
            store.log(key)
            return key
    """)
    assert not result.findings


def test_rng_confirmed_key_passed_to_unknown_callable_counts():
    result = run_lint("""
        import jax

        def f(d, sample):
            k = jax.random.key(0)
            a = jax.random.normal(k, (d,))
            b = sample(k)
            return a, b
    """)
    assert "rng-key-reuse" in rule_names(result)


def test_tracer_leak_ignores_value_returning_update_calls():
    # new_state = optimizer.update(...) flows through the trace normally
    result = run_lint("""
        import jax

        def make_step(optimizer):
            @jax.jit
            def step(opt_state, grads):
                updates, new_state = optimizer.update(grads, opt_state)
                return updates, new_state

            return step
    """)
    assert not result.findings


def test_reshard_name_assigned_from_sharding_ctor_counts():
    # provenance flows through a local name, not just inline ctor calls
    result = run_lint("""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(x, mesh):
            target = NamedSharding(mesh, P("dp"))
            return jax.device_put(x, target)
    """)
    assert "hand-rolled-reshard" in rule_names(result)


def test_reshard_unknown_provenance_attribute_not_flagged():
    # self.cache_sharding could be anything — precision over recall
    result = run_lint("""
        import jax

        class Engine:
            def place(self, x):
                return jax.device_put(x, self.cache_sharding)
    """)
    assert not result.findings


def test_reshard_allowed_path_exempts_planner_files():
    cfg = {"reshard_allowed_paths": ["pkg/redistribute"]}
    src = textwrap.dedent("""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def step(x, mesh):
            return jax.device_put(x, NamedSharding(mesh, P("dp")))
    """)
    inside = analyze_source(
        "pkg/redistribute/executor.py", src, get_rules(cfg))
    assert not inside.findings
    outside = analyze_source("pkg/serving/engine.py", src, get_rules(cfg))
    assert "hand-rolled-reshard" in rule_names(outside)


def test_reshard_suppression_with_justification():
    result = run_lint("""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def first_placement(x, mesh):
            # graftlint: disable-next-line=hand-rolled-reshard -- fresh host batch, no source sharding to plan from
            return jax.device_put(x, NamedSharding(mesh, P("dp")))
    """)
    assert not result.findings
    assert len(result.suppressed) == 1


def test_host_sync_unknown_provenance_not_flagged():
    # int() on a host/unknown value inside a hot loop is fine
    result = run_lint("""
        def decode_loop(batches):
            total = 0
            for b in batches:
                total += int(b["n_tokens"])
            return total
    """)
    assert not result.findings


# -- suppressions ----------------------------------------------------------

def test_same_line_suppression_with_justification():
    result = run_lint("""
        import jax.numpy as jnp

        def train_loop(batches):
            for b in batches:
                loss = jnp.mean(b)
                print(float(loss))  # graftlint: disable=host-sync-in-hot-loop -- debug epoch log
    """)
    assert not result.findings
    assert len(result.suppressed) == 1


def test_next_line_suppression():
    result = run_lint("""
        import jax.numpy as jnp

        def train_loop(batches):
            for b in batches:
                loss = jnp.mean(b)
                # graftlint: disable-next-line=host-sync-in-hot-loop -- debug epoch log
                print(float(loss))
    """)
    assert not result.findings
    assert len(result.suppressed) == 1


def test_unjustified_suppression_is_itself_a_finding():
    result = run_lint("""
        import jax.numpy as jnp

        def train_loop(batches):
            for b in batches:
                loss = jnp.mean(b)
                print(float(loss))  # graftlint: disable=host-sync-in-hot-loop
    """)
    assert rule_names(result) == ["unjustified-suppression"]
    assert len(result.suppressed) == 1


def test_unused_suppression_is_reported():
    result = run_lint("""
        def quiet():
            # graftlint: disable-next-line=host-sync-in-hot-loop -- nothing here
            return 1
    """)
    assert rule_names(result) == ["unused-suppression"]


def test_directive_inside_docstring_is_documentation():
    result = run_lint('''
        def helper():
            """Example: x.item()  # graftlint: disable=host-sync-in-hot-loop -- why"""
            return 1
    ''')
    assert not result.findings


def test_no_justification_check_flag():
    result = run_lint("""
        import jax.numpy as jnp

        def train_loop(batches):
            for b in batches:
                loss = jnp.mean(b)
                print(float(loss))  # graftlint: disable=host-sync-in-hot-loop
    """, require_justification=False)
    assert not result.findings


# -- reporters / baseline / CLI --------------------------------------------

def test_json_output_shape(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(COMM_STAGING_BAD))
    rc = cli_main([str(bad), "--format", "json", "--no-config"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["version"] == 1
    assert payload["summary"]["findings"] == len(payload["findings"]) == 1
    (finding,) = payload["findings"]
    assert finding["rule"] == "comm-staging"
    assert finding["line"] > 0
    assert "comm-staging" in payload["summary"]["rules_run"]


def test_baseline_round_trip(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(COMM_STAGING_BAD))
    base = tmp_path / "base.json"

    rc = cli_main([str(bad), "--write-baseline", str(base), "--no-config"])
    assert rc == 0
    capsys.readouterr()

    # baselined finding no longer fails the run...
    rc = cli_main([str(bad), "--baseline", str(base), "--no-config"])
    assert rc == 0
    capsys.readouterr()

    # ...but a NEW finding still does, and line moves don't resurrect
    # the baselined one (fingerprints are line-insensitive)
    bad.write_text(
        "\n\n" + textwrap.dedent(COMM_STAGING_BAD) + textwrap.dedent("""
        def broadcast_size(pg, n):
            import numpy as np
            return pg.broadcast(np.array([n]), 0)
        """)
    )
    rc = cli_main(
        [str(bad), "--baseline", str(base), "--format", "json",
         "--no-config"]
    )
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["summary"]["findings"] == 1
    assert payload["summary"]["baselined"] == 1
    assert payload["findings"][0]["symbol"].endswith("broadcast_size")


def test_baseline_rejects_unknown_version(tmp_path):
    base = tmp_path / "base.json"
    base.write_text('{"version": 99, "fingerprints": []}')
    with pytest.raises(ValueError):
        baseline_mod.load_baseline(str(base))


def test_cli_unknown_rule_is_config_error(tmp_path, capsys):
    src = tmp_path / "x.py"
    src.write_text("x = 1\n")
    rc = cli_main([str(src), "--rules", "no-such-rule", "--no-config"])
    capsys.readouterr()
    assert rc == 2


def test_parse_error_is_reported(tmp_path, capsys):
    src = tmp_path / "broken.py"
    src.write_text("def f(:\n")
    rc = cli_main([str(src), "--format", "json", "--no-config"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["findings"][0]["rule"] == "parse-error"


# -- config ----------------------------------------------------------------

def test_config_block_parses(tmp_path):
    py = tmp_path / "pyproject.toml"
    py.write_text(textwrap.dedent("""
        [tool.other]
        x = 1

        [tool.graftlint]
        enable = [
            "comm-staging",
            "rng-key-reuse",
        ]
        exclude = ["examples"]
        known_axes = ["dp", "tp"]

        [tool.after]
        y = 2
    """))
    cfg = config_mod.load_config(str(py))
    assert cfg["enable"] == ["comm-staging", "rng-key-reuse"]
    assert cfg["known_axes"] == ["dp", "tp"]
    assert "examples" in config_mod.effective_excludes(cfg)
    assert [r.name for r in get_rules(cfg)] == [
        "comm-staging", "rng-key-reuse"
    ]


def test_config_unknown_key_fails_loudly(tmp_path):
    py = tmp_path / "pyproject.toml"
    py.write_text("[tool.graftlint]\nenbale = [\"comm-staging\"]\n")
    with pytest.raises(ValueError, match="enbale"):
        config_mod.load_config(str(py))


def test_repo_config_enables_all_rules():
    cfg = config_mod.load_config(os.path.join(REPO_ROOT, "pyproject.toml"))
    assert set(cfg["enable"]) == set(all_rules())


# -- cross-file jit-binding resolution (the project index) -----------------

LINT_LIB = """
    import jax

    def _impl(buf, x):
        return buf + x

    fork = jax.jit(_impl, donate_argnums=(0,))
    stat = jax.jit(_impl, static_argnums=(1,))
"""

LINT_APP = """
    from pkg.lib import fork
    import pkg.lib as plib

    def donated_read(buf, x):
        out = fork(buf, x)
        print(buf)                # read after donation -> finding
        return out

    def rebound_is_clean(buf, x):
        buf = plib.fork(buf, x)   # module-attr spelling, rebinds
        return buf

    def unhashable_static(buf):
        from pkg.lib import stat
        return stat(buf, [1, 2])  # list in a static position -> finding
"""


def _analyze_pkg(tmp_path, monkeypatch, files):
    from pytorch_distributed_tpu.analysis.core import analyze_paths

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    monkeypatch.chdir(tmp_path)
    return analyze_paths(["pkg"], get_rules())


def test_module_name_for_path():
    from pytorch_distributed_tpu.analysis.core import module_name_for_path

    assert module_name_for_path("a/b/c.py") == "a.b.c"
    assert module_name_for_path("a/b/__init__.py") == "a.b"


def test_cross_file_donated_read_is_found(tmp_path, monkeypatch):
    """A donation spec declared in one module must follow its binding
    through a from-import: reading the donated buffer in the importing
    module is the same deleted-on-TPU crash."""
    res = _analyze_pkg(tmp_path, monkeypatch,
                       {"lib.py": LINT_LIB, "app.py": LINT_APP})
    donated = [f for f in res.findings if f.rule == "donated-buffer-reuse"]
    assert donated, [f.render() for f in res.findings]
    assert all("donated_read" in f.symbol for f in donated), donated
    # the rebinding caller (module-attr spelling) must stay clean
    assert not any("rebound_is_clean" in f.symbol for f in res.findings)


def test_cross_file_static_argnums_is_found(tmp_path, monkeypatch):
    res = _analyze_pkg(tmp_path, monkeypatch,
                       {"lib.py": LINT_LIB, "app.py": LINT_APP})
    recompile = [f for f in res.findings if f.rule == "recompile-hazard"]
    assert any("unhashable_static" in f.symbol for f in recompile), (
        [f.render() for f in res.findings]
    )


def test_single_file_analysis_has_no_project_index():
    """analyze_source (single file, no index) must not fire on imported
    bindings it cannot see — cross-file resolution is analyze_paths-only."""
    result = run_lint(LINT_APP)
    assert not result.findings


# -- import canonicalization (relative / aliased spellings) ----------------

def _imports(src, path):
    import ast

    from pytorch_distributed_tpu.analysis.core import Module

    source = textwrap.dedent(src)
    return Module(path, source, ast.parse(source)).imports


def test_relative_imports_canonicalize_to_absolute():
    """Relative imports must land on the absolute dotted names the
    ProjectIndex is keyed by, expanded against the importer's package."""
    imp = _imports("from .lib import fork\n", "pkg/app.py")
    assert imp["fork"] == "pkg.lib.fork"
    imp = _imports("from . import lib\n", "pkg/app.py")
    assert imp["lib"] == "pkg.lib"
    imp = _imports("from ..core import thing\n", "pkg/sub/mod.py")
    assert imp["thing"] == "pkg.core.thing"
    # a package __init__ is its own package: level-1 stays inside it
    imp = _imports("from .sibling import f\n", "pkg/__init__.py")
    assert imp["f"] == "pkg.sibling.f"
    imp = _imports("from .lib import fork as fk\n", "pkg/app.py")
    assert imp["fk"] == "pkg.lib.fork"


def test_relative_import_past_root_stays_unresolved():
    """Climbing above the analyzed root cannot be resolved lexically —
    dropped (no guessed absolute name), never a wrong resolution."""
    imp = _imports("from ...mystery import f\n", "pkg/app.py")
    assert "f" not in imp


def test_aliased_module_import_spellings():
    imp = _imports("import pkg.lib as plib\n", "pkg/app.py")
    assert imp["plib"] == "pkg.lib"
    # un-aliased dotted import binds only the root package name
    imp = _imports("import pkg.lib\n", "other/app.py")
    assert imp["pkg"] == "pkg"


LINT_APP_RELATIVE = """
    from .lib import fork as fk
    from . import lib

    def donated_read(buf, x):
        out = fk(buf, x)
        print(buf)                # read after donation -> finding
        return out

    def attr_read(buf, x):
        out = lib.fork(buf, x)
        print(buf)                # aliased module-attr spelling resolves
        return out
"""


def test_cross_file_resolution_through_relative_imports(
    tmp_path, monkeypatch
):
    """The donation contract must follow relative-import and module-attr
    spellings of the same binding — both canonicalize to pkg.lib.fork."""
    res = _analyze_pkg(tmp_path, monkeypatch,
                       {"lib.py": LINT_LIB, "app.py": LINT_APP_RELATIVE})
    donated = [f for f in res.findings if f.rule == "donated-buffer-reuse"]
    symbols = {f.symbol for f in donated}
    assert any("donated_read" in s for s in symbols), (
        [f.render() for f in res.findings]
    )
    assert any("attr_read" in s for s in symbols), (
        [f.render() for f in res.findings]
    )


# -- --changed-only --------------------------------------------------------

def test_only_files_filters_rule_pass_but_keeps_index(
    tmp_path, monkeypatch
):
    """only_files narrows which files the rules run on, while the cross-
    file index still covers the whole tree — a changed caller is checked
    against an UNchanged library's donation contract."""
    from pytorch_distributed_tpu.analysis.core import analyze_paths

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "lib.py").write_text(textwrap.dedent(LINT_LIB))
    (pkg / "app.py").write_text(textwrap.dedent(LINT_APP))
    monkeypatch.chdir(tmp_path)

    res = analyze_paths(["pkg"], get_rules(),
                        only_files=[str(pkg / "app.py")])
    assert res.files == 1
    assert any(f.rule == "donated-buffer-reuse" for f in res.findings), (
        [f.render() for f in res.findings]
    )

    # ...and restricting to the (clean) library reports nothing: app.py's
    # findings are outside the changed set
    res = analyze_paths(["pkg"], get_rules(),
                        only_files=[str(pkg / "lib.py")])
    assert res.files == 1
    assert not res.findings


def test_changed_only_falls_back_outside_git(tmp_path, monkeypatch, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(COMM_STAGING_BAD))
    monkeypatch.chdir(tmp_path)
    rc = cli_main([str(bad), "--changed-only", "--no-config"])
    captured = capsys.readouterr()
    assert "not a git work tree" in captured.err
    assert rc == 1  # fell back to a full run, which sees the finding


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_changed_only_analyzes_only_changed_and_untracked(tmp_path):
    """In a git repo: a committed (unchanged) bad file is skipped, an
    untracked bad file is caught — the pre-commit contract."""
    committed = tmp_path / "committed_bad.py"
    committed.write_text(textwrap.dedent(COMM_STAGING_BAD))
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "committed_bad.py")
    _git(tmp_path, "commit", "-qm", "seed")
    untracked = tmp_path / "untracked_bad.py"
    untracked.write_text(textwrap.dedent(COMM_STAGING_BAD))

    proc = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_tpu.analysis",
         ".", "--changed-only", "--no-config", "--format", "json"],
        capture_output=True, text=True, cwd=tmp_path,
        env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    paths = {f["path"] for f in payload["findings"]}
    assert paths == {"untracked_bad.py"}, payload
    assert payload["summary"]["files"] == 1


# -- the tier-1 gate -------------------------------------------------------

def test_paging_subsystem_is_gated():
    """The paged-cache tree and its kernel lint clean on their own — an
    explicit gate so a suppression creeping into the paging files cannot
    hide inside the whole-package run's aggregate count."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_tpu.analysis",
         "pytorch_distributed_tpu/serving/paging/",
         "pytorch_distributed_tpu/ops/paged_attention.py",
         "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, (
        f"paging files have findings:\n{proc.stdout}\n{proc.stderr}"
    )
    payload = json.loads(proc.stdout)
    assert payload["summary"]["findings"] == 0
    assert payload["summary"]["suppressed"] == 0
    assert payload["summary"]["files"] >= 5


def test_repo_is_clean():
    """The whole package must lint clean: zero unsuppressed findings,
    and (because unjustified-suppression is itself a finding) every
    suppression in the tree carries a justification. chip_smoke.py is
    gated too — its step loops must not host-sync per step (the
    dict-subscript provenance extension catches float(m["loss"]) on
    jitted-call results)."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_tpu.analysis",
         "pytorch_distributed_tpu/", "chip_smoke.py", "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, (
        f"graftlint found regressions:\n{proc.stdout}\n{proc.stderr}"
    )
    payload = json.loads(proc.stdout)
    assert payload["summary"]["findings"] == 0
    assert len(payload["summary"]["rules_run"]) >= 7
