"""The readers of the program's own spans and sections, on hand-made traces
whose answers are known."""

import pytest

from chipbench import program_trace, trace_reduce
from chipbench.readers import (
    device_time_by_scope,
    program_idle_outside,
    program_span_duration,
    program_span_stat,
)
from chipbench.tests import handmade_program

US = 1e-6

#: a 100 us window; the device runs 10-30 and 50-80; the scheduler makes two
#: steps, each a decode (dispatch, then a read that waits for the device)
#: and a consume; one admission in the second step; a runner's submit with a
#: fence inside; a span of another thread that overlaps them all
SERVED = {
    "/device:TPU:0": {
        "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20),
                    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)", 50, 20),
                    ("%copy.3 = f32[8]{0} copy(f32[8]{0} %r)", 70, 10)],
    },
    "/host:CPU": {
        "python3": [
            ("cb.window", 0, 100),
            ("pdt.sched.step", 2, 38, {"step": 7, "n_active": 2}),
            ("pdt.engine.decode", 4, 28),
            ("pdt.engine.decode.dispatch", 4, 4, {"executables": 1}),
            ("pdt.engine.decode.read", 8, 24),
            ("pdt.sched.consume", 33, 6, {"tokens": 2, "finished": 1}),
            ("pdt.sched.step", 42, 50, {"step": 8, "n_active": 1}),
            ("pdt.sched.admit", 43, 3, {"request_id": 5, "queue_us": 1500}),
            ("pdt.engine.decode", 47, 35),
            ("pdt.engine.decode.dispatch", 47, 6, {"executables": 1}),
            ("pdt.engine.decode.read", 53, 29),
            ("pdt.sched.consume", 84, 8, {"tokens": 3, "finished": 0}),
            ("pdt.sched.step", 120, 10, {"step": 9, "n_active": 3}),
            ("other", 0, 90),
        ],
        "worker": [("pdt.runner.submit", 1, 60, {"step": 3}),
                   ("pdt.runner.fence", 11, 45, {"step": 1}),
                   ("pdt.runner.dispatch", 3, 7, {"step": 3})],
    },
}


@pytest.fixture(scope="module")
def context():
    profile = handmade_program.profile(SERVED)
    return {"trace": trace_reduce.from_profile(profile),
            "program_spans": program_trace.spans_of_profile(profile),
            "steps_in_trace": 2,
            "op_names": {
                "fusion.1": "jit(pstep)/jvp(GPT2)/h_0/attn/dot_general",
                "fusion.2": "jit(pstep)/transpose(jvp(GPT2))/h_0/attn/mul",
                "copy.3": "jit(pstep)/optimizer/add"}}


def test_spans_carry_their_stats_and_nest_by_time_on_their_thread(context):
    spans = program_trace.host_spans(context)
    assert [s.name for s in spans][:3] == [
        "runner.submit", "sched.step", "runner.dispatch"]
    by_start = {(s.name, round(s.t0 / US)): s for s in spans}
    admit = by_start["sched.admit", 43]
    assert admit.stats == {"request_id": 5, "queue_us": 1500}
    assert admit.parent.stats["step"] == 8
    read = by_start["engine.decode.read", 8]
    assert read.parent.name == "engine.decode"
    assert read.parent.parent.stats["step"] == 7
    # the runner's thread nests on its own, whatever the other thread does
    fence = by_start["runner.fence", 11]
    assert fence.parent.name == "runner.submit"
    assert by_start["sched.step", 2].parent is None
    # a span that begins after the window closed is not of the window
    assert len(program_trace.in_window(context, "sched.step")) == 2


def test_duration_mean_percentile_and_self_time_minus_a_child(context):
    read = program_span_duration.read
    assert read(context, "sched.consume") == pytest.approx(7e-3)   # ms
    # nearest rank: of two values the median is the lower, p95 the upper
    assert read(context, "engine.decode.read", percentile=50) == \
        pytest.approx(24e-3)
    assert read(context, "engine.decode.dispatch", percentile=95) == \
        pytest.approx(6e-3)
    # 60 us of submit, 45 of them blocked on the fence
    assert read(context, "runner.submit", minus_child="runner.fence") == \
        pytest.approx(15e-3)
    # a child of another name takes nothing away
    assert read(context, "runner.submit", minus_child="sched.admit") == \
        pytest.approx(60e-3)


def test_stat_percentile_and_sum_per_counting_span(context):
    read = program_span_stat.read
    assert read(context, "sched.admit", "queue_us", percentile=95,
                scale=1e-3) == pytest.approx(1.5)
    # 2 + 3 tokens consumed by two forwards of the decode program
    assert read(context, "sched.consume", "tokens",
                per="engine.decode") == pytest.approx(2.5)
    assert read(context, "sched.step", "n_active", percentile=95) == 2


def test_idle_outside_the_spans_in_which_the_host_waits(context):
    """The device idles 0-10, 30-50 and 80-100. The host waits for it in
    the reads 8-32 and 53-82: of the first gap 8-10, of the second 30-32
    and of the third 80-82 lie inside a read, so 8 + 18 + 18 us are the
    host's doing, over two steps."""
    assert trace_reduce.idle_gaps(context["trace"]) == [
        pytest.approx(g) for g in
        [(0, 10 * US), (30 * US, 50 * US), (80 * US, 100 * US)]]
    assert program_idle_outside.read(
        context, ["engine.decode.read"], per="sched.step") == \
        pytest.approx((8 + 18 + 18) / 2 * 1e-3)
    # every instant outside no span at all: the whole 50 us of idle
    assert program_idle_outside.read(
        context, ["engine.decode.read", "engine.prefill.read"],
        per="engine.decode") == pytest.approx((8 + 18 + 18) / 2 * 1e-3)


def test_device_time_by_the_section_of_fused_operations(context):
    read = device_time_by_scope.read
    fwd = read(context, r"jvp\(", exclude=r"transpose\(|/optimizer")
    bwd = read(context, r"transpose\(")
    opt = read(context, r"/optimizer|/grad_clip")
    assert (fwd, bwd, opt) == pytest.approx((10e-3, 10e-3, 5e-3))   # ms/step
    assert read(context, r"/attn(/|$)") == pytest.approx(20e-3)
    assert fwd + bwd + opt == pytest.approx(
        1e3 * trace_reduce.busy_seconds(context["trace"]) / 2)


def test_instruction_names_and_op_names_from_a_compiled_text():
    text = """
HloModule jit_pstep, entry_computation_layout={...}

%fused_computation.7 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(pstep)/jvp(GPT2)/h_0/mlp/mul" source_file="a.py" source_line=3}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state.params['wte']"}
  %fusion.993 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(pstep)/jvp(GPT2)/h_0/mlp/mul" source_file="a.py" source_line=3}
  add.5 = f32[8]{0} add(%fusion.993, %a), metadata={op_name="jit(pstep)/optimizer/add"}
  ROOT %copy.1 = f32[8]{0} copy(add.5)
}
"""
    names = program_trace.op_names_of_text(text)
    assert names["fusion.993"] == "jit(pstep)/jvp(GPT2)/h_0/mlp/mul"
    assert names["add.5"] == "jit(pstep)/optimizer/add"
    assert names["mul.3"].endswith("mlp/mul") and "copy.1" not in names
    assert program_trace.instruction_of(
        "%fusion.993 = f32[8]{0:T(128)} fusion(f32[8]{0} %a), kind=kLoop"
    ) == "fusion.993"
    assert program_trace.instruction_of("fusion.993") == "fusion.993"


def test_a_trace_without_the_programs_spans_is_not_parsed_again(
        tmp_path, monkeypatch):
    # the parent of the PR that brought the spans writes none: its trace is
    # told apart by its bytes, and costs no second parse
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"\x0a\x09cb.window\x0a\x06submit")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    parsed = []
    monkeypatch.setattr(program_trace, "spans_of_profile",
                        lambda profile: parsed.append(profile) or [])
    program_trace._spans_of_newest_trace.cache_clear()
    try:
        assert program_trace._spans_of_newest_trace() == []
        assert parsed == []
        assert program_trace._mentions(
            str(run / "host.xplane.pb"), b"cb.")
        (run / "empty.xplane.pb").write_bytes(b"")
        assert not program_trace._mentions(
            str(run / "empty.xplane.pb"), b"pdt.")
    finally:
        program_trace._spans_of_newest_trace.cache_clear()


def test_every_reader_returns_none_where_nothing_matches(context):
    assert program_span_duration.read(context, "sched.nothing") is None
    assert program_span_stat.read(context, "sched.admit", "nothing",
                                  percentile=50) is None
    assert program_span_stat.read(context, "sched.consume", "tokens",
                                  per="engine.nothing") is None
    assert program_idle_outside.read(context, ["engine.nothing"],
                                     per="sched.step") is None
    assert device_time_by_scope.read(context, r"/nothing/") is None
    # a program without spans or registry (this PR's parent), and a run
    # that was not traced
    bare = {"trace": context["trace"], "program_spans": [], "op_names": {},
            "steps_in_trace": 2}
    untraced = {"trace": None}
    for ctx in (bare, untraced):
        assert program_span_duration.read(ctx, "sched.consume") is None
        assert program_span_stat.read(ctx, "sched.admit", "queue_us",
                                      percentile=95) is None
        assert program_idle_outside.read(ctx, ["engine.decode.read"],
                                         per="sched.step") is None
        assert device_time_by_scope.read(ctx, r"jvp\(") is None


def test_every_new_metric_names_a_reader_and_its_cells():
    """Every metric that reads the program's own spans or scopes (thirteen
    at PR 24, more since; counted from ``BENCHMARK.json``, never by hand)
    resolves to its reader, lists cells that exist, and is found again
    through each of them: a cell's share is what ``cells.resolve`` gives."""
    from chipbench import cells

    bench = cells.load_benchmark()

    def reads_program(m):
        return cells.load_json(
            cells.HERE / "metrics" / f"{m['name']}.json"
        )["reader"].startswith(("program_", "device_time_by_scope"))

    new = [m for m in bench["per_layer"] if reads_program(m)]
    assert len(new) >= 13
    known = {w["name"] for w in bench["workloads"]}
    per_cell = {}
    for m in new:
        read, args = cells.load_reader(m["name"])
        assert callable(read) and isinstance(args, dict)
        assert m["workloads"] and set(m["workloads"]) <= known, m["name"]
        for cell in m["workloads"]:
            per_cell[cell] = per_cell.get(cell, 0) + 1
    assert sum(per_cell.values()) == sum(len(m["workloads"]) for m in new)
    for cell, share in per_cell.items():
        resolved = cells.resolve(bench, cell).per_layer
        assert sum(map(reads_program, resolved)) == share, cell
