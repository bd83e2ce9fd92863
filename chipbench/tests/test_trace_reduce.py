"""The reduction from a trace to numbers, on hand-made traces whose answers
are known and on a small trace recorded on the chip."""

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr
from chipbench.tests import handmade

US = 1e-6
DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def two_ops():
    """One device, 100 us window: an op 10-30, a collective 40-70 and an op
    60-90 that overlaps its last 10 us; host in submit 5-15, sync 20-95."""
    return tr.from_profile(handmade.profile({
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 10, 20), ("all-gather.2", 40, 30),
                        ("fusion.3", 60, 30)],
            "XLA Modules": [("jit_pstep(123)", 10, 80)],
        },
        "/host:CPU": {"main": [("cb.window", 0, 100), ("cb.submit", 5, 10),
                               ("cb.sync", 20, 75), ("other", 0, 50)]},
    }))


def test_window_is_the_benchmarks_window_span(two_ops):
    assert two_ops.window == pytest.approx((0.0, 100 * US))
    assert [s[0] for s in two_ops.spans] == ["window", "submit", "sync"]


def test_busy_is_the_union_of_op_intervals(two_ops):
    # 10-30 and 40-90: the overlap 60-70 counts once
    assert tr.busy_seconds(two_ops) == pytest.approx(70 * US)
    assert tr.window_seconds(two_ops) == pytest.approx(100 * US)
    assert tr.idle_gaps(two_ops) == [
        pytest.approx(g) for g in
        [(0, 10 * US), (30 * US, 40 * US), (90 * US, 100 * US)]]


def test_gaps_go_to_the_innermost_host_span(two_ops):
    by = dict(tr.longest_idle_by_span(two_ops))
    # 0-5 nothing, 5-10 submit, 30-40 sync, 90-95 sync, 95-100 nothing
    assert by == pytest.approx(
        {"sync": 15 * US, "no_span": 10 * US, "submit": 5 * US})


def test_innermost_span_wins():
    by = tr.attribute([(0.0, 10.0)], [("outer", 0.0, 10.0),
                                      ("inner", 2.0, 5.0)])
    assert by == pytest.approx({"outer": 7.0, "inner": 3.0})


def test_collective_exposure_on_two_ops(two_ops):
    total, exposed = tr.collective_seconds(two_ops)
    assert total == pytest.approx(30 * US)
    assert exposed == pytest.approx(20 * US)     # 60-70 hides behind fusion.3


def test_module_envelopes_and_top_ops(two_ops):
    assert tr.module_durations(two_ops, "pstep") == [pytest.approx(80 * US)]
    assert tr.module_durations(two_ops, "decode") == []
    assert dict(tr.top_ops(two_ops)) == pytest.approx(
        {"fusion": 50 * US, "all-gather": 30 * US})


def test_busy_averages_over_devices():
    reduced = tr.from_profile(handmade.profile({
        "/device:TPU:0": {"XLA Ops": [("a", 0, 100)]},
        "/device:TPU:1": {"XLA Ops": [("a", 0, 50)]},
        "/host:CPU": {"main": [("cb.window", 0, 100)]},
    }))
    assert [d.ordinal for d in reduced.devices] == [0, 1]
    assert tr.busy_seconds(reduced) == pytest.approx(75 * US)


def test_ops_outside_the_window_do_not_count():
    reduced = tr.from_profile(handmade.profile({
        "/device:TPU:0": {"XLA Ops": [("a", 0, 40), ("b", 90, 40)]},
        "/host:CPU": {"main": [("cb.window", 20, 80)]},
    }))
    assert tr.busy_seconds(reduced) == pytest.approx(30 * US)


def test_recorded_chip_trace():
    """A few steps of the GPT-2 125M train cell, recorded on a TPU v5e and
    cut to the lines the reducer reads (see data/README)."""
    import gzip

    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "gpt2_train_v5e.xplane.pb.gz").read_bytes())
    reduced = tr.from_profile(ProfileData.from_serialized_xspace(raw))
    assert len(reduced.devices) == 1 and reduced.devices[0].ops
    steps = tr.module_durations(reduced, "pstep")
    assert len(steps) >= 2
    window = tr.window_seconds(reduced)
    busy = tr.busy_seconds(reduced)
    assert 0.9 * window < busy <= window        # the runner keeps it fed
    by = dict(tr.longest_idle_by_span(reduced))
    assert sum(by.values()) == pytest.approx(window - busy, rel=1e-6)
    assert set(by) <= {"submit", "sync", "no_span"}
    # the chip names an operation by its HLO line; the label keeps what adds up
    assert tr.top_ops(reduced)[0][0] == "fusion bf16[16,12,1024,64]"
    assert tr.collective_seconds(reduced) == (0.0, 0.0)    # one chip
