"""Chunk arithmetic and the span recorder."""

import pytest

from chipbench import measure


def test_one_slow_chunk_moves_the_mean_not_the_median():
    steady = [2.0] * 8
    odd = [2.0] * 7 + [2.4]
    assert measure.chunk_summary(odd)["median_s"] == \
        measure.chunk_summary(steady)["median_s"] == 2.0
    assert measure.chunk_summary(odd)["mean_s"] == pytest.approx(2.05)
    assert measure.chunk_summary(odd)["max_s"] == 2.4
    assert measure.chunk_summary(odd)["n"] == 8


def test_a_stall_in_one_chunk_moves_the_rate_by_its_share():
    steady = [2.0] * 25
    stalled = [2.0] * 24 + [2.05]               # 50 ms lost in 50 s
    assert measure.window_rate(100.0, steady) == pytest.approx(50.0)
    lost = 1 - measure.window_rate(100.0, stalled) / 50.0
    assert lost == pytest.approx(0.05 / 50.05)
    # every chunk 1% slow: the rate is 1% low, the median would say so too
    assert measure.window_rate(100.0, [2.02] * 25) == pytest.approx(50 / 1.01)


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert measure.percentile(values, 50) == 51   # round(0.5 * 99) = 50
    assert measure.percentile(values, 95) == 95
    assert measure.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        measure.percentile([], 50)


def test_self_time_is_duration_minus_children():
    spans = measure.Spans()
    spans.records = [("engine.prefill", 1.0, 2.0, 1),
                     ("engine.decode", 2.5, 4.0, 1),
                     ("sched.step", 0.0, 5.0, 0),
                     ("sched.step", 6.0, 7.0, 0)]
    assert spans.self_times("sched.step") == pytest.approx([2.5, 1.0])
    assert spans.durations("engine.decode") == pytest.approx([1.5])
    assert spans.self_times("sched.step", since=5.5) == pytest.approx([1.0])
