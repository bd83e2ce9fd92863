"""The open-loop generator, and latencies counted from the due instant."""

import numpy as np
import pytest

from chipbench import loadgen
from chipbench.drivers import serve_open_loop as drv

TRAFFIC = {
    "prompt_len": {"dist": "log_uniform", "min": 32, "max": 512},
    "output_len": {"dist": "log_uniform", "min": 32, "max": 256},
    "arrivals": {"gaps": "exponential_quantiles", "rate_per_s": 8.0},
    "warm_seconds": 5.0, "tail_seconds": 10.0, "base_seed": 23,
}


def facts(stream):
    return ([a.due_s for a in stream], [len(a.prompt) for a in stream],
            [a.output_len for a in stream], [a.measured for a in stream])


def test_same_seed_same_stream():
    a = loadgen.stream(TRAFFIC, 2 ** 31 + 5, 30.0, 50257)
    b = loadgen.stream(TRAFFIC, 2 ** 31 + 5, 30.0, 50257)
    assert facts(a) == facts(b)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_another_order():
    a = loadgen.stream(TRAFFIC, 1, 30.0, 50257)
    b = loadgen.stream(TRAFFIC, 2, 30.0, 50257)
    ma = [x for x in a if x.measured]
    mb = [x for x in b if x.measured]
    assert len(ma) == len(mb) == 240             # rate x seconds
    assert sorted(len(x.prompt) for x in ma) == sorted(len(x.prompt) for x in mb)
    assert sorted(x.output_len for x in ma) == sorted(x.output_len for x in mb)
    assert [len(x.prompt) for x in ma] != [len(x.prompt) for x in mb]
    # ... the SAME cyclic order, begun elsewhere: lengths and gaps together
    pa = [(len(x.prompt), x.output_len) for x in ma]
    pb = [(len(x.prompt), x.output_len) for x in mb]
    shift = next(k for k in range(240) if pb == pa[k:] + pa[:k])
    ga = np.diff([5.0] + [x.due_s for x in ma])
    gb = np.diff([5.0] + [x.due_s for x in mb])
    assert gb[:-1] == pytest.approx(np.roll(ga, -shift)[:-1], abs=1e-6)
    gaps_a = np.sort(np.diff([5.0] + [x.due_s for x in ma]))
    gaps_b = np.sort(np.diff([5.0] + [x.due_s for x in mb]))
    assert gaps_a == pytest.approx(gaps_b, abs=1e-6)


def test_stretches_and_ranges():
    s = loadgen.stream(TRAFFIC, 3, 30.0, 50257)
    due = [a.due_s for a in s]
    assert due == sorted(due)
    for a in s:
        assert a.measured == (5.0 <= a.due_s < 35.0)
        assert 32 <= len(a.prompt) <= 512 and 32 <= a.output_len <= 256
        assert a.prompt.dtype == np.int32 and a.prompt.max() < 50257
    assert due[-1] < 45.0
    lens = loadgen.lengths(TRAFFIC["prompt_len"], 1000)
    assert np.median(lens) == pytest.approx(128, rel=0.02)   # log-uniform
    assert loadgen.gaps(TRAFFIC["arrivals"], 100, 10.0).sum() == \
        pytest.approx(10.0)


def test_latency_counts_from_the_due_instant_when_the_submitter_is_late():
    arrivals = [loadgen.Arrival(1.0, np.zeros(4, np.int32), 3, True),
                loadgen.Arrival(2.0, np.zeros(4, np.int32), 3, True),
                loadgen.Arrival(9.0, np.zeros(4, np.int32), 3, False)]
    served = drv.Served(
        arrivals, submit_s={0: 101.5, 1: 102.0}, first_s={0: 101.75},
        last_s={0: 102.75}, tokens={0: [7, 8, 9]}, t_start=100.0,
        window=(100.5, 105.0), occupancy=[], backlog=[])
    lat = drv.latencies(served)
    # request 0 was due at 101.0, submitted half a second late: the wait counts
    assert lat["ttft_s"][0] == pytest.approx(0.75)
    assert lat["gen_late_s"] == pytest.approx([0.5, 0.0])
    assert lat["tpot_s"] == pytest.approx([0.5])       # (102.75-101.75)/2
    # request 1 never finished: it waits to the end of the run, the worst
    assert lat["ttft_s"][1] == pytest.approx(105.0 - 102.0)
    assert len(lat["ttft_s"]) == 2                     # filler is not measured


def test_backlog_is_read_in_shares_of_the_window():
    served = drv.Served([], {}, {}, {}, {}, 0.0, (10.0, 20.0), [],
                        backlog=[(14.5, 2), (14.9, 4), (19.5, 10), (12.0, 99)])
    assert drv.mean_backlog(served, 0.4, 0.5) == 3.0
    assert drv.mean_backlog(served, 0.9, 1.0) == 10.0
