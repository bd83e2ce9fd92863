"""One UNTRACED run of a serving cell, in this process, with the host clock
read around every ``Scheduler.step`` and every ``engine.decode`` of the
pass (nothing under ``chipbench/`` that the benchmark had is edited:
``drivers/serve_open_loop.py::serve`` is wrapped from here, AFTER the warm-up
has traced the programs, so no program's call stack changes):

    python3 chipbench/records/serve-waits/timed_steps.py <out.json> \\
        --workload <cell> --seed <n> --seconds 51

The run's own output goes to the standard output as ever (its last line is
the result line). ``<out.json>`` gets, over the steps of the measured window
that decode: how many, the median and mean ``engine.decode`` (the driver's
``timed_decode`` around the engine's), the same by the count of active slots,
the rest of a step that admits nobody (``Scheduler.step`` less its decode:
the scheduler's own Python), the gap from one such step's return to the next
one's start (the driver's loop), and the step-to-step period: the three
parts of a token's gap, for two trees to be compared part by part. Times in
microseconds on the host's clock; two clock reads and an append a wrapper."""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

from chipbench import run  # noqa: E402
from chipbench.drivers import serve_open_loop as driver  # noqa: E402
from pytorch_distributed_tpu.serving import Scheduler  # noqa: E402

out_path, argv = sys.argv[1], sys.argv[2:] + ["--trace", "0"]
seen = {}
serve = driver.serve


def timed_serve(engine, marks, *rest):
    steps, decodes = [], []
    step, decode = Scheduler.step, engine.decode

    def timed_step(self):
        t0 = time.perf_counter()
        out = step(self)
        steps.append((t0, time.perf_counter()))
        return out

    def timed_decode(cache, last_tokens, active):
        t0 = time.perf_counter()
        out = decode(cache, last_tokens, active)
        decodes.append((t0, time.perf_counter(), int(active.sum())))
        return out

    Scheduler.step, engine.decode = timed_step, timed_decode
    try:
        served = serve(engine, marks, *rest)
    finally:
        Scheduler.step, engine.decode = step, decode
    seen.update(steps=steps, decodes=decodes, window=served.window)
    return served


driver.serve = timed_serve
rc = run.main(argv)
if rc:
    sys.exit(rc)


def summary(values):
    if not values:
        return None
    values = [1e6 * v for v in values]
    q = statistics.quantiles(values, n=4) if len(values) > 1 else values * 3
    return {"n": len(values), "median": q[1], "mean": statistics.fmean(values),
            "q1": q[0], "q3": q[2]}


w0, w1 = seen["window"]
steps = [s for s in seen["steps"] if w0 <= s[0] < w1]
decodes = iter(d for d in seen["decodes"] if d[0] >= w0)
rows, d = [], next(decodes, None)
for t0, t1 in steps:                     # a step holds at most one decode
    while d is not None and d[0] < t0:
        d = next(decodes, None)
    if d is not None and d[1] <= t1:
        rows.append((t0, t1, d[1] - d[0], d[2]))
quiet = [r for r in rows if (r[1] - r[0]) - r[2] < 1e-3]   # admits nobody
gaps, periods = [], []
for a, b in zip(rows, rows[1:]):
    if b[0] - a[1] < 1e-3 and (b[1] - b[0]) - b[2] < 1e-3:
        gaps.append(b[0] - a[1])
        periods.append(b[1] - a[1])
by_active = {}
for r in rows:
    by_active.setdefault(r[3], []).append(r[2])
record = {
    "decode_steps": len(rows), "engine_decode_us": summary([r[2] for r in rows]),
    "engine_decode_us_by_active": {
        str(n): summary(v) for n, v in sorted(by_active.items()) if len(v) >= 50},
    "step_rest_us": summary([(r[1] - r[0]) - r[2] for r in quiet]),
    "loop_gap_us": summary(gaps), "period_us": summary(periods),
}
with open(out_path, "w") as f:
    json.dump(record, f, indent=1)
