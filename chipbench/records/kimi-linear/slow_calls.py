"""One UNTRACED run of a serving cell, in this process, with the host clock
read around every ``engine.prefill`` and ``engine.decode`` of the pass and
around every collection of Python's garbage collector, to say WHAT a run
that reads a tail far off the others' was waiting for (PR 45 after review:
one run of fourteen read ``serve_ttft_p95_ms`` 638 ms where thirteen read
237-247, with the generator 509 ms late at its 95th percentile). Nothing of
the benchmark is edited: ``drivers/serve_open_loop.py::serve`` is wrapped
from here, AFTER the warm-up has traced the programs.

    python3 chipbench/records/kimi-linear/slow_calls.py <out.json> \\
        --workload <cell> --seed <n> --seconds 51

The run's own output goes to the standard output as ever. ``<out.json>``
gets, over the measured window: the count and the median of each kind of
call, every call that took more than three times its kind's median (a
prefill: of its bucket) with its start on the window's clock and, of its
time, what this thread and the whole process spent ON a CPU, what this
thread spent runnable but WAITING for one (``/proc/thread-self/schedstat``)
and what the machine's hypervisor took from all CPUs (``steal`` of
``/proc/stat``), and how often it gave up its CPU or lost it; every gap between two calls over 20 ms; every collection
over 5 ms."""

import gc
import json
import os
import resource
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

from chipbench import run  # noqa: E402
from chipbench.drivers import serve_open_loop as driver  # noqa: E402

out_path, argv = sys.argv[1], sys.argv[2:] + ["--trace", "0"]
seen = {}
serve = driver.serve


def counters():
    """``(wall, this thread on a CPU, the process on CPUs, this thread
    waiting for a CPU, stolen from the machine's CPUs)`` in seconds, then
    this thread's context switches in thousands, of its own accord (it
    blocked) and not (it was taken off its CPU)."""
    try:
        with open("/proc/thread-self/schedstat") as f:
            waited = int(f.read().split()[1]) * 1e-9
    except OSError:              # a kernel without scheduler statistics
        waited = float("nan")
    with open("/proc/stat") as f:
        stolen = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    switches = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.perf_counter(), time.thread_time(), time.process_time(),
            waited, stolen, switches.ru_nvcsw * 1e-3,
            switches.ru_nivcsw * 1e-3)


def timed_serve(engine, marks, *rest):
    calls, collections, started = [], [], []
    prefill, decode = engine.prefill, engine.decode

    def timed_prefill(cache, slot, prompt, *a, **k):
        t0 = time.perf_counter()
        out = prefill(cache, slot, prompt, *a, **k)
        calls.append((t0, time.perf_counter(), "prefill", len(prompt)))
        return out

    def timed_decode(cache, last_tokens, active):
        c0 = counters()
        out = decode(cache, last_tokens, active)
        c1 = counters()
        calls.append((c0[0], c1[0], "decode", int(active.sum()),
                      [b - a for a, b in zip(c0[1:], c1[1:])]))
        return out

    def collected(phase, info):
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            collections.append((started.pop(), time.perf_counter(),
                                info["generation"]))

    engine.prefill, engine.decode = timed_prefill, timed_decode
    gc.callbacks.append(collected)
    try:
        served = serve(engine, marks, *rest)
    finally:
        engine.prefill, engine.decode = prefill, decode
        gc.callbacks.remove(collected)
    seen.update(calls=calls, collections=collections, window=served.window)
    return served


driver.serve = timed_serve
rc = run.main(argv)
if rc:
    sys.exit(rc)

w0, w1 = seen["window"]
calls = [c for c in seen["calls"] if w0 <= c[0] < w1]


def kind_of(call):
    if call[2] == "decode":
        return "decode"
    return "prefill_%d" % next(b for b in (512, 1024, 2048, 4096, 1 << 30)
                               if b >= call[3])


by_kind = {}
for c in calls:
    by_kind.setdefault(kind_of(c), []).append(c[1] - c[0])
median = {k: statistics.median(v) for k, v in by_kind.items()}
record = {
    "calls": {k: {"n": len(v), "median_ms": 1e3 * median[k],
                  "max_ms": 1e3 * max(v)} for k, v in sorted(by_kind.items())},
    "slow_calls": [
        {"at_s": c[0] - w0, "kind": kind_of(c), "n": c[3],
         "ms": 1e3 * (c[1] - c[0]),
         **dict(zip(("thread_cpu_ms", "process_cpu_ms", "thread_waited_ms",
                     "machine_stolen_ms", "switches_blocked",
                     "switches_preempted"),
                    (1e3 * x for x in (c[4] if len(c) > 4 else ()))))}
        for c in calls if c[1] - c[0] > 3 * median[kind_of(c)]],
    "gaps_over_20_ms": [
        {"at_s": a[1] - w0, "ms": 1e3 * (b[0] - a[1]), "before": kind_of(b)}
        for a, b in zip(calls, calls[1:]) if b[0] - a[1] > 0.020],
    "collections_over_5_ms": [
        {"at_s": t0 - w0, "ms": 1e3 * (t1 - t0), "generation": g}
        for t0, t1, g in seen["collections"]
        if w0 <= t0 < w1 and t1 - t0 > 0.005],
    "collections": len([1 for t0, _, _ in seen["collections"]
                        if w0 <= t0 < w1]),
}
with open(out_path, "w") as f:
    json.dump(record, f, indent=1)
