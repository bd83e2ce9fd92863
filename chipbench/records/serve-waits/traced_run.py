"""One ``--trace 1`` run of a cell, in this process, with what ISSUE 42 asks
of a traced run measured around it (nothing under ``chipbench/`` that the
benchmark had is edited: ``jax.profiler.stop_trace`` and
``trace_reduce.reduce`` are wrapped from here):

    python3 chipbench/records/serve-waits/traced_run.py <out.json> [--inside] \\
        --workload <cell> --seed <n> --seconds 51

The run's own output goes to the standard output as ever (its last line is
the result line). ``<out.json>`` gets: the wall time of the whole run, the
seconds ``stop_trace`` took, the ``.xplane.pb``'s size, the count of
``pdt.*`` events by name, and over the ``pdt.sched.admit`` spans of the
traced window: whether both identities hold exactly on every span, the least
``wait_other_us``, the four means against the mean ``ttft_us``, one row a
request (with ``late_us`` joined from ``pdt.sched.submit``), and the medians
of ``.dispatch``, ``.inputs`` and ``.call``. With ``--inside`` also the
runtime's OWN host events under ``pdt.engine.decode.dispatch.call`` and
``.inputs`` (host tracer level 2), by name: calls and microseconds a step.
A program without the spans (the parent) gives counts of zero and no rows."""

import json
import os
import statistics
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

from chipbench import program_trace, run, trace_reduce  # noqa: E402

out_path, argv = sys.argv[1], sys.argv[2:]
inside = "--inside" in argv
argv = [a for a in argv if a != "--inside"] + ["--trace", "1"]
seen = {"stop_trace_s": 0.0}
stop_trace, reduce = jax.profiler.stop_trace, trace_reduce.reduce


def timed_stop_trace():
    t0 = time.perf_counter()
    stop_trace()
    seen["stop_trace_s"] += time.perf_counter() - t0


def kept_reduce(trace_dir):
    t0 = time.perf_counter()
    seen["reduced"] = reduce(trace_dir)
    seen["reduce_s"] = time.perf_counter() - t0
    return seen["reduced"]


jax.profiler.stop_trace, trace_reduce.reduce = timed_stop_trace, kept_reduce
rc = run.main(argv)
if rc:
    sys.exit(rc)                   # the run said why on the standard error
record = {"rc": rc, "wall_s": time.perf_counter() - T0,
          "stop_trace_s": seen["stop_trace_s"],
          "reduce_s": seen.get("reduce_s")}
path = trace_reduce.newest_xplane(str(run.TRACE_DIR))
record["xplane_bytes"] = os.path.getsize(path)
context = {"trace": seen["reduced"]}
spans = program_trace.host_spans(context)
by_name = {}
for s in spans:
    by_name[s.name] = by_name.get(s.name, 0) + 1
record["pdt_events"] = len(spans)
record["pdt_events_by_name"] = dict(sorted(by_name.items()))


def median_ms(name):
    found = program_trace.in_window(context, name)
    return 1e3 * statistics.median(s.seconds for s in found) if found else None


for name in ("engine.decode", "engine.decode.dispatch",
             "engine.decode.dispatch.inputs", "engine.decode.dispatch.call",
             "engine.decode.read", "engine.prefill.dispatch",
             "engine.prefill.dispatch.inputs", "engine.prefill.dispatch.call"):
    record[f"median_ms.{name}"] = median_ms(name)

admits = program_trace.in_window(context, "sched.admit")
late = {s.stats.get("request_id"): s.stats.get("late_us")
        for s in spans if s.name == "sched.submit"}
prefill = {s.stats.get("request_id"): s for s in spans
           if s.name == "engine.prefill"}
KEYS = ("queue_us", "wait_prefill_us", "prefills_ahead", "wait_decode_us",
        "wait_other_us", "admit_us", "ttft_us")
rows = [a.stats for a in admits if all(k in a.stats for k in KEYS)]
record["admits_in_window"] = len(admits)
record["admits_with_waits"] = len(rows)
if rows:
    record["identities_hold"] = all(
        r["queue_us"] == r["wait_prefill_us"] + r["wait_decode_us"]
        + r["wait_other_us"] and r["ttft_us"] == r["queue_us"] + r["admit_us"]
        for r in rows)
    record["wait_other_us_min"] = min(r["wait_other_us"] for r in rows)
    means = {k: statistics.fmean(r[k] for r in rows) for k in KEYS}
    record["mean_us"] = means
    record["four_means_over_mean_ttft"] = (
        means["wait_prefill_us"] + means["wait_decode_us"]
        + means["wait_other_us"] + means["admit_us"]) / means["ttft_us"]
    record["late_us_within_queue_us"] = all(
        late.get(r["request_id"], 0) <= r["queue_us"] for r in rows)
    record["rows"] = [
        dict({k: r[k] for k in ("request_id", "prompt_len") + KEYS},
             late_us=late.get(r["request_id"]),
             prefill_us=int(prefill[r["request_id"]].seconds * 1e6)
             if r["request_id"] in prefill else None,
             bucket=prefill[r["request_id"]].stats.get("bucket")
             if r["request_id"] in prefill else None)
        for r in rows]

if inside and by_name.get("engine.decode.dispatch.call"):
    from jax.profiler import ProfileData

    lo, hi = seen["reduced"].window
    for leaf in ("call", "inputs"):
        name = f"pdt.engine.decode.dispatch.{leaf}"
        under, steps = {}, 0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                events = sorted(
                    ((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                      e.name) for e in line.events), key=lambda e: e[0])
                frames = [e for e in events if e[2] == name
                          and lo <= e[0] < hi]
                if not frames:
                    continue
                steps += len(frames)
                i = 0
                for t0, t1, n in events:
                    while i < len(frames) and frames[i][1] <= t0:
                        i += 1
                    if i == len(frames):
                        break
                    if n != name and frames[i][0] <= t0 and t1 <= frames[i][1]:
                        c, us = under.get(n, (0, 0.0))
                        under[n] = (c + 1, us + (t1 - t0) * 1e6)
        top = sorted(under.items(), key=lambda kv: -kv[1][1])[:24]
        record[f"runtime_events_under_{leaf}"] = {
            "steps": steps,
            "by_name": [[n[:120], c / steps, us / steps] for n, (c, us) in top]
            if steps else []}

os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
with open(out_path, "w") as f:
    json.dump(record, f, indent=1)
sys.exit(rc)
