"""``tools/sweep.py`` for the sessions cell: ``tools/sweep.py`` builds its
engine and its stream with ``serve_open_loop`` (no pages, no shared
prefixes) and is not this PR's to edit, so the knee of
``sarvam-105b.serve-doc-sessions`` is found by the same loop over the cell's
own driver: its traffic at several fixed REQUEST rates, one pass each, in
ONE process (the engine and its compiled programs are shared, each pass gets
a new scheduler, pool and radix tree), a ``sweep`` line a rate with what the
window's admissions found cached beside it. The knee is the highest rate at
which the backlog at the end of the window is under one request and no
larger than at its middle.

    python3 chipbench/records/sarvam-105b/sweep.py --rates 1.0,1.5,2.0 \
        --seconds 51 --seed 7 [--no-reuse]

``--no-reuse`` wraps the scheduler's ``radix.match`` to find nothing (IN
THIS RECORD: there is no such switch in the program or the benchmark): every
ask is then a cold prefill, which is what shows that the cell's rate stands
on the mechanism."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import cells, measure  # noqa: E402
from chipbench.drivers import serve_open_loop as base  # noqa: E402
from chipbench.drivers import serve_sessions_by_family as drv  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sarvam-105b.serve-doc-sessions")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--no-reuse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache
    from pytorch_distributed_tpu.serving import Scheduler
    from pytorch_distributed_tpu.serving.paging import RadixTree

    cell = cells.resolve(cells.load_benchmark(), args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        measure.fail("the sweep needs the chip")
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    if args.no_reuse:
        RadixTree.match = lambda self, tokens, touch=True: []
    spans = measure.Spans()
    engine, _, _ = drv.build_engine(cell, args.seed, devices)
    marks = base.instrument(engine, spans)
    drv.warm_programs(engine, Scheduler(engine, emit_events=False),
                      cell.traffic)
    print(json.dumps({"event": "memory",
                      **(devices[0].memory_stats() or {})}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic)
        traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
        arrivals = drv.sessions(traffic, args.seed, args.seconds,
                                cell.config["vocab_size"])
        served = drv.serve(engine, marks, spans, arrivals, args.seconds,
                           traffic, None)
        record = base.sweep_record(served, base.latencies(served), rate)
        record["decode_step_ms_p50"] = 1e3 * measure.percentile(
            spans.durations("engine.decode", since=served.window[0]), 50)
        record["prefill_ms_p50"] = 1e3 * measure.percentile(
            spans.durations("engine.prefill", since=served.window[0]), 50)
        record["no_reuse"] = args.no_reuse
        record.update(drv.reuse_record(served, traffic["page_size"]))
        print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
