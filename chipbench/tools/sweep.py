"""Find a serving cell's knee: offer its traffic at several fixed rates, one
pass each, in ONE process (the engine and its compiled programs are shared,
each pass gets a new scheduler and cache), and print a ``sweep`` line a
rate. The knee is the highest rate at which the backlog at the end of the
window is no larger than at its middle; the cell then runs at four fifths
of it, fixed in its traffic file. Run once, when a cell is defined.

    python3 -m chipbench.tools.sweep --workload gpt2-125m.serve-chat \
        --rates 4,6,8,10,12 --seconds 30 --seed 7
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import cells, loadgen, measure
from chipbench.drivers import serve_open_loop as drv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache
    from pytorch_distributed_tpu.serving import Scheduler

    cell = cells.resolve(cells.load_benchmark(), args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        measure.fail("the sweep needs the chip")
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    spans = measure.Spans()
    engine, _, _ = drv.build_engine(cell, args.seed, devices)
    marks = drv.instrument(engine, spans)
    warmed = False
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic)
        traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
        arrivals = loadgen.stream(traffic, args.seed, args.seconds,
                                  cell.config["vocab_size"])
        if not warmed:
            drv.warm_programs(engine, Scheduler(engine, emit_events=False),
                              arrivals)
            warmed = True
        served = drv.serve(engine, marks, spans, arrivals, args.seconds,
                           traffic, None)
        record = drv.sweep_record(served, drv.latencies(served), rate)
        record["decode_step_ms_p50"] = 1e3 * measure.percentile(
            spans.durations("engine.decode", since=served.window[0]), 50)
        print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
