"""The readings the limits of ``families/sarvam_mla.py`` are set between:
``tools/check_limits_knobs.py`` for the sessions cell (that tool builds its
engine and stream with ``serve_open_loop``, which has no pages and no shared
prefixes, and is not this PR's to edit). Serves the cell's traffic for
``--seconds`` as the driver does (untraced), then, for the family's sample of
finished requests, prints one JSON line a reading: ``served`` (the
program's tokens) and the argmax of each degraded reference, of
``family.DEGRADED`` and of ``family.NOT_TOLD_APART_ON_THE_CHIP`` alike, every
one scored under the plain float32 reference and under the family's own
``faults_of`` rule: ``served`` must pass it, every reading of ``DEGRADED``
must fail it, and what the others do is what the list's name says.

    python3 chipbench/records/sarvam-105b/limits.py --seed <n> --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.getcwd())

from chipbench import cells, measure  # noqa: E402
from chipbench.drivers import serve_open_loop as base  # noqa: E402
from chipbench.drivers import serve_sessions_by_family as drv  # noqa: E402
from chipbench.tools.check_limits_knobs import reading  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sarvam-105b.serve-doc-sessions")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)

    import jax

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache
    from pytorch_distributed_tpu.serving import Scheduler

    cell = cells.resolve(cells.load_benchmark(), args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        measure.fail("the readings need the chip")
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    config, traffic = cell.config, cell.traffic
    spans = measure.Spans()
    engine, variables, family = drv.build_engine(cell, args.seed, devices)
    arrivals = drv.sessions(traffic, args.seed, args.seconds,
                            config["vocab_size"])
    marks = base.instrument(engine, spans)
    drv.warm_programs(engine, Scheduler(engine, emit_events=False), traffic)
    served = drv.serve(engine, marks, spans, arrivals, args.seconds, traffic,
                       None)
    print(json.dumps(base.sweep_record(
        served, base.latencies(served), traffic["arrivals"]["rate_per_s"])),
        flush=True)
    degraded = {**family.DEGRADED, **family.NOT_TOLD_APART_ON_THE_CHIP}
    if args.only:
        degraded = {k: v for k, v in degraded.items()
                    if k in args.only.split(",")}
    readings = {name: [] for name in ("served", *degraded)}
    margins = []
    sample = family.sample_of(served, args.seed)
    print(json.dumps({"event": "sample", "checked": [
        {"prompt_len": len(served.arrivals[i].prompt),
         **served.admitted[i]} for i in sample]}), flush=True)
    for i in sample:
        tokens, logits, margin = family.reference_logits(
            variables, config, traffic, served, i)
        margins.append(margin)
        readings["served"].append(family.regrets_of(logits, tokens))
        for name, knobs in degraded.items():
            _, other, _ = family.reference_logits(
                variables, config, traffic, served, i,
                **knobs(config, len(served.arrivals[i].prompt)))
            readings[name].append(
                family.regrets_of(logits, other.argmax(-1)))
    margins = np.concatenate(margins)
    ok = True
    for name, parts in readings.items():
        regrets = np.concatenate(parts)
        reading(name, regrets, margins, family.THRESHOLDS_READ)
        _, faults = family.faults_of(regrets, margins)
        passes = not faults
        if name == "served" or name in family.DEGRADED:
            ok &= passes == (name == "served")
        print(json.dumps({"reading": name, "passes_the_rule": passes,
                          "told_apart": name in family.DEGRADED,
                          "faults": faults}), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
