"""The readings a by-family serving cell's limits are set between, for a
family that names its own degraded references.

    python3 -m chipbench.tools.check_limits_knobs --workload <cell> \
        --seed <n> --seconds 20

``check_limits`` with the list of degraded references taken from the family
module (``family.DEGRADED``: a name and the knobs of ``reference.forward``
that make it) where ``check_limits`` knows two. Serves the cell's traffic
for ``--seconds`` as the driver does (untraced), then, for the family's
sample of finished requests, prints one JSON line a reading: ``served`` (the
program's tokens) and each degraded reference's argmax, every one scored
under the plain float32 reference as ``check_limits`` scores them, and
under the family's own ``faults_of`` rule: ``served`` must pass it, every
other reading must fail it.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import cells, loadgen, measure
from chipbench.drivers import serve_open_loop as base


def reading(name, regrets, margins, thresholds):
    """``check_limits.reading`` at the family's own thresholds (a margin
    is in the units the family's reference gives it)."""
    line = {"reading": name, "checked_tokens": int(len(regrets))}
    for t in thresholds:
        rest = regrets[margins >= t]
        line[f"near_tie_{t}"] = {
            "near_ties": int((margins < t).sum()),
            "exact_share": float((rest == 0).mean()) if len(rest) else None,
            "worst_regret": float(rest.max()) if len(rest) else None,
            "over_2^-6": int((rest > 2.0 ** -6).sum()),
            "over_2^-5": int((rest > 2.0 ** -5).sum()),
        }
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
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
    engine, variables, family = base.build_engine(cell, args.seed, devices)
    arrivals = loadgen.stream(traffic, args.seed, args.seconds,
                              config["vocab_size"])
    marks = base.instrument(engine, spans)
    base.warm_programs(engine, Scheduler(engine, emit_events=False), arrivals)
    print(json.dumps({"event": "memory", **(devices[0].memory_stats() or {})}),
          flush=True)
    served = base.serve(engine, marks, spans, arrivals, args.seconds, traffic,
                        None)
    print(json.dumps(base.sweep_record(
        served, base.latencies(served), traffic["arrivals"]["rate_per_s"])),
        flush=True)

    readings = {name: [] for name in ("served", *family.DEGRADED)}
    margins = []
    sample = family.sample_of(served, args.seed)
    print(json.dumps({"event": "sample", "prompt_lens": [
        len(served.arrivals[i].prompt) for i in sample]}), flush=True)
    for i in sample:
        tokens, logits, margin = family.reference_logits(
            variables, config, traffic, served, i)
        margins.append(margin)
        readings["served"].append(family.regrets_of(logits, tokens))
        for name, knobs in family.DEGRADED.items():
            _, degraded, _ = family.reference_logits(
                variables, config, traffic, served, i, **knobs(config))
            readings[name].append(
                family.regrets_of(logits, degraded.argmax(-1)))
    margins = np.concatenate(margins)
    ok = True
    for name, parts in readings.items():
        regrets = np.concatenate(parts)
        reading(name, regrets, margins, family.THRESHOLDS_READ)
        _, faults = family.faults_of(regrets, margins)
        passes = not faults
        ok &= passes == (name == "served")
        print(json.dumps({"reading": name, "passes_the_rule": passes,
                          "faults": faults}), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
