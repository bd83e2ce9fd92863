"""The two readings a by-family serving cell's limits are set between.

    python3 -m chipbench.tools.check_limits --workload <cell> --seed <n> \
        --seconds 20

Serves the cell's traffic for ``--seconds`` as the driver does (untraced),
then, for the family's seeded sample of finished requests, prints one JSON
line a reading: ``served`` (the program's tokens), ``reference_8bit`` (the
argmax of the plain reference with both operands of every matrix product
rounded to ``float8_e4m3fn``: the nearest precision below the
configuration's bfloat16) and ``reference_fewer_experts`` (the argmax of
the reference routing to one expert fewer a token), each scored under the
plain float32 reference: checked tokens, router near ties at several
thresholds and, for the positions that are none, the share that is the
reference's argmax and the worst regret. The family's limits must pass the
first and fail the other two.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import cells, loadgen, measure
from chipbench.drivers import serve_open_loop as base

THRESHOLDS = (0.0, 0.001, 0.002, 0.004, 0.008)


def reading(name, regrets, margins):
    line = {"reading": name, "checked_tokens": int(len(regrets))}
    for t in THRESHOLDS:
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
    import jax.numpy as jnp

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

    fewer = config["num_experts_per_tok"] - 1
    readings = {"served": [], "reference_8bit": [],
                "reference_fewer_experts": []}
    margins = []
    for i in family.sample_of(served, args.seed):
        tokens, logits, margin = family.reference_logits(
            variables, config, traffic, served, i)
        margins.append(margin)
        readings["served"].append(family.regrets_of(logits, tokens))
        for name, knobs in (
                ("reference_8bit", {"round_to": jnp.float8_e4m3fn}),
                ("reference_fewer_experts", {"experts_per_token": fewer})):
            _, degraded, _ = family.reference_logits(
                variables, config, traffic, served, i, **knobs)
            readings[name].append(
                family.regrets_of(logits, degraded.argmax(-1)))
    for name, parts in readings.items():
        reading(name, np.concatenate(parts), np.concatenate(margins))
    return 0


if __name__ == "__main__":
    sys.exit(main())
