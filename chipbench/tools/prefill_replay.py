"""Replay the prompts of a serving cell's fixed trace through
``engine.prefill`` alone, one at a time into slot 0, and print by prefill
bucket how long a prefill took and the largest of each count that it left
in the cache's ``step_stats`` (the cache class's ``STEP_STATS``; a prefill
program returns one token, so its counts reach no span: ``write_slot``
leaves them in the resident cache, where this reads them).

    python3 -m chipbench.tools.prefill_replay --workload <cell> --seed <n>

For ``k-exaone-236b-a23b.serve-mixed-len`` (PR 41): ``experts_fill_pct`` is
the fullest buffer of held pairs any chunk of any expert layer made, in
percent of ``ops.dropless_experts.share_rows``, and ``experts_spill`` the
passes beyond a chunk's first (a buffer over 100 percent). One JSON line a
bucket, then one for the whole trace; the times are the host's around a
call that ends in a device read, each prompt once after its bucket's
program has run once.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

from chipbench import cells, loadgen
from chipbench.drivers import serve_open_loop as base


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window's length (BENCHMARK.json's run_seconds)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    bench = cells.load_benchmark()
    cell = cells.resolve(bench, args.workload)
    seconds = args.seconds or bench["run_seconds"]
    engine, _, _ = base.build_engine(cell, args.seed, jax.devices()[:1])
    arrivals = loadgen.stream(cell.traffic, args.seed, seconds,
                              cell.config["vocab_size"])
    cache = engine.init_cache()
    names = tuple(getattr(cache, "STEP_STATS", ()))
    by_bucket, warm = {}, set()
    for a in arrivals:
        bucket = engine.prefill_bucket(len(a.prompt))
        if bucket not in warm:
            cache, _ = engine.prefill(cache, 0, a.prompt)
            warm.add(bucket)
        t0 = time.perf_counter()
        cache, _ = engine.prefill(cache, 0, a.prompt)
        ms = 1e3 * (time.perf_counter() - t0)
        by_bucket.setdefault(bucket, []).append(
            (ms, len(a.prompt), np.asarray(cache.step_stats).tolist()))
        cache = cache.evict(0)
    whole = {"event": "trace", "workload": args.workload, "seed": args.seed,
             "prompts": len(arrivals)}
    for bucket in sorted(by_bucket):
        runs = by_bucket[bucket]
        line = {"event": "bucket", "bucket": bucket, "prompts": len(runs),
                "prefill_ms_p50": statistics.median(r[0] for r in runs),
                "prefill_ms_max": max(r[0] for r in runs)}
        for i, name in enumerate(names):
            line[f"{name}_max"] = max(r[2][i] for r in runs)
            whole[f"{name}_max"] = max(whole.get(f"{name}_max", 0),
                                       line[f"{name}_max"])
        print(json.dumps(line), flush=True)
    whole["prefill_s_sum"] = sum(r[0] for runs in by_bucket.values()
                                 for r in runs) / 1e3
    whole["tokens"] = sum(r[1] for runs in by_bucket.values() for r in runs)
    print(json.dumps(whole), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
