"""Replay the prompts of a serving cell's fixed trace through
``engine.prefill`` alone (as ``chipbench/tools/prefill_replay.py`` does, one
at a time into slot 0, each once after its bucket's program has run once)
and print ONE LINE A PROMPT: its real length, its bucket, the host's
milliseconds around the call (which ends in a read of the first token) and
that token. Two trees on one seed give the same prompts and weights, so the
lines pair up: the first tokens must be equal, the times say what a bucket
costs by the real tokens in it. Then, in the cell's longest bucket, made-up
prompts of chosen lengths (``--lengths``), three calls each, the median.

    python3 chipbench/records/prefill-real-chunks/replay_by_length.py \
        --workload <cell> --seed <n> [--lengths 32768,28672,...]

Runs in any tree that has the cell (the parent's too: nothing of PR 51 is
read but ``engine.prefill``).
"""

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))

from chipbench import cells, loadgen                      # noqa: E402
from chipbench.drivers import serve_open_loop as base     # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lengths", default="")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    bench = cells.load_benchmark()
    cell = cells.resolve(bench, args.workload)
    engine, _, _ = base.build_engine(cell, args.seed, jax.devices()[:1])
    arrivals = loadgen.stream(cell.traffic, args.seed, bench["run_seconds"],
                              cell.config["vocab_size"])
    cache = engine.init_cache()

    def timed(prompt):
        nonlocal cache
        t0 = time.perf_counter()
        cache, tok = engine.prefill(cache, 0, prompt)
        ms = 1e3 * (time.perf_counter() - t0)
        cache = cache.evict(0)
        return ms, int(tok)

    warm = set()
    total = 0.0
    for i, a in enumerate(arrivals):
        bucket = engine.prefill_bucket(len(a.prompt))
        if bucket not in warm:
            timed(a.prompt)
            warm.add(bucket)
        ms, tok = timed(a.prompt)
        total += ms
        print(json.dumps({"i": i, "n_real": len(a.prompt), "bucket": bucket,
                          "ms": round(ms, 3), "tok": tok}), flush=True)
    print(json.dumps({"event": "trace", "prompts": len(arrivals),
                      "prefill_s_sum": total / 1e3,
                      "tokens": sum(len(a.prompt) for a in arrivals),
                      "executables": engine._prefill._cache_size()}),
          flush=True)
    top = engine.prefill_buckets[-1]
    rng = np.random.default_rng(args.seed)
    for n in [int(x) for x in args.lengths.split(",") if x]:
        prompt = rng.integers(1, cell.config["vocab_size"], size=n,
                              dtype=np.int32)
        if engine.prefill_bucket(n) not in warm:
            timed(prompt)
            warm.add(engine.prefill_bucket(n))
        runs = [timed(prompt) for _ in range(3)]
        print(json.dumps({"event": "length", "n_real": n,
                          "bucket": engine.prefill_bucket(n), "of_top": top,
                          "ms_p50": round(statistics.median(
                              r[0] for r in runs), 3),
                          "ms": [round(r[0], 3) for r in runs],
                          "tok": runs[0][1]}), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"event": "memory",
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
