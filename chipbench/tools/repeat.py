"""Run one cell several times, each run a process of its own (this parent
never touches JAX, so it holds no chip), keep every run's output, and print
the spread of every metric as the contract's rule takes it.

    python3 -m chipbench.tools.repeat --workload <name> --runs 6 \
        --seconds 35 --seed0 1000 --out chiprun_out/sets/a [--trace 0]

Seeds are ``seed0 + i * 1000003``, so two sets with the same ``seed0`` use
the same seeds. Each run's whole standard output goes to
``<out>/<workload>.<i>.log``; ``<out>/<workload>.summary.json`` gets the
last lines and the spreads.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path


def spread(values):
    """Distance between the first and the third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed0", type=int, default=2147480000)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    lines, walls = [], []
    for i in range(args.runs):
        seed = args.seed0 + i * 1000003
        cmd = [sys.executable, "-m", "chipbench.run", "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--trace", str(args.trace)]
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        walls.append(time.time() - t0)
        (out / f"{args.workload}.{i}.log").write_text(
            proc.stdout + "\n--- stderr (tail) ---\n" + proc.stderr[-4000:])
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            lines.append(json.loads(last))
        except json.JSONDecodeError:
            lines.append({"rc": proc.returncode, "error": proc.stderr[-2000:]})
        print(f"run {i} seed {seed} rc {proc.returncode} wall "
              f"{walls[-1]:.1f}s: {last[:600]}", flush=True)
    good = [l for l in lines if "metrics" in l]
    summary = {"workload": args.workload, "seconds": args.seconds,
               "seed0": args.seed0, "trace": args.trace, "walls_s": walls,
               "lines": lines, "values": {}, "spread": {}, "median": {}}
    for name in (good[0]["metrics"] if good else {}):
        values = [l["metrics"][name]["value"] for l in good
                  if name in l["metrics"]]
        summary["values"][name] = values
        summary["median"][name] = statistics.median(values)
        if len(values) >= 3:
            summary["spread"][name] = spread(values)
    (out / f"{args.workload}.summary.json").write_text(
        json.dumps(summary, indent=1))
    print(json.dumps({k: summary[k] for k in ("median", "spread")}))
    return 0 if len(good) == args.runs else 1


if __name__ == "__main__":
    sys.exit(main())
