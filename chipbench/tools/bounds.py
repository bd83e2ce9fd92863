"""The bounds the contract's rule gives, from every set of runs recorded
under ``chipbench/records/``: for each end-to-end metric the widest spread
(distance between quartiles over the median, ``statistics.quantiles(n=4)``)
over all sets of all cells that report it, and five times that, never under
1% and never over 10%.

A ``kind: train`` cell's rate is read from each run's ``chunks`` line, not
from its last line: all the window's steps over all its time (``rate``; in
the logs made before PR 23's review, whose last line held the rate by the
median chunk, ``rate_by_mean``, the same quotient). Sets made at another
length than ``run_seconds`` are listed and count for nothing: a stall of
some tens of milliseconds weighs on a short window more, a serving
percentile's spread follows from how many requests the window holds, and
set-up is not judged by its spread. Sets under ``records/superseded/`` were
made with a traffic generator this benchmark no longer has (a free shuffle
per seed) and count for nothing either; they are listed so that their
spreads stand beside the bound.

    python3 -m chipbench.tools.bounds
"""

from __future__ import annotations

import json
import statistics
import sys

from chipbench import cells
from chipbench.tools.repeat import spread


def window_rates(set_dir, workload):
    """The whole-window rate of every run of ``workload`` kept in
    ``set_dir``, from the runs' ``chunks`` lines."""
    rates = []
    for log in sorted(set_dir.glob(f"{workload}.*.log")):
        for line in log.read_text().splitlines():
            if line.startswith('{"event": "chunks"'):
                chunks = json.loads(line)
                rates.append(chunks.get("rate", chunks.get("rate_by_mean")))
    return rates


def main() -> int:
    bench = cells.load_benchmark()
    widest = {}
    for path in sorted((cells.HERE / "records").glob("**/*.summary.json")):
        summary = json.loads(path.read_text())
        counts = (summary["seconds"] == bench["run_seconds"]
                  and "superseded" not in path.parts)
        cell = cells.resolve(bench, summary["workload"])
        values = dict(summary["values"])
        rate_metric = cell.traffic.get("rate_metric")
        if rate_metric in values:
            values[rate_metric] = window_rates(path.parent,
                                               summary["workload"])
        for name, runs in values.items():
            if len(runs) < 3 or summary["trace"]:
                continue
            sp = spread(runs)
            print(f"{'' if counts else '(not counted) '}"
                  f"{path.parent.name}/{summary['workload']} {name}: "
                  f"n={len(runs)} median={statistics.median(runs):.6g} "
                  f"spread={sp:.5f}")
            if counts:
                widest[name] = max(widest.get(name, 0.0), sp)
    for m in bench["end_to_end"]:
        w = widest.get(m["name"])
        if w is None:
            continue
        rule = min(0.1, max(0.01, 5 * w))
        print(f"{m['name']}: widest spread {w:.5f}, rule gives "
              f"{rule:.4f}, BENCHMARK.json has {m['bound']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
