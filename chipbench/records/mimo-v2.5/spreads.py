"""The spreads of the cell's sets against the rule a new cell is held to
(PERF.md section 2), from the logs ``tools/repeat.py`` wrote:

    python3 chipbench/records/mimo-v2.5/spreads.py <dir of sets> <set> <set>

``serve_ttft_p95_ms`` and ``setup_s`` from each run's last line,
``serve_tpot_p50_ms`` (which the cell does not report) from its ``sweep``
line. For each: every reading, each set's spread by the bounds rule (the
distance between the quartiles of ``statistics.quantiles(n=4)`` over the
median) and the driver's way (the run farthest from the median left out,
then the same quartiles), and the mean of the sets' against half the 10%
bound. Then every run's ``correct``, ``failed``, programs compiled while
serving, exact share off the near ties and backlog at the window's end.
"""

import json
import statistics
import sys
from pathlib import Path

CELL = "mimo-v2.5.serve-code-agent"
BOUND = 0.10


def iqr(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def drivers_way(values):
    median = statistics.median(values)
    return iqr(sorted(values, key=lambda v: abs(v - median))[:-1])


def runs_of(directory):
    out = []
    for log in sorted(Path(directory).glob(f"{CELL}.[0-9]*.log")):
        found = {}
        for line in log.read_text().splitlines():
            if line.startswith("{"):
                record = json.loads(line)
                found[record.get("event", "result")] = record
        out.append(found)
    return out


def main(root, *sets):
    by_set = {s: runs_of(Path(root) / s) for s in sets}
    read = {
        "serve_ttft_p95_ms": lambda r: r["result"]["metrics"][
            "serve_ttft_p95_ms"]["value"],
        "serve_tpot_p50_ms": lambda r: r["sweep"]["tpot_p50_ms"],
        "setup_s": lambda r: r["result"]["metrics"]["setup_s"]["value"],
    }
    for metric, of in read.items():
        print(metric)
        tight = []
        for s, runs in by_set.items():
            values = [of(r) for r in runs]
            median = statistics.median(values)
            tight.append(drivers_way(values) / median)
            print(f"  {s}: n={len(values)} median {median:.4f} rule's spread "
                  f"{iqr(values) / median:.5f} driver's {tight[-1]:.5f} | "
                  + " ".join(f"{v:.3f}" for v in values))
        mean = statistics.mean(tight)
        print(f"  mean of the sets, driver's way: {mean:.5f} against half "
              f"the bound {BOUND / 2}: "
              f"{'within' if mean <= BOUND / 2 else 'PAST'}")
    for s, runs in by_set.items():
        for i, r in enumerate(runs):
            check, sweep, result = r["check"], r["sweep"], r["result"]
            rest = check["checked_tokens"] - check["router_near_ties"]
            print(f"  {s}.{i}: correct {result['correct']} failed "
                  f"{result['failed']} of {result['attempted']} compiled "
                  f"{check['compiled_while_serving']} exact "
                  f"{check['argmax_matches'] / rest:.4f} over "
                  f"{check['over_tolerance']} of {rest} near ties "
                  f"{check['router_near_ties'] / check['checked_tokens']:.3f}"
                  f" backlog_end {sweep['backlog_end']:.2f} occupancy "
                  f"{sweep['occupancy_mean']:.2f} peak "
                  f"{result['device']['memory_peak_bytes']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
