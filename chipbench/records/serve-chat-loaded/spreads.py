"""What the sets of one machine say about the two rules a benchmark PR is
held to, for every end-to-end metric of ``gpt2-125m.serve-chat``.

    python3 chipbench/records/serve-chat-loaded/spreads.py <machine dir> <set> <set> [...]

The sets are directories that ``tools/repeat.py`` wrote, named in the order
they ran. Printed for each metric, as shares of the median:

- each set's spread by the bounds rule (``repeat.spread``: the distance
  between the quartiles of ``statistics.quantiles(n=4)`` over the median),
  which ``tools/bounds.py`` multiplies by five;
- each set's spread the driver's way for tightness
  (``records/serve-waits/steady.py``: the run farthest from the median left
  out, then the same quartiles), and the mean of the sets against HALF the
  bound (PR 26's row: "the mean of the two spreads may be at most 50% of
  the bound");
- every window of six consecutive runs of the machine, the driver's way,
  against the bound itself, since six runs a side are what a check makes;
- the widest spread of all the runs of a set against an eighth of the bound
  (under it the bound is too loose, unless it is 1%).

Then every run's ``correct``, ``failed``, backlog at the window's end,
programs compiled while serving and the worst regret of its checked tokens.
"""

import json
import statistics
import sys
from pathlib import Path

CELL = "gpt2-125m.serve-chat"
ROOT = Path(__file__).resolve().parents[3]


def iqr(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def drivers_way(values):
    median = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - median))[:-1]
    return iqr(kept)


def events(log):
    found = {}
    for line in log.read_text().splitlines():
        if line.startswith("{"):
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue
            found[record.get("event", "result")] = record
    return found


def main(machine, *sets):
    bounds = {m["name"]: m["bound"] for m in
              json.loads((ROOT / "BENCHMARK.json").read_text())["end_to_end"]}
    by_set = {s: json.loads((Path(machine) / s / f"{CELL}.summary.json")
                            .read_text()) for s in sets}
    for metric in ("serve_tpot_p50_ms", "serve_ttft_p95_ms", "setup_s"):
        bound = bounds[metric]
        print(f"{metric}: bound {bound}")
        every, tight = [], []
        for s, summary in by_set.items():
            runs = summary["values"][metric]
            median = statistics.median(runs)
            every += runs
            tight.append(drivers_way(runs) / median)
            print(f"  {s}: n={len(runs)} median {median:.5f} "
                  f"rule's spread {iqr(runs) / median:.5f} "
                  f"driver's {tight[-1]:.5f} | "
                  + " ".join(f"{v:.4f}" for v in runs))
        mean = statistics.mean(tight)
        print(f"  mean of the sets, driver's way: {mean:.5f} against half "
              f"the bound {bound / 2:.5f}: "
              f"{'within' if mean <= bound / 2 else 'PAST'}")
        windows = [drivers_way(every[i:i + 6]) / statistics.median(
            every[i:i + 6]) for i in range(len(every) - 5)]
        past = sum(w > bound for w in windows)
        print(f"  windows of six consecutive runs ({len(windows)}), driver's "
              f"way: " + " ".join(f"{w:.5f}" for w in windows)
              + f" | widest {max(windows):.5f}, {past} past the bound")
        widest = max(iqr(s["values"][metric]) / statistics.median(
            s["values"][metric]) for s in by_set.values())
        print(f"  widest set by the rule {widest:.5f}: five times is "
              f"{5 * widest:.5f}; the bound is {bound / widest:.1f} times it")
    for s in sets:
        for log in sorted((Path(machine) / s).glob(f"{CELL}.[0-9]*.log"),
                          key=lambda p: int(p.name.split(".")[-2])):
            e = events(log)
            result, sweep, check = e["result"], e["sweep"], e["check"]
            hits = e["setup"].get("cache_hits")
            print(f"  {s}/{log.name}: correct {result['correct']} failed "
                  f"{result['failed']} of {result['attempted']} backlog_mid "
                  f"{sweep['backlog_mid']:.3f} backlog_end "
                  f"{sweep['backlog_end']:.3f} occupancy "
                  f"{sweep['occupancy_mean']:.4f} late_p95_ms "
                  f"{sweep['gen_late_p95_ms']:.3f} compiled_while_serving "
                  f"{check['compiled_while_serving']} checked "
                  f"{check['checked_tokens']} exact {check['argmax_matches']} "
                  f"worst_regret {check['worst_regret']:.5f} cache_hits "
                  f"{hits} peak_bytes "
                  f"{result['device']['memory_peak_bytes']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
