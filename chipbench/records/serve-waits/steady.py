"""Each side's spread of an end-to-end metric over the untraced runs of one
directory of logs, as the driver's check computes it: of a side's runs the
one farthest from its median left out, then the distance between the first
and the third quartile (``statistics.quantiles(values, n=4)``), beside the
bound (a share of the parent's median). With more than six runs a side, also
every window of six consecutive runs, since six are what a check makes.

    python3 chipbench/records/serve-waits/steady.py <directory> [cell] [metric] [bound]
"""

import glob
import json
import re
import statistics
import sys


def values(directory, cell, side, metric):
    out = []
    logs = glob.glob(f"{directory}/{cell}.{side}.[0-9]*.log")
    for log in sorted(logs, key=lambda p: int(re.search(r"\.(\d+)\.log$", p).group(1))):
        for line in open(log):
            if line.startswith('{"correct"'):
                result = json.loads(line)
                assert result["correct"] and not result["failed"], log
                out.append(result["metrics"][metric]["value"])
    return out


def spread(runs):
    median = statistics.median(runs)
    kept = sorted(runs, key=lambda v: abs(v - median))[:-1]
    q1, _, q3 = statistics.quantiles(kept, n=4)
    return q3 - q1


def main(directory, cell="gpt2-125m.serve-chat", metric="serve_tpot_p50_ms",
         bound="0.01"):
    sides = {side: values(directory, cell, side, metric)
             for side in ("parent", "change", "held")}
    if not sides["held"]:               # steady.sh's third tree, where run
        del sides["held"]
    limit = float(bound) * statistics.median(sides["parent"])
    print(f"{metric}: bound {limit:.5f} ({bound} of the parent's median "
          f"{statistics.median(sides['parent']):.5f})")
    for side, runs in sides.items():
        print(f"{side}: {len(runs)} runs, median {statistics.median(runs):.5f}, "
              f"stdev {statistics.stdev(runs):.5f}, spread of all {spread(runs):.5f}")
        print("   ", " ".join(f"{v:.4f}" for v in runs))
        if len(runs) > 6:
            windows = [spread(runs[i:i + 6]) for i in range(len(runs) - 5)]
            print("    sixes:", " ".join(f"{w:.4f}" for w in windows),
                  f"({sum(w > limit for w in windows)} of {len(windows)} past the bound)")


if __name__ == "__main__":
    main(*sys.argv[1:])
