"""Pair the lines of two replays (``replay_by_length.py``) of one cell on
one seed, the parent's and the change's: whether every first token is the
same, and by bucket the sums of the two sides' milliseconds; then the
top bucket's prompts one by one, by real length.

    python3 pair_up.py <parent.txt> <change.txt>
"""

import json
import sys


def lines(path):
    rows = [json.loads(line) for line in open(path) if line.startswith("{")]
    return ([r for r in rows if "i" in r],
            [r for r in rows if r.get("event") == "length"])


def main(parent, change):
    (a, a_len), (b, b_len) = lines(parent), lines(change)
    assert [(r["i"], r["n_real"]) for r in a] == [
        (r["i"], r["n_real"]) for r in b], "the replays differ in prompts"
    differ = [r["i"] for r, s in zip(a, b) if r["tok"] != s["tok"]]
    differ += [r["n_real"] for r, s in zip(a_len, b_len)
               if r["tok"] != s["tok"]]
    print(json.dumps({"prompts": len(a), "first_tokens_differ": differ}))
    buckets = sorted({r["bucket"] for r in a})
    for bucket in buckets:
        pairs = [(r, s) for r, s in zip(a, b) if r["bucket"] == bucket]
        print(json.dumps({
            "bucket": bucket, "prompts": len(pairs),
            "real": sum(r["n_real"] for r, _ in pairs),
            "parent_ms": round(sum(r["ms"] for r, _ in pairs), 1),
            "change_ms": round(sum(s["ms"] for _, s in pairs), 1)}))
    print(json.dumps({
        "bucket": "all", "prompts": len(a),
        "parent_ms": round(sum(r["ms"] for r in a), 1),
        "change_ms": round(sum(s["ms"] for s in b), 1)}))
    for r, s in sorted(((r, s) for r, s in zip(a, b)
                        if r["bucket"] == buckets[-1]),
                       key=lambda p: p[0]["n_real"]):
        print(json.dumps({"n_real": r["n_real"], "bucket": r["bucket"],
                          "parent_ms": r["ms"], "change_ms": s["ms"]}))
    for r, s in zip(a_len, b_len):
        print(json.dumps({"made_up": r["n_real"], "bucket": r["bucket"],
                          "parent_ms_p50": r["ms_p50"],
                          "change_ms_p50": s["ms_p50"]}))


if __name__ == "__main__":
    main(*sys.argv[1:3])
