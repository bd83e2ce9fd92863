"""What a traced run's prefill programs spent in the full layer's Pallas
kernel, read from the trace the run left in ``.chipbench_trace/`` of the
tree it ran in (PR 51: ``gqa_prefill_roofline_pct`` read 31.6 at the parent
and 70.3 with the change on one seed, the kernel untouched). One line a run
of a prefill program in the window: its length, the events whose name holds
``custom-call`` (name cut, seconds), and the five longest events of any
kind.

    python3 chipbench/records/prefill-real-chunks/kernel_events.py <tree>
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))

from chipbench import program_trace, trace_reduce          # noqa: E402


def main(tree):
    from jax.profiler import ProfileData

    path = trace_reduce.newest_xplane(f"{tree}/.chipbench_trace")
    profile = ProfileData.from_file(path)
    reduced = trace_reduce.from_profile(profile)
    device = reduced.devices[0]
    lo, hi = reduced.window
    print(json.dumps({"window_s": round(hi - lo, 3), "ops": len(device.ops),
                      "modules": len(device.modules)}))
    for s in program_trace.spans_of_profile(profile):
        if s.name == "engine.prefill":
            print(json.dumps({"span_at_s": round(s.t0 - lo, 4),
                              "span_ms": round(1e3 * (s.t1 - s.t0), 2),
                              **{k: s.stats.get(k) for k in
                                 ("bucket", "n_real", "n_computed")}}))
    runs = sorted((t0, t1) for name, t0, t1 in device.modules
                  if "prefill_fn" in name and lo <= t0 < hi)
    for t0, t1 in runs:
        ops = [(name, b - a) for name, a, b in device.ops if t0 <= a < t1]
        calls = [(n.split(" = ")[0], n.split(" ", 3)[2][:40], round(1e3 * s, 3))
                 for n, s in ops if "custom-call" in n and s > 2e-4]
        longest = sorted(ops, key=lambda o: -o[1])[:5]
        print(json.dumps({
            "run_at_s": round(t0 - lo, 4),
            "run_ms": round(1e3 * (t1 - t0), 2), "events": len(ops),
            "custom_calls_over_0.2ms": calls,
            "longest": [(n[:70], round(1e3 * s, 3)) for n, s in longest]}))


if __name__ == "__main__":
    main(sys.argv[1])
