"""Print what a recorded trace holds: planes, their lines, how many events
each has, and the first few with their statistics. Look at a trace by hand
with this before writing a reader against it.

    python3 -m chipbench.tools.trace_dump <trace_dir or file.xplane.pb> [n]
"""

from __future__ import annotations

import sys

from chipbench import trace_reduce


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[0]
    if not path.endswith(".pb"):
        path = trace_reduce.newest_xplane(path)
    show = int(argv[1]) if len(argv) > 1 else 4
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:show]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in e.stats}
                print(f"    {e.name[:100]!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
