"""Hand-made traces for the reducer's tests, as the text form of the
profiler's ``XSpace``: each line is a list of ``(name, start_us, dur_us)``."""

from __future__ import annotations

from typing import Dict, List, Tuple

Ev = Tuple[str, float, float]


def xspace_text(planes: Dict[str, Dict[str, List[Ev]]]) -> str:
    out = []
    for plane_name, lines in planes.items():
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        out.append(f'planes {{ name: "{plane_name}"')
        for line_id, (line_name, evs) in enumerate(lines.items()):
            out.append(f'  lines {{ id: {line_id} name: "{line_name}" '
                       f'timestamp_ns: 0')
            for name, start_us, dur_us in evs:
                out.append(
                    f"    events {{ metadata_id: {ids[name]} "
                    f"offset_ps: {int(start_us * 1e6)} "
                    f"duration_ps: {int(dur_us * 1e6)} }}")
            out.append("  }")
        for name, i in ids.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{name}" }} }}')
        out.append("}")
    return "\n".join(out)


def profile(planes):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(xspace_text(planes))
