"""Hand-made traces with STATISTICS on their events, for the tests of the
readers of the program's own spans: as ``handmade.py``, but each event is
``(name, start_us, dur_us, {stat: value})`` (the fourth part may be left
out). Integers and strings, as ``observability.span`` writes them."""

from __future__ import annotations

from typing import Dict, List


def xspace_text(planes: Dict[str, Dict[str, List[tuple]]]) -> str:
    out = []
    for plane_name, lines in planes.items():
        events = [e for evs in lines.values() for e in evs]
        ids = {n: i + 1 for i, n in enumerate(sorted({e[0] for e in events}))}
        stat_ids = {n: i + 1 for i, n in enumerate(sorted(
            {k for e in events if len(e) > 3 for k in e[3]}))}
        out.append(f'planes {{ name: "{plane_name}"')
        for line_id, (line_name, evs) in enumerate(lines.items()):
            out.append(f'  lines {{ id: {line_id} name: "{line_name}" '
                       f'timestamp_ns: 0')
            for name, start_us, dur_us, *rest in evs:
                stats = "".join(
                    f" stats {{ metadata_id: {stat_ids[k]} "
                    + (f'str_value: "{v}"' if isinstance(v, str)
                       else f"int64_value: {v}") + " }"
                    for k, v in (rest[0] if rest else {}).items())
                out.append(
                    f"    events {{ metadata_id: {ids[name]} "
                    f"offset_ps: {int(start_us * 1e6)} "
                    f"duration_ps: {int(dur_us * 1e6)}{stats} }}")
            out.append("  }")
        for name, i in ids.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{name}" }} }}')
        for name, i in stat_ids.items():
            out.append(f'  stat_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{name}" }} }}')
        out.append("}")
    return "\n".join(out)


def profile(planes):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(xspace_text(planes))
