"""Driver of ``kind: serve_open_loop_by_family`` traffic: ``serve_open_loop``
for a model family that brings its own reference and its own limits. The
engine, the warm-up, the serving loop, the latencies and the ``sweep`` line
are ``serve_open_loop``'s, unchanged; ``correct`` is decided by the family
module's ``check_served(variables, config, traffic, served, seed) ->
(record, faults)`` (teacher forcing of what was served through the family's
plain reference, the serving cache freed first)."""

from __future__ import annotations

from chipbench import loadgen, measure
from chipbench.drivers import serve_open_loop as base
from chipbench.measure import Result, Spans, emit


def run(cell, seed: int, seconds: float, trace: bool, devices,
        trace_dir: str) -> Result:
    from pytorch_distributed_tpu.serving import Scheduler

    config, traffic = cell.config, cell.traffic
    compiles = measure.CompileCounter()
    spans = Spans()
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
    engine, variables, family = base.build_engine(cell, seed, devices)
    arrivals = loadgen.stream(traffic, seed, seconds, config["vocab_size"])
    marks = base.instrument(engine, spans)
    warm_sched = Scheduler(engine, emit_events=False)
    buckets = base.warm_programs(engine, warm_sched, arrivals)
    programs = {}
    memory = base.decode_program_memory(engine, warm_sched.cache)
    if memory:
        programs["decode"] = memory
    resident = measure.resident_bytes(devices)   # weights and one cache
    del warm_sched
    emit({"event": "setup", "prefill_buckets": buckets, **compiles.snapshot(),
          "memory_stats": devices[0].memory_stats(),
          "decode_program_bytes": memory})
    compiled_before = compiles.programs

    served = base.serve(engine, marks, spans, arrivals, seconds, traffic,
                        trace_dir if trace else None)
    compiled_in_run = compiles.programs - compiled_before

    lat = base.latencies(served)
    measured = [i for i, a in enumerate(arrivals) if a.measured]
    unfinished = [i for i in measured if i not in served.last_s]
    wrong_length = [i for i in measured if i in served.tokens
                    and len(served.tokens[i]) != arrivals[i].output_len]
    record, faults = family.check_served(variables, config, traffic, served,
                                         seed)
    if compiled_in_run:
        faults.append(f"{compiled_in_run} programs compiled while serving")
    if wrong_length:
        faults.append(f"{len(wrong_length)} requests got another number of "
                      f"tokens than they asked for")
    e2e = {
        "serve_ttft_p95_ms": 1e3 * measure.percentile(lat["ttft_s"], 95),
        "serve_tpot_p50_ms": 1e3 * measure.percentile(lat["tpot_s"], 50)
        if lat["tpot_s"] else float("nan"),
    }
    emit({"event": "check", **record,
          "compiled_while_serving": compiled_in_run})
    emit(base.sweep_record(served, lat, traffic["arrivals"]["rate_per_s"]))
    return Result(
        correct=not faults, attempted=len(measured),
        failed=len(unfinished) + len(wrong_length),
        setup_end=served.window[0], end_to_end=e2e,
        context={
            "spans": spans, "window_t0": served.window[0],
            "trace": served.reduced, "programs": programs,
            "samples": {"ttft_s": lat["ttft_s"],
                        "gen_late_s": lat["gen_late_s"],
                        "slot_occupancy": served.occupancy},
            "counters": {"device_kind": devices[0].device_kind,
                         "config": config},
        },
        why_incorrect="; ".join(faults) or None,
        resident_bytes=resident,
    )
