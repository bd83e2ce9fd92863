"""Driver of ``kind: serve_open_loop`` traffic: the program's
``InferenceEngine`` behind its ``Scheduler``, fed by ``loadgen``'s stream on
its schedule whether or not earlier requests have finished.

One thread: between two ``Scheduler.step`` calls it submits every request
that has come due (``step`` is synchronous, so nothing can be admitted
sooner anyway; ``gen_late`` says how long that wait was). Every latency
starts at the instant a request was DUE. A token exists for the user when
the engine call that made it returns (both end in a device read), so the
first token is timed at the return of ``engine.prefill`` and the last at the
return of the ``engine.decode`` that finished the request.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import time
from typing import Any, Dict, List, Optional

from chipbench import loadgen, measure, trace_reduce
from chipbench.drivers.train import seed_key
from chipbench.measure import Result, Spans, emit
from chipbench.references import gpt2 as reference

#: How many finished requests the reference checks, and how far below the
#: reference's own best logit a served token may lie, as a share of the
#: reference's logit range at that position. The served forward (bf16,
#: cached) and the reference (float32, uncached) round differently, and
#: with random weights the best two of 50257 logits now and then lie closer
#: than the rounding. bf16 carries 8 significant bits: on the chip the worst
#: of 1,580 checked tokens lay 0.0031 (0.8 of a bf16 ulp, 2^-8) below the
#: best, and 98.8% were the argmax itself (records/sweep). The rule is
#: four ulps for any token and nineteen in twenty exact: arithmetic in
#: fewer bits than the configuration states (an 8-bit type keeps 3 or 4)
#: errs by 2^-4 of the range and fails both.
CHECKED_REQUESTS = 8
TOKEN_TOLERANCE = 2.0 ** -6
MIN_EXACT_SHARE = 0.95


@dataclasses.dataclass
class Served:
    """What one pass of the stream left behind."""
    arrivals: List[loadgen.Arrival]
    submit_s: Dict[int, float]          # stream index -> host clock
    first_s: Dict[int, float]
    last_s: Dict[int, float]
    tokens: Dict[int, List[int]]
    t_start: float                      # host clock of the stream's zero
    window: tuple                       # (start, end) on the host clock
    occupancy: List[float]
    backlog: List[tuple]                # (host clock, queued requests)
    reduced: Any = None


def build_engine(cell, seed: int, devices):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.serving import InferenceEngine

    config, traffic = cell.config, cell.traffic
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    model = family.build_model(config)
    with jax.default_device(devices[0]):
        variables = jax.jit(model.init)(
            seed_key(seed), jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(
        model, variables, n_slots=traffic["n_slots"],
        max_len=traffic["max_len"], cache_kind=traffic["cache_kind"],
        seed=seed % 2 ** 31)
    return engine, variables, family


def instrument(engine, spans: Spans) -> Dict[str, Any]:
    """Wrap the engine's two calls in the benchmark's spans and keep when
    each last returned (per slot for prefill)."""
    marks = {"prefill_end": {}, "decode_end": 0.0}
    prefill, decode = engine.prefill, engine.decode

    def timed_prefill(cache, slot, prompt, **kw):
        with spans.span("engine.prefill"):
            out = prefill(cache, slot, prompt, **kw)
        marks["prefill_end"][int(slot)] = time.perf_counter()
        return out

    def timed_decode(cache, last_tokens, active):
        with spans.span("engine.decode"):
            out = decode(cache, last_tokens, active)
        marks["decode_end"] = time.perf_counter()
        return out

    engine.prefill, engine.decode = timed_prefill, timed_decode
    return marks


def warm_programs(engine, sched, arrivals) -> List[int]:
    """Run one short request through every prefill bucket the stream can
    reach, and with it the decode program and the scheduler's small eager
    programs. Returns the buckets."""
    from pytorch_distributed_tpu.serving import Request

    by_bucket = {}
    for a in arrivals:
        by_bucket.setdefault(engine.prefill_bucket(len(a.prompt)), a.prompt)
    for bucket in sorted(by_bucket):
        sched.submit(Request(prompt=by_bucket[bucket], max_new_tokens=2))
    sched.run()
    return sorted(by_bucket)


def serve(engine, marks, spans: Spans, arrivals, seconds: float,
          traffic: Dict[str, Any], trace_dir: Optional[str]) -> Served:
    """Offer ``arrivals`` on their schedule until every measured request has
    finished or ``drain_seconds_max`` past the window's end."""
    from pytorch_distributed_tpu.serving import Request, Scheduler

    sched = Scheduler(engine, emit_events=False)
    warm = traffic["warm_seconds"]
    index_of: Dict[int, int] = {}           # request id -> stream index
    out = Served(arrivals, {}, {}, {}, {}, 0.0, (0.0, 0.0), [], [])
    measured = {i for i, a in enumerate(arrivals) if a.measured}
    open_measured = set(measured)
    give_up = warm + seconds + traffic["drain_seconds_max"]
    nxt = 0
    tracer = contextlib.ExitStack()
    tracing = False
    out.t_start = t_start = time.perf_counter()
    out.window = (t_start + warm, t_start + warm + seconds)
    while True:
        now = time.perf_counter() - t_start
        if trace_dir and not tracing and now >= warm:
            tracer.enter_context(trace_reduce.tracing(trace_dir))
            tracer.enter_context(spans.span("window"))
            tracing = True
            now = time.perf_counter() - t_start
        if tracing and now >= warm + seconds:
            tracer.close()
            tracing = False
            out.reduced = trace_reduce.reduce(trace_dir)
            trace_dir = None
            now = time.perf_counter() - t_start
        if (now >= warm + seconds and not open_measured) or now > give_up:
            break
        while nxt < len(arrivals) and arrivals[nxt].due_s <= now:
            a = arrivals[nxt]
            rid = sched.submit(Request(prompt=a.prompt,
                                       max_new_tokens=a.output_len))
            index_of[rid] = nxt
            out.submit_s[nxt] = time.perf_counter()
            nxt += 1
        if not sched.has_work:
            if nxt >= len(arrivals):
                break
            wait = arrivals[nxt].due_s - (time.perf_counter() - t_start)
            time.sleep(max(0.0, min(wait, 0.005)))
            continue
        in_window = out.window[0] <= time.perf_counter() < out.window[1]
        if in_window:
            out.backlog.append((time.perf_counter(), len(sched.queue)))
        with spans.span("sched.step"):
            finished = sched.step()
        if in_window:
            out.occupancy.append(sched.n_active / engine.n_slots)
        for slot, state in enumerate(sched.slots):
            if state is not None:
                i = index_of[state.request.request_id]
                if i not in out.first_s:
                    out.first_s[i] = marks["prefill_end"][slot]
        for done in finished:
            i = index_of[done.request_id]
            out.first_s.setdefault(i, marks["decode_end"])
            out.last_s[i] = marks["decode_end"]
            out.tokens[i] = done.tokens
            open_measured.discard(i)
    tracer.close()
    return out


def latencies(served: Served) -> Dict[str, List[float]]:
    """Per measured request: time to first token from its due instant
    (a request never answered counts as the whole wait to the run's end),
    time per output token after the first, and how late it was submitted."""
    end = max(list(served.last_s.values()) + [served.window[1]])
    ttft, tpot, late = [], [], []
    for i, a in enumerate(served.arrivals):
        if not a.measured:
            continue
        due = served.t_start + a.due_s
        finished = i in served.last_s
        ttft.append((served.first_s[i] if finished else end) - due)
        if i in served.submit_s:
            late.append(served.submit_s[i] - due)
        if finished and len(served.tokens[i]) > 1:
            tpot.append((served.last_s[i] - served.first_s[i])
                        / (len(served.tokens[i]) - 1))
    return {"ttft_s": ttft, "tpot_s": tpot, "gen_late_s": late}


def mean_backlog(served: Served, lo: float, hi: float) -> float:
    """Mean queued requests over the share [lo, hi) of the window."""
    w0, w1 = served.window
    inside = [q for t, q in served.backlog
              if w0 + lo * (w1 - w0) <= t < w0 + hi * (w1 - w0)]
    return sum(inside) / len(inside) if inside else 0.0


def sweep_record(served: Served, lat: Dict[str, List[float]],
                 rate: float) -> Dict[str, Any]:
    """The ``sweep`` line of a pass: what the knee is read from."""
    measured = [i for i, a in enumerate(served.arrivals) if a.measured]
    pct = measure.percentile
    return {
        "event": "sweep", "rate_per_s": rate, "due_in_window": len(measured),
        "unfinished": sum(i not in served.last_s for i in measured),
        "backlog_mid": mean_backlog(served, 0.4, 0.5),
        "backlog_end": mean_backlog(served, 0.9, 1.0),
        "ttft_p50_ms": 1e3 * pct(lat["ttft_s"], 50),
        "ttft_p95_ms": 1e3 * pct(lat["ttft_s"], 95),
        "tpot_p50_ms": 1e3 * pct(lat["tpot_s"], 50) if lat["tpot_s"] else None,
        "tpot_p95_ms": 1e3 * pct(lat["tpot_s"], 95) if lat["tpot_s"] else None,
        "gen_late_p95_ms": 1e3 * pct(lat["gen_late_s"], 95),
        "occupancy_mean": sum(served.occupancy) / len(served.occupancy)
        if served.occupancy else None,
        "drain_s": max(list(served.last_s.values()) + [served.window[1]])
        - served.window[1],
    }


def token_regrets(variables, config, traffic, served: Served, seed: int,
                  sizes: Dict[str, Any]):
    """Teacher forcing on the plain reference: for a seeded sample of
    finished measured requests, how far the reference's logit of each served
    token lies below the reference's own best at that position, as a share
    of its logit range there (0 = the served token IS the argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    done = sorted(i for i in served.tokens if served.arrivals[i].measured)
    rng = np.random.default_rng(seed)
    sample = rng.choice(done, min(CHECKED_REQUESTS, len(done)), replace=False)
    # one width for every run, so that the one program is always cached
    width = min(config["n_positions"], 128 * -(-(
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]) // 128))
    fwd = jax.jit(lambda p, t: reference.forward(p, t, **sizes))
    regrets = []
    for i in sample:
        prompt = served.arrivals[i].prompt
        tokens = np.asarray(served.tokens[i])
        seq = np.concatenate([prompt, tokens[:-1]])
        buf = np.zeros((1, width), np.int32)
        buf[0, :len(seq)] = seq          # causal: the padded tail is unseen
        logits = np.asarray(
            fwd(variables["params"], jnp.asarray(buf))
            [0, len(prompt) - 1:len(seq)], np.float32)
        top = logits.max(-1)
        got = logits[np.arange(len(tokens)), tokens]
        regrets.append((top - got) / (top - logits.min(-1)))
    return np.concatenate(regrets) if regrets else np.zeros(0)


def decode_program_memory(engine, sched_cache) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` of the decode program. The engine has no public
    way to its compiled programs yet; where the private one is gone this
    reads nothing."""
    import jax.numpy as jnp

    decode = getattr(engine, "_decode", None)
    if decode is None:
        return None
    n = engine.n_slots
    compiled = decode.lower(
        engine.params, sched_cache, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), bool), engine._next_rng()).compile()
    return trace_reduce.memory_of(compiled)


def run(cell, seed: int, seconds: float, trace: bool, devices,
        trace_dir: str) -> Result:
    from pytorch_distributed_tpu.serving import Scheduler

    config, traffic = cell.config, cell.traffic
    compiles = measure.CompileCounter()
    spans = Spans()
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
    engine, variables, family = build_engine(cell, seed, devices)
    arrivals = loadgen.stream(traffic, seed, seconds, config["vocab_size"])
    marks = instrument(engine, spans)
    warm_sched = Scheduler(engine, emit_events=False)
    buckets = warm_programs(engine, warm_sched, arrivals)
    programs = {}
    memory = decode_program_memory(engine, warm_sched.cache)
    if memory:
        programs["decode"] = memory
    del warm_sched
    emit({"event": "setup", "prefill_buckets": buckets, **compiles.snapshot(),
          "memory_stats": devices[0].memory_stats(),
          "decode_program_bytes": memory})
    compiled_before = compiles.programs

    served = serve(engine, marks, spans, arrivals, seconds, traffic,
                   trace_dir if trace else None)
    compiled_in_run = compiles.programs - compiled_before

    lat = latencies(served)
    measured = [i for i, a in enumerate(arrivals) if a.measured]
    unfinished = [i for i in measured if i not in served.last_s]
    wrong_length = [i for i in measured if i in served.tokens
                    and len(served.tokens[i]) != arrivals[i].output_len]
    regrets = token_regrets(variables, config, traffic, served, seed,
                            family.reference_sizes(config))
    faults = []
    if compiled_in_run:
        faults.append(f"{compiled_in_run} programs compiled while serving")
    if wrong_length:
        faults.append(f"{len(wrong_length)} requests got another number of "
                      f"tokens than they asked for")
    if not len(regrets):
        faults.append("no finished request to check")
    elif (regrets > TOKEN_TOLERANCE).any():
        faults.append(f"{int((regrets > TOKEN_TOLERANCE).sum())} served "
                      f"tokens lie more than 2^-6 of the logit range below "
                      f"the reference's best (worst {regrets.max():.4f})")
    elif (regrets == 0).mean() < MIN_EXACT_SHARE:
        faults.append(f"only {(regrets == 0).mean():.3f} of the served "
                      f"tokens are the reference's argmax")
    e2e = {
        "serve_ttft_p95_ms": 1e3 * measure.percentile(lat["ttft_s"], 95),
        "serve_tpot_p50_ms": 1e3 * measure.percentile(lat["tpot_s"], 50)
        if lat["tpot_s"] else float("nan"),
    }
    emit({"event": "check", "checked_tokens": int(len(regrets)),
          "argmax_matches": int((regrets == 0).sum()),
          "worst_regret": float(regrets.max()) if len(regrets) else None,
          "compiled_while_serving": compiled_in_run})
    emit(sweep_record(served, lat, traffic["arrivals"]["rate_per_s"]))
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
            "counters": {"device_kind": devices[0].device_kind},
        },
        why_incorrect="; ".join(faults) or None,
    )
