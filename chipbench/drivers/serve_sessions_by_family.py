"""Driver of ``kind: serve_sessions_by_family`` traffic: DOCUMENTS that are
each asked several times, served from a PAGED cache whose radix tree shares
a document's pages between its asks. ``serve_open_loop_by_family`` in
everything the two have in common (``serve_open_loop``'s instrumentation,
latencies and ``sweep`` line, the family module's ``check_served``); its own
are the one thing ``loadgen.stream`` cannot say, requests that share a
prefix (``sessions``), an engine built with the traffic file's pages, a
warm-up that runs every program a tail or a cold prompt can reach, and a
serving loop that keeps the scheduler in hand: what each admission found
cached and whether a page had been reclaimed by then decide which requests
the reference checks, and the allocator's own audit (a shared page held
once) is part of ``correct``.

The traffic file: ``n_slots``, ``max_len``, ``page_size``, ``n_pages``,
``tail_len``, ``prefill_buckets``; ``doc_len``, ``question_len``,
``output_len`` (``dist``, ``min``, ``max``), ``asks`` (each document is
asked each of these numbers of times equally often), ``ask_gap_mean_s`` (the
first ask comes at the document's arrival, each later one an exponential gap
after the one before, whether or not that has finished), ``arrivals``
(``gaps``, ``rate_per_s``: REQUESTS a second; documents arrive at that over
the mean of ``asks``), ``warm_seconds``, ``tail_seconds``,
``drain_seconds_max``, ``trace_seconds``, ``base_seed``.

As ``loadgen``: ONE fixed trace from the file (every length, gap and number
of asks once, at evenly spaced quantiles, in an order ``base_seed`` fixes,
a document's asks staying with it); ``--seed`` picks where the cycle of
sessions starts and draws the tokens.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import loadgen, measure, trace_reduce
from chipbench.drivers import serve_open_loop as base
from chipbench.drivers.train import seed_key
from chipbench.measure import Result, Spans, emit


@dataclasses.dataclass
class Ask(loadgen.Arrival):
    """An arrival that is ask ``ask`` of document ``doc`` (numbered over
    the run), whose first ``doc_len`` tokens are the document's."""
    doc: int = 0
    ask: int = 0
    doc_len: int = 0


@dataclasses.dataclass
class Sessions(base.Served):
    """``Served`` and, for each stream index that was admitted, what its
    admission found: ``cached_len`` and the pages the radix tree had given
    back by then."""
    admitted: Dict[int, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    pool_fault: Optional[str] = None


def _documents(traffic, rng, turn: float, which: int, start: float,
               seconds: float, vocab: int, first_doc: int) -> List[Ask]:
    """The asks of the documents that ARRIVE in one stretch of the clock.
    ``base_seed`` fixes the cycle of SESSIONS (a document's length, the gap
    before it, its number of asks and each ask's gap, question and answer
    lengths stay together); ``turn`` in [0, 1) says where in the cycle the
    stretch begins, ``rng`` draws the tokens."""
    asks = np.asarray(traffic["asks"])
    n = int(round(traffic["arrivals"]["rate_per_s"] / asks.mean() * seconds))
    if n == 0:
        return []
    order = np.random.default_rng([traffic["base_seed"], which])
    doc_len = order.permutation(loadgen.lengths(traffic["doc_len"], n))
    n_asks = order.permutation(np.resize(asks, n))
    before = order.permutation(loadgen.gaps(traffic["arrivals"], n, seconds))
    total = int(n_asks.sum())
    question = order.permutation(
        loadgen.lengths(traffic["question_len"], total))
    output = order.permutation(loadgen.lengths(traffic["output_len"], total))
    gap = -np.log1p(-(np.arange(total) + 0.5) / total)
    gap = order.permutation(gap * traffic["ask_gap_mean_s"] / gap.mean())
    first_ask = np.concatenate([[0], np.cumsum(n_asks)])
    cycle = np.roll(np.arange(n), -int(turn * n))
    due = start + np.cumsum(before[cycle])
    # the last gap ends on the stretch's edge: keep every arrival inside it
    due = np.minimum(due, start + seconds - 1e-9)
    out = []
    for at, d in enumerate(cycle):
        document = rng.integers(0, vocab, doc_len[d]).astype(np.int32)
        when = float(due[at])
        for k in range(int(n_asks[d])):
            i = first_ask[d] + k
            if k:
                when += float(gap[i])
            prompt = np.concatenate(
                [document, rng.integers(0, vocab, question[i]).astype(
                    np.int32)])
            out.append(Ask(when, prompt, int(output[i]), False,
                           doc=first_doc + at, ask=k,
                           doc_len=int(doc_len[d])))
    return out


def sessions(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Ask]:
    """All asks of a run, in due order. MEASURED are the asks of the
    documents that ARRIVE in the window, every one of them whenever it comes
    due: the same documents and the same number of requests under every
    seed. (Measured by their own due instant, the requests of a window were
    69 to 89 over six seeds at one rate, as the warm stretch's documents'
    later asks fell in or out of it, and the 95th percentile moved between
    the third and the fifth largest of some twenty cold prompts: 6.8% of
    spread; PERF.md section 6, PR 54.) The asks of the documents of the
    warm and tail stretches are load, and those of them due after the tail
    are left out. ``--seed`` turns all three stretches' cycles of sessions
    by one share of their length, and draws the tokens."""
    rng = np.random.default_rng(seed)
    turn = float(rng.random())
    warm, tail = traffic["warm_seconds"], traffic["tail_seconds"]
    out: List[Ask] = []
    for which, (start, length) in enumerate(
            ((0.0, warm), (warm, seconds), (warm + seconds, tail))):
        asks = _documents(traffic, rng, turn, which, start, length, vocab,
                          1 + max((a.doc for a in out), default=-1))
        for a in asks:
            a.measured = which == 1
        out += asks
    return sorted((a for a in out
                   if a.measured or a.due_s < warm + seconds + tail),
                  key=lambda a: a.due_s)


def build_engine(cell, seed: int, devices):
    """``serve_open_loop.build_engine`` with the traffic file's pages."""
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
        max_len=traffic["max_len"], cache_kind="paged",
        page_size=traffic["page_size"], n_pages=traffic["n_pages"],
        tail_len=traffic["tail_len"],
        prefill_buckets=traffic["prefill_buckets"], seed=seed % 2 ** 31)
    return engine, variables, family


def warm_programs(engine, sched, traffic) -> List[int]:
    """Every program the traffic can reach, through a scheduler: a document
    a cold bucket (whole pages long, so that a later ask's tail is its
    question), then the longest of them asked again with a question a tail
    bucket, and once more behind a prefix cut short, whose tail goes
    through the tail program in pieces. Returns the buckets."""
    from pytorch_distributed_tpu.serving import Request

    page, tail_len = engine.page_size, traffic["tail_len"]
    longest = traffic["doc_len"]["max"] + traffic["question_len"]["max"]
    cold = [b for b in engine.prefill_buckets
            if b > tail_len and b // 2 < longest]
    tails = [b for b in engine.prefill_buckets if b <= tail_len]
    doc = np.ones((1,), np.int32)
    for bucket in cold:
        doc = np.full(((min(bucket, longest) - 1) // page * page,), bucket,
                      np.int32)
        sched.submit(Request(prompt=np.append(doc, 1), max_new_tokens=2))
        sched.run()
    for bucket in tails:
        sched.submit(Request(prompt=np.concatenate(
            [doc, np.full((bucket,), 2, np.int32)]), max_new_tokens=2))
        sched.run()
    # a prefix of the document alone, then other tokens: a tail of pieces
    cut = np.concatenate([doc[:page], np.full((tail_len + 1,), 3, np.int32)])
    sched.submit(Request(prompt=cut, max_new_tokens=2))
    sched.run()
    return cold + tails


def serve(engine, marks, spans: Spans, arrivals, seconds: float,
          traffic: Dict[str, Any], trace_dir: Optional[str]) -> Sessions:
    """``serve_open_loop.serve`` with the scheduler in hand: what each
    admission found cached, the scheduler's counts and the allocator's
    audit at the end."""
    from pytorch_distributed_tpu.serving import Request, Scheduler

    sched = Scheduler(engine, emit_events=False)
    warm = traffic["warm_seconds"]
    index_of: Dict[int, int] = {}           # request id -> stream index
    out = Sessions(arrivals, {}, {}, {}, {}, 0.0, (0.0, 0.0), [], [])
    prefill = engine.prefill

    def noted_prefill(cache, slot, prompt, *, cached_len=0, request_id=None):
        out.admitted[index_of[request_id]] = {
            "cached_len": int(cached_len),
            "reclaimed_before": int(sched.pages_reclaimed)}
        return prefill(cache, slot, prompt, cached_len=cached_len,
                       request_id=request_id)

    engine.prefill = noted_prefill
    measured = {i for i, a in enumerate(arrivals) if a.measured}
    open_measured = set(measured)
    give_up = warm + seconds + traffic["drain_seconds_max"]
    nxt = 0
    tracer = contextlib.ExitStack()
    tracing = False
    out.t_start = t_start = time.perf_counter()
    out.window = (t_start + warm, t_start + warm + seconds)
    try:
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
    finally:
        tracer.close()
        engine.prefill = prefill
    out.stats = {k: v for k, v in sched.stats().items()
                 if isinstance(v, float)}
    try:
        sched.allocator.check()
    except AssertionError as fault:
        out.pool_fault = f"the page allocator's audit failed: {fault}"
    return out


def reuse_record(served: Sessions, page: int) -> Dict[str, Any]:
    """What the window's admissions found cached, for the ``check`` line
    (a document's whole pages of ``page`` positions can be)."""
    rows = [(served.arrivals[i], a) for i, a in served.admitted.items()
            if served.arrivals[i].measured]
    prompt = sum(len(a.prompt) for a, _ in rows)
    partly = [(a.doc_len, found["cached_len"]) for a, found in rows
              if 0 < found["cached_len"] < a.doc_len // page * page]
    return {
        "admitted_in_window": len(rows),
        "cold": sum(found["cached_len"] == 0 for _, found in rows),
        "prefix_cached_tokens_pct": 100.0 * sum(
            found["cached_len"] for _, found in rows) / prompt
        if prompt else None,
        "prefix_partly_reclaimed": len(partly),
        "prefix_partly_reclaimed_doc_cached": partly[:8],
        "cold_asks_after_the_first": sum(
            found["cached_len"] == 0 and a.ask > 0 for a, found in rows),
        **served.stats,
    }


def run(cell, seed: int, seconds: float, trace: bool, devices,
        trace_dir: str) -> Result:
    from pytorch_distributed_tpu.serving import Scheduler

    config, traffic = cell.config, cell.traffic
    compiles = measure.CompileCounter()
    spans = Spans()
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
    engine, variables, family = build_engine(cell, seed, devices)
    arrivals = sessions(traffic, seed, seconds, config["vocab_size"])
    marks = base.instrument(engine, spans)
    warm_sched = Scheduler(engine, emit_events=False)
    buckets = warm_programs(engine, warm_sched, traffic)
    programs = {}
    memory = base.decode_program_memory(engine, warm_sched.cache)
    if memory:
        programs["decode"] = memory
    resident = measure.resident_bytes(devices)   # weights and one pool
    del warm_sched
    emit({"event": "setup", "prefill_buckets": buckets, **compiles.snapshot(),
          "memory_stats": devices[0].memory_stats(),
          "decode_program_bytes": memory})
    compiled_before = compiles.programs

    served = serve(engine, marks, spans, arrivals, seconds, traffic,
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
    if served.pool_fault:
        faults.append(served.pool_fault)
    e2e = {"serve_ttft_p95_ms": 1e3 * measure.percentile(lat["ttft_s"], 95)}
    emit({"event": "check", **record,
          **reuse_record(served, traffic["page_size"]),
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
