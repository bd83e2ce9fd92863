"""Observability tests: C++ FlightRecorder (record/dump/watchdog/stall),
fr_trace analyzer, PG integration, events/metrics, NaN check, iteration
logger, debug levels; the program's host spans and named sections."""

import json
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.observability import (
    DebugLevel,
    FlightRecorder,
    IterationLogger,
    debug_level,
    fr_trace,
    get_flight_recorder,
    nan_check,
    record_event,
)


class TestFlightRecorder:
    def test_record_complete_dump(self):
        fr = FlightRecorder(capacity=16)
        i1 = fr.record("all_reduce", "default", 1024)
        i2 = fr.record("broadcast", "default", 64)
        fr.complete(i1, ok=True)
        fr.complete(i2, ok=False)
        entries = fr.dump()
        assert len(entries) == 2
        by_op = {e["op"]: e for e in entries}
        assert by_op["all_reduce"]["status"] == "completed"
        assert by_op["all_reduce"]["bytes"] == 1024
        assert by_op["broadcast"]["status"] == "failed"
        assert by_op["all_reduce"]["t_done"] >= by_op["all_reduce"]["t_sched"]
        fr.close()

    def test_ring_wraps(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.complete(fr.record(f"op{i}", "g", 0))
        entries = fr.dump()
        assert len(entries) == 4
        assert sorted(e["id"] for e in entries) == [6, 7, 8, 9]
        fr.close()

    def test_oldest_inflight_and_watchdog(self, tmp_path):
        fr = FlightRecorder(capacity=8)
        assert fr.oldest_inflight_age() is None
        fr.record("hung_all_gather", "default", 4096)  # never completed
        time.sleep(0.05)
        assert fr.oldest_inflight_age() >= 0.05

        dump = str(tmp_path / "fr_dump.json")
        fr.start_watchdog(timeout_s=0.2, dump_path=dump, poll_interval_s=0.05)
        assert not fr.stalled()
        time.sleep(0.6)
        assert fr.stalled()  # watchdog noticed the hang
        payload = json.load(open(dump))
        assert payload["entries"][0]["op"] == "hung_all_gather"
        fr.stop_watchdog()
        fr.close()

    def test_fr_trace_analyzer(self, tmp_path):
        fr = FlightRecorder(capacity=32)
        for _ in range(3):
            fr.complete(fr.record("all_reduce", "default", 10))
        fr.record("barrier", "default", 0)  # hang suspect
        report = fr_trace(fr.dump())
        assert report["by_op"] == {"all_reduce": 3, "barrier": 1}
        assert report["hang_suspect"]["op"] == "barrier"
        assert report["latency_avg_s"] is not None
        fr.close()

    def test_pg_records_collectives(self):
        from pytorch_distributed_tpu.distributed import (
            FakeBackend,
            HashStore,
            ProcessGroup,
        )

        fr = get_flight_recorder()
        before = len(fr.dump())
        pg = ProcessGroup(FakeBackend(HashStore(), 0, 2), "frtest")
        pg.all_reduce(np.ones(8)).result()
        pg.barrier().result()
        entries = [e for e in fr.dump() if e["group"] == "frtest"]
        assert {e["op"] for e in entries} >= {"all_reduce", "barrier"}
        assert all(e["status"] == "completed" for e in entries)
        assert len(fr.dump()) >= before + 2


class TestLoggingUtils:
    def test_events_and_metrics(self):
        from pytorch_distributed_tpu.observability import recent_events

        ev = record_event("rendezvous_complete", source="agent", nodes=4)
        assert ev.metadata == {"nodes": 4}
        assert json.loads(ev.serialize())["name"] == "rendezvous_complete"
        assert recent_events(1) == [ev]
        # events are the one record beside the spans: no counter registry
        import pytorch_distributed_tpu.observability as obs
        assert not hasattr(obs, "put_metric")
        assert not hasattr(obs, "get_metrics")

    def test_an_event_is_serialised_only_for_a_debug_logger(
            self, monkeypatch, caplog):
        import logging

        from pytorch_distributed_tpu.observability import logging_utils

        calls = []
        serialize = logging_utils.Event.serialize
        monkeypatch.setattr(
            logging_utils.Event, "serialize",
            lambda self: calls.append(self.name) or serialize(self))
        with caplog.at_level(logging.INFO, logger="pytorch_distributed_tpu"):
            record_event("quiet", source="agent")
        assert calls == []
        with caplog.at_level(logging.DEBUG, logger="pytorch_distributed_tpu"):
            record_event("loud", source="agent")
        assert calls == ["loud"]
        assert any("loud" in r.getMessage() for r in caplog.records)

    def test_nan_check(self):
        nan_check({"w": np.ones(3)}, name="grads")  # clean passes
        with pytest.raises(FloatingPointError, match="grads"):
            nan_check({"w": np.array([1.0, np.nan])}, name="grads")
        nan_check({"i": np.array([1, 2])})  # ints ignored

    def test_iteration_logger(self):
        il = IterationLogger(sample_rate=2)
        for _ in range(4):
            il.start_iteration()
            il.end_iteration(loss=1.0)
        s = il.summary()
        assert s["iterations"] == 4
        assert s["avg_step_time_s"] >= 0
        assert len(il.samples) == 2  # sampled every 2nd

    def test_debug_level(self, monkeypatch):
        monkeypatch.delenv("TPU_DISTRIBUTED_DEBUG", raising=False)
        assert debug_level() is DebugLevel.OFF
        monkeypatch.setenv("TPU_DISTRIBUTED_DEBUG", "detail")
        assert debug_level() is DebugLevel.DETAIL
        monkeypatch.setenv("TPU_DISTRIBUTED_DEBUG", "bogus")
        assert debug_level() is DebugLevel.OFF


# -- spans inside the program, on the profiler's clock -----------------------
def _pdt_spans(trace_dir):
    """The program's ``pdt.*`` host spans of the newest trace under
    ``trace_dir``, by start, each with its ``stats`` and its ``parent``
    (the benchmark's reader of them is the one reader there is)."""
    from jax.profiler import ProfileData

    from chipbench import program_trace, trace_reduce

    return program_trace.spans_of_profile(ProfileData.from_file(
        trace_reduce.newest_xplane(str(trace_dir))))


def _python_frames(trace_dir):
    """Events the Python tracer wrote (``$file.py:line function``)."""
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    profile = ProfileData.from_file(trace_reduce.newest_xplane(str(trace_dir)))
    return sum(e.name.startswith("$") for plane in profile.planes
               for line in plane.lines for e in line.events)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _tiny_gpt2():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config(vocab_size=97, n_positions=48, n_embd=48,
                            n_layer=2, n_head=4, dtype=jnp.float32))
    return model, model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Seven requests through two slots under a profiler session: the
    spans the scheduler and the engine wrote, what was finished, and the
    cache positions the slots held as each step began (recounted from the
    slots, outside the session's spans)."""
    from pytorch_distributed_tpu.observability import profile_trace
    from pytorch_distributed_tpu.serving import (
        InferenceEngine,
        Request,
        Scheduler,
    )

    model, variables = _tiny_gpt2()
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8)
    sched = Scheduler(engine, emit_events=False)
    rng = np.random.default_rng(3)
    trace_dir = tmp_path_factory.mktemp("served")
    with profile_trace(str(trace_dir)):
        for _ in range(7):
            sched.submit(Request(
                prompt=rng.integers(0, 97, int(rng.integers(2, 8))),
                max_new_tokens=int(rng.integers(2, 9))))
        finished, held = [], []
        while sched.has_work:
            held.append((sum(st.prompt.shape[0] + len(st.tokens) - 1
                             for st in sched.slots if st is not None),
                         type(sched._kv_rows)))
            finished.extend(sched.step())
    return _pdt_spans(trace_dir), finished, held, _python_frames(trace_dir)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Six steps of a tiny GPT-2 through ``AsyncRunner`` under a profiler
    session: the runner's spans, its counters and the step's text."""
    import jax
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.observability import (
        profile_trace,
        programs,
    )
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.trainer import Trainer, lm_loss

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    model, _ = _tiny_gpt2()
    mesh = ptd.init_device_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = Trainer(model, optax.adamw(1e-3), ptd.parallel.DataParallel(mesh),
                      loss_fn=lm_loss, policy="fp32")
    batch = (np.zeros((2, 16), np.int32), np.ones((2, 16), np.int32))
    state = trainer.init(jax.random.key(1), batch)
    runner = AsyncRunner(trainer)
    runner.start(state, batch)      # the ring's small eager programs compile
    before_start = len(compiles)
    runner.start(state, batch)      # registers the thunk anew
    compiled_by_start = len(compiles) - before_start
    step = programs()["step"]
    compiled_by_thunk = len(compiles) - before_start
    text = step().as_text()
    compiled_by_call = len(compiles) - before_start
    trace_dir = tmp_path_factory.mktemp("trained")
    with profile_trace(str(trace_dir)):
        for _ in range(6):
            runner.submit(batch)
        runner.sync()
        counts = (runner.dispatch_count, runner.executable_count)
        runner.finish()
    return {"spans": _pdt_spans(trace_dir), "counts": counts, "text": text,
            "compiled": (compiled_by_start, compiled_by_thunk,
                         compiled_by_call)}


class TestSpans:
    """``observability.span``: the one way the program says what the host
    was doing, through the profiler and only while it runs."""

    def test_a_scheduler_step_encloses_admission_decode_and_consume(
            self, served):
        spans, _, _, _ = served
        step = _named(spans, "sched.step")[0]
        assert step.stats == {"step": 0, "n_active": 0, "queued": 7,
                              "kv_rows": 0}
        inside = [s for s in spans if s.parent is step]
        assert [s.name for s in inside] == [
            "sched.admit", "sched.admit", "engine.decode", "sched.consume"]
        for engine_call in ("engine.prefill", "engine.decode"):
            call = _named(spans, engine_call)[0]
            assert [s.name for s in spans if s.parent is call] == [
                engine_call + ".dispatch", engine_call + ".read"]
        prefill = _named(spans, "engine.prefill")[0]
        admit = prefill.parent
        assert admit.name == "sched.admit" and admit.parent is step
        assert prefill.stats["request_id"] == admit.stats["request_id"]
        assert prefill.stats["bucket"] == 8
        assert prefill.stats["n_real"] == admit.stats["prompt_len"]
        assert set(admit.stats) == {
            "request_id", "slot", "prompt_len", "cached_len", "queue_us",
            "wait_prefill_us", "prefills_ahead", "wait_decode_us",
            "wait_other_us", "admit_us", "ttft_us"}
        # every step counts itself; decode steps say how many tokens came
        steps = _named(spans, "sched.step")
        assert [s.stats["step"] for s in steps] == list(range(len(steps)))
        for consume in _named(spans, "sched.consume"):
            assert 1 <= consume.stats["tokens"] <= 2
            assert consume.parent.name == "sched.step"

    def test_a_step_says_how_many_cache_rows_its_sequences_hold(self, served):
        """``kv_rows`` is what a decode step has to read at least: the sum
        over active sequences of prompt + tokens - 1, through admissions,
        decode steps, evictions and re-admissions into the freed slots;
        kept as a Python int, so the stat costs no array operation."""
        spans, finished, held, _ = served
        steps = _named(spans, "sched.step")
        assert [s.stats["kv_rows"] for s in steps] == [n for n, _ in held]
        assert all(kind is int for _, kind in held)
        rows = [n for n, _ in held]
        # seven requests through two slots: the count rose, fell at an
        # eviction and rose again at the re-admission, and ends empty
        falls = [i for i in range(1, len(rows)) if rows[i] < rows[i - 1]]
        assert falls and any(rows[j] > rows[j - 1]
                             for j in range(falls[0] + 1, len(rows)))
        assert rows[0] == 0 and len(finished) == 7
        evicted = _named(spans, "sched.evict")
        assert len(evicted) == 7

    def test_every_admitted_request_is_evicted_with_its_tokens(self, served):
        spans, finished, _, _ = served
        admitted = [s.stats["request_id"]
                    for s in _named(spans, "sched.admit")]
        evicted = {s.stats["request_id"]: s.stats
                   for s in _named(spans, "sched.evict")}
        assert sorted(admitted) == sorted(evicted) == list(range(7))
        for fin in finished:
            stats = evicted[fin.request_id]
            assert stats["new_tokens"] == len(fin.tokens)
            assert stats["reason"] == fin.reason == "length"
        done = sum(s.stats["finished"]
                   for s in _named(spans, "sched.consume"))
        assert done == len(_named(spans, "sched.evict")) == 7
        # no executable was added while serving: each dispatch says so
        # (slots and prompt lengths change: their host types trace nothing)
        for dispatch in ("engine.decode.dispatch", "engine.prefill.dispatch"):
            assert {s.stats["executables"]
                    for s in _named(spans, dispatch)} == {1}

    def test_queue_wait_counts_from_arrival(self, served):
        """Two slots, seven requests submitted at once: the third waits
        until a slot frees, and its wait is in its time to first token."""
        spans, finished, _, _ = served
        by_id = {f.request_id: f for f in finished}
        for fin in finished:
            assert 0 <= fin.queue_s < fin.ttft_s <= fin.total_s
        first, third = by_id[0], by_id[2]
        freed = min(s.t0 for s in _named(spans, "sched.evict"))
        waited_steps = [s for s in _named(spans, "sched.step")
                        if s.t1 <= freed]
        assert len(waited_steps) >= 1
        assert third.queue_s >= sum(s.seconds for s in waited_steps)
        assert third.queue_s > first.queue_s
        admits = {s.stats["request_id"]: s.stats["queue_us"]
                  for s in _named(spans, "sched.admit")}
        assert admits[2] == int(third.queue_s * 1e6)

    def test_both_identities_hold_for_every_admission(self, served):
        """Whole microseconds, computed once: the wait is its three causes
        and the time to first token is the wait plus the admission."""
        spans, finished, _, _ = served
        admits = _named(spans, "sched.admit")
        assert len(admits) == 7
        for a in admits:
            st = a.stats
            assert st["queue_us"] == (st["wait_prefill_us"]
                                      + st["wait_decode_us"]
                                      + st["wait_other_us"])
            assert st["ttft_us"] == st["queue_us"] + st["admit_us"]
            assert min(st["wait_prefill_us"], st["wait_decode_us"],
                       st["wait_other_us"], st["admit_us"]) >= 0
        by_id = {f.request_id: f for f in finished}
        for a in admits:
            assert a.stats["ttft_us"] == int(
                by_id[a.stats["request_id"]].ttft_s * 1e6)

    def test_a_wait_is_accounted_by_what_the_scheduler_was_doing(
            self, served):
        """Two slots, seven requests submitted at once: the second waits
        out the first's prefill in the same join loop; the third waits for
        a slot, through both prefills and every decode step until one
        frees."""
        spans, finished, _, _ = served
        admits = {s.stats["request_id"]: s.stats
                  for s in _named(spans, "sched.admit")}
        prefills = {s.stats["request_id"]: s
                    for s in _named(spans, "engine.prefill")}
        assert admits[0]["prefills_ahead"] == 0
        assert admits[0]["wait_prefill_us"] == admits[0]["wait_decode_us"] == 0
        assert admits[1]["prefills_ahead"] == 1
        assert admits[1]["wait_prefill_us"] >= int(prefills[0].seconds * 1e6)
        assert admits[1]["wait_decode_us"] == 0
        assert admits[2]["prefills_ahead"] == 2
        assert admits[2]["wait_decode_us"] > 0
        freed = min(s.t0 for s in _named(spans, "sched.evict"))
        decodes = [s for s in _named(spans, "engine.decode") if s.t1 <= freed]
        assert admits[2]["wait_decode_us"] >= int(
            sum(s.seconds for s in decodes) * 1e6)
        by_id = {f.request_id: f for f in finished}
        assert by_id[2].wait_decode_s > 0 and by_id[0].wait_decode_s == 0

    def test_submission_is_a_span_that_joins_to_the_admission(self, served):
        """Every stage of a request's life carries its ``request_id``:
        submit, admit, prefill, evict. Here the scheduler stamped the
        arrival itself, so nothing was late."""
        spans, _, _, _ = served
        submits = _named(spans, "sched.submit")
        assert [s.stats["request_id"] for s in submits] == list(range(7))
        assert [s.stats["queued"] for s in submits] == list(range(7))
        admits = {s.stats["request_id"]: s.stats
                  for s in _named(spans, "sched.admit")}
        for s in submits:
            assert set(s.stats) == {"request_id", "prompt_len", "late_us",
                                    "queued", "n_active"}
            admit = admits[s.stats["request_id"]]
            assert s.stats["late_us"] == 0 <= admit["queue_us"]
            assert s.stats["prompt_len"] == admit["prompt_len"]
            assert s.parent is None      # submitted between steps
        for stage in ("sched.admit", "engine.prefill", "sched.evict"):
            assert sorted(s.stats["request_id"]
                          for s in _named(spans, stage)) == list(range(7))

    def test_a_dispatch_is_its_inputs_and_its_call(self, served):
        spans, _, _, _ = served
        for engine_call in ("engine.prefill", "engine.decode"):
            dispatches = _named(spans, engine_call + ".dispatch")
            assert dispatches
            for d in dispatches:
                inside = [s for s in spans if s.parent is d]
                assert [s.name for s in inside] == [
                    engine_call + ".dispatch.inputs",
                    engine_call + ".dispatch.call"]
                inputs, call = inside
                assert d.t0 <= inputs.t0 <= inputs.t1 <= call.t0
                assert call.t1 <= d.t1
                assert not [s for s in spans if s.parent in (inputs, call)]
        # they cover the dispatch to within the spans' own overhead (the
        # median: one descheduled step must not decide it)
        import statistics
        gaps = [d.seconds - sum(s.seconds for s in spans if s.parent is d)
                for d in _named(spans, "engine.decode.dispatch")]
        assert 0 <= statistics.median(gaps) < 200e-6
        assert "executables" in _named(
            spans, "engine.decode.dispatch")[0].stats

    def test_the_session_leaves_the_python_tracer_off(self, served):
        """``profile_trace`` is for the spans and the runtime's own events;
        Python frames would slow the host it measures."""
        spans, *_, python_frames = served
        assert spans and python_frames == 0

    def test_a_front_end_can_say_when_a_request_arrived(self):
        from pytorch_distributed_tpu.serving import (
            InferenceEngine,
            Request,
            Scheduler,
        )

        model, variables = _tiny_gpt2()
        sched = Scheduler(InferenceEngine(model, variables, n_slots=1,
                                          max_len=32, prefill_len=8),
                          emit_events=False)
        due = time.perf_counter() - 0.25      # due a quarter second ago
        sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2,
                             arrival_s=due))
        (fin,) = sched.run()
        assert fin.queue_s >= 0.25 and fin.ttft_s > fin.queue_s
        assert sched.ttft.percentile(50) == fin.ttft_s

    def test_an_arrival_in_the_past_is_accounted_from_that_instant(
            self, tmp_path):
        """A front end that names the due instant: the request was due a
        quarter second ago, while another's prefill was in progress. Of that
        prefill only the part AFTER the due instant is its wait, and its
        submission says how late it came."""
        from pytorch_distributed_tpu.observability import profile_trace
        from pytorch_distributed_tpu.serving import (
            InferenceEngine,
            Request,
            Scheduler,
        )

        model, variables = _tiny_gpt2()
        engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                                 prefill_len=8)
        sched = Scheduler(engine, emit_events=False)
        sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        sched.run()                      # compiles, outside the session
        prefill = engine.prefill

        def slow_prefill(*args, **kw):
            time.sleep(0.4)
            return prefill(*args, **kw)

        with profile_trace(str(tmp_path)):
            engine.prefill = slow_prefill
            sched.submit(Request(prompt=[4, 5, 6], max_new_tokens=4))
            sched.step()                 # 0.4 s inside the first's prefill
            engine.prefill = prefill
            due = time.perf_counter() - 0.25
            rid = sched.submit(Request(prompt=[7, 8], max_new_tokens=2,
                                       arrival_s=due))
            finished = sched.run()
        t0, t1, was_prefill = [c for c in sched._engine_calls if c[2]][-2]
        assert was_prefill and t1 - t0 >= 0.4 and t0 < due < t1
        spans = _pdt_spans(tmp_path)
        (submit,) = [s for s in _named(spans, "sched.submit")
                     if s.stats["request_id"] == rid]
        (admit,) = [s for s in _named(spans, "sched.admit")
                    if s.stats["request_id"] == rid]
        assert 250_000 <= submit.stats["late_us"] <= admit.stats["queue_us"]
        st = admit.stats
        assert st["prefills_ahead"] == 1
        assert st["wait_prefill_us"] == int((t1 - due) * 1e6)
        assert st["wait_prefill_us"] < int((t1 - t0) * 1e6)
        assert st["wait_decode_us"] > 0          # the step's decode
        assert st["queue_us"] == (st["wait_prefill_us"] + st["wait_decode_us"]
                                  + st["wait_other_us"])
        (fin,) = [f for f in finished if f.request_id == rid]
        assert fin.wait_prefill_s == pytest.approx(t1 - due)
        assert fin.queue_s >= 0.25

    def test_finished_requests_carry_their_waits_without_a_session(self):
        """What an operator without a profiler reads: the same accounting
        on ``FinishedRequest``, and the history it is made from bounded."""
        from pytorch_distributed_tpu.serving import (
            InferenceEngine,
            Request,
            Scheduler,
        )

        model, variables = _tiny_gpt2()
        sched = Scheduler(InferenceEngine(model, variables, n_slots=1,
                                          max_len=32, prefill_len=8),
                          emit_events=False)
        for _ in range(3):
            sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
        first, second, third = sched.run()
        assert first.wait_prefill_s == first.wait_decode_s == 0.0
        # one slot: the second waits out the first's prefill and decode steps
        assert second.wait_prefill_s > 0 and second.wait_decode_s > 0
        assert third.wait_prefill_s > second.wait_prefill_s
        for fin in (first, second, third):
            assert (fin.wait_prefill_s + fin.wait_decode_s
                    <= fin.queue_s < fin.ttft_s)

    def test_the_history_of_engine_calls_is_bounded(self):
        """A thousand steps leave the deque at its ``maxlen`` or under:
        nothing grows with the steps served."""
        from pytorch_distributed_tpu.serving import Request, Scheduler

        class Engine:
            """Just enough of an engine: one slot, a token a call."""
            n_slots, spec_k, cache_kind, max_len = 1, 0, "slotted", 1 << 30

            class _Cache:
                def evict(self, slot):
                    return self

            def init_cache(self):
                return self._Cache()

            def init_draft_cache(self):
                return None

            def prefill(self, cache, slot, prompt, **kw):
                return cache, 0

            def decode(self, cache, last_tokens, active):
                return cache, np.zeros((1,), np.int32)

        sched = Scheduler(Engine(), emit_events=False)
        sched._engine_calls = type(sched._engine_calls)(maxlen=64)
        sched.submit(Request(prompt=[1], max_new_tokens=1001))
        (fin,) = sched.run()
        assert sched.decode_steps == 1000 and len(fin.tokens) == 1001
        assert len(sched._engine_calls) == 64
        assert Scheduler(Engine())._engine_calls.maxlen == 4096

    def test_one_dispatch_span_a_step_from_one_executable(self, trained):
        spans, (dispatches, executables) = trained["spans"], trained["counts"]
        dispatch = _named(spans, "runner.dispatch")
        assert len(dispatch) == dispatches == 6 and executables == 1
        assert [s.stats for s in dispatch] == [
            {"step": i, "executables": 1} for i in range(6)]
        for s in dispatch:
            assert s.parent.name == "runner.submit"
            assert s.parent.stats == {"step": s.stats["step"]}
        # depth 2: from the third submit on the host waits on step n - 2
        fences = _named(spans, "runner.fence")
        assert [s.stats["step"] for s in fences] == [0, 1, 2, 3]
        assert [s.parent.stats["step"] for s in fences] == [2, 3, 4, 5]
        assert len(_named(spans, "runner.place_batch")) == 6
        assert _named(spans, "runner.sync")[0].stats == {"steps": 6}
        assert _named(spans, "runner.finish")[0].stats == {"steps": 6}

    def test_the_step_text_names_its_sections(self, trained):
        import re

        names = set(re.findall(r'op_name="([^"]+)"', trained["text"]))
        for section in ("/optimizer/", "/grad_clip/", "/metric_ring/",
                        "jvp(loss)", "transpose(jvp(loss))",
                        "jvp(GPT2)/head/", "transpose(jvp(GPT2))/head/",
                        "jvp(GPT2)/embed/", "jvp(GPT2)/h_0/attn/",
                        "transpose(jvp(GPT2))/h_1/mlp/"):
            assert any(section in n for n in names), section

    def test_the_way_to_the_step_is_lazy(self, trained):
        # start() registers a thunk and compiles nothing for it, nor does
        # looking the thunk up; calling it compiles the one step program
        assert trained["compiled"] == (0, 0, 1)

    def test_the_engine_registers_decode_and_every_prefill_bucket(self):
        from pytorch_distributed_tpu.observability import programs
        from pytorch_distributed_tpu.serving import InferenceEngine

        model, variables = _tiny_gpt2()
        InferenceEngine(model, variables, n_slots=2, max_len=32,
                        prefill_len=16)
        found = programs()
        assert {"decode", "prefill/8", "prefill/16"} <= set(found)
        memory = found["decode"]().memory_analysis()
        assert memory.argument_size_in_bytes > 0
        assert "op_name" in found["prefill/16"]().as_text()

    def test_without_a_profiler_session_a_span_is_a_no_op(self):
        from pytorch_distributed_tpu.observability import span

        with span("test.nothing", step=1) as s:
            assert not s.is_enabled()
            s.set_metadata(tokens=3)           # harmless when off
        t0 = time.perf_counter()
        for i in range(100_000):
            with span("test.loop", step=i):
                pass
        # about 0.05 s here (half a microsecond a span); the budget leaves
        # room for a loaded test machine
        assert time.perf_counter() - t0 < 2.0

    def test_a_collective_of_the_process_group_is_a_span(self, tmp_path):
        from pytorch_distributed_tpu.distributed.process_group import (
            FakeBackend,
            ProcessGroup,
        )
        from pytorch_distributed_tpu.observability import profile_trace

        pg = ProcessGroup(FakeBackend(None, 0, 1), "spans")
        with profile_trace(str(tmp_path)):
            pg.all_reduce(np.ones(4, np.float32))
        (found,) = _named(_pdt_spans(tmp_path), "pg.all_reduce")
        assert found.stats["group"] == "spans"


# -- the benchmark's reader of ratios over the program's spans ---------------
def _handmade_context():
    """Twelve admissions in a 100 ms window, one after it: request i waited
    ``100 * i`` us on others' prefills and took 1,000 us to admit; its
    prefill computed a 64-token bucket for ``40 + i`` real tokens in
    ``(i + 1)`` ms. One admission carries no ``ttft_us`` (an older program's
    span) and one lies outside the window."""
    from chipbench import trace_reduce
    from chipbench.program_trace import HostSpan

    spans = []
    for i in range(12):
        t0 = 0.001 + 0.008 * i
        spans.append(HostSpan("sched.admit", t0, t0 + 0.002, {
            "request_id": i, "wait_prefill_us": 100 * i, "admit_us": 1000,
            "ttft_us": 1000 + 100 * i}))
        spans.append(HostSpan("engine.prefill", t0, t0 + 0.001 * (i + 1), {
            "request_id": i, "n_real": 40 + i, "bucket": 64}))
    spans.append(HostSpan("sched.admit", 0.0995, 0.0999, {"request_id": 98}))
    spans.append(HostSpan("sched.admit", 0.2, 0.3, {
        "request_id": 99, "wait_prefill_us": 10 ** 6, "admit_us": 1,
        "ttft_us": 10 ** 6 + 1}))
    return {"program_spans": spans,
            "trace": trace_reduce.Reduced(devices=[], spans=[],
                                          window=(0.0, 0.1))}


@pytest.mark.parametrize("args, expected", [
    # a ratio of sums: real tokens over the tokens the buckets computed
    (dict(span="engine.prefill", num="n_real", den="bucket", scale=100.0),
     100.0 * sum(40 + i for i in range(12)) / (12 * 64)),
    # ``seconds`` is the span's own duration: ms a thousand real tokens
    (dict(span="engine.prefill", num="seconds", den="n_real", scale=1e6),
     1e6 * sum(0.001 * (i + 1) for i in range(12))
     / sum(40 + i for i in range(12))),
    # the tail: at or above the 80th percentile of ttft_us (nearest rank of
    # twelve: the ninth smallest), so requests 9, 10, 11
    (dict(span="sched.admit", num="wait_prefill_us", den="ttft_us",
          scale=100.0, tail_of="ttft_us", tail_percentile=80, min_spans=10),
     100.0 * (900 + 1000 + 1100) / (1900 + 2000 + 2100)),
    (dict(span="sched.admit", num="admit_us", den="ttft_us", scale=100.0,
          tail_of="ttft_us", tail_percentile=80, min_spans=10),
     100.0 * 3000 / (1900 + 2000 + 2100)),
    # nothing to read: an absent span, an absent statistic, too few spans
    (dict(span="sched.nothing", num="admit_us", den="ttft_us"), None),
    (dict(span="sched.admit", num="nothing_us", den="ttft_us"), None),
    (dict(span="sched.admit", num="admit_us", den="ttft_us",
          tail_of="ttft_us", tail_percentile=80, min_spans=13), None),
], ids=["ratio", "seconds", "tail_wait", "tail_admit", "absent_span",
        "absent_stat", "under_min_spans"])
def test_program_span_ratio_on_hand_made_spans(args, expected):
    from chipbench.readers import program_span_ratio

    got = program_span_ratio.read(_handmade_context(), **args)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)
    # a run that was not traced, and a program without the spans
    assert program_span_ratio.read({"trace": None}, **args) is None
    bare = dict(_handmade_context(), program_spans=[])
    assert program_span_ratio.read(bare, **args) is None


def test_every_metric_of_the_waits_names_a_reader_and_its_cells():
    """The ten metrics of ISSUE 42 resolve to their readers with the
    arguments their readers take, in the cells that list them."""
    import inspect

    from chipbench import cells

    bench = cells.load_benchmark()
    serve = ["gpt2-125m.serve-chat", "xing4.0-29b-a4b.serve-docqa",
             "k-exaone-236b-a23b.serve-mixed-len",
             "kimi-linear-48b-a3b.serve-long-answer",
             "mimo-v2.5.serve-code-agent"]
    # cell 8's 8 s of trace hold 9 admissions at 1.12/s, under the tail
    # metrics' ``min_spans`` of 10: it is not on their lists
    tails = serve[:4]
    # cell 9 (PR 54) likewise: 8 s at 1.28/s hold about ten admissions
    serve = serve + ["sarvam-105b.serve-doc-sessions"]
    expected = {
        "ttft_wait_prefill_ms_mean": serve, "ttft_wait_decode_ms_mean": serve,
        "ttft_wait_other_ms_mean": serve, "ttft_admit_ms_mean": serve,
        "ttft_tail_wait_prefill_pct": tails, "ttft_tail_admit_pct": tails,
        "prefill_ms_per_ktok": serve, "prefill_real_tokens_pct": serve,
        "decode_dispatch_inputs_ms_p50": serve[:1],
        "decode_dispatch_call_ms_p50": serve[:1]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("ttft_wait_prefill_ms_mean")   # later PRs append
    assert names[first:first + 10] == list(expected)
    for name, where in expected.items():
        assert listed[name]["workloads"] == where
        read, args = cells.load_reader(name)
        inspect.signature(read).bind({}, **args)
        assert read({"trace": None}, **args) is None
