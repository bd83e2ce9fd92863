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
    put_metric,
    get_metrics,
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
        ev = record_event("rendezvous_complete", source="agent", nodes=4)
        assert ev.metadata == {"nodes": 4}
        assert json.loads(ev.serialize())["name"] == "rendezvous_complete"
        put_metric("agent.restarts")
        put_metric("agent.restarts", 2)
        assert get_metrics()["agent.restarts"] >= 3

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
    return _pdt_spans(trace_dir), finished, held


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
        spans, _, _ = served
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
            "request_id", "slot", "prompt_len", "cached_len", "queue_us"}
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
        spans, finished, held = served
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
        spans, finished, _ = served
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
        decodes = _named(spans, "engine.decode.dispatch")
        assert {s.stats["executables"] for s in decodes} == {1}

    def test_queue_wait_counts_from_arrival(self, served):
        """Two slots, seven requests submitted at once: the third waits
        until a slot frees, and its wait is in its time to first token."""
        spans, finished, _ = served
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
