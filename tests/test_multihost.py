"""Multi-host serving: router + per-host schedulers over the store plane.

Correctness is anchored the same way the single-host stack anchors it:
greedy decode is teacher-forcing-exact, so every token stream the ROUTER
hands back must equal the uncached-forward argmax oracle — including
streams stitched together across a forced host eviction mid-decode, where
the surviving host continues from the committed prefix via prompt+refeed.
On top of parity the tests pin the control-plane invariants: exactly-once
finishes, admission backpressure, deterministic routing, event-trace
reconciliation, and clean rejoin after failure.

Most tests co-step router and workers synchronously in one thread — the
control plane is poll-based, so synchronous stepping is both legal and
fully deterministic. The smoke test and the `slow` churn test run workers
for real (threads / subprocesses with a TCPStore and a SIGKILL).
"""

import functools
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.distributed.store import HashStore
from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.observability import recent_events
from pytorch_distributed_tpu.serving import InferenceEngine, Request, Scheduler
from pytorch_distributed_tpu.serving.multihost import HostWorker, Keys, Router
from pytorch_distributed_tpu.serving.multihost import protocol

pytestmark = [pytest.mark.serving, pytest.mark.multihost]

REPO = Path(__file__).parent.parent


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=97, n_positions=48, n_embd=48, n_layer=2,
                     n_head=4, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables


@functools.lru_cache(maxsize=None)
def _oracle_fwd(model):
    return jax.jit(model.apply)


def greedy_oracle(model, variables, prompt, n_tokens):
    """Teacher forcing on the uncached forward: argmax continuation.

    The input is zero-padded to ``n_positions`` so the jitted forward
    compiles once per model — causal attention makes the padded tail
    invisible to the position being read.
    """
    fwd = _oracle_fwd(model)
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(n_tokens):
        buf = np.zeros((1, model.cfg.n_positions), np.int32)
        buf[0, : len(seq)] = seq
        logits = fwd(variables, jnp.asarray(buf))
        nxt = int(jnp.argmax(logits[0, len(seq) - 1].astype(jnp.float32)))
        out.append(nxt)
        seq.append(nxt)
    return out


def make_worker(store, tiny, host_id, *, n_slots=2, prefill_len=32,
                step_delay_s=0.0, **engine_kw):
    model, variables = tiny
    engine = InferenceEngine(
        model, variables, n_slots=n_slots, max_len=48,
        prefill_len=prefill_len, **engine_kw,
    )
    sched = Scheduler(engine, emit_events=False)
    if step_delay_s:
        real_step = sched.step

        def slow_step():
            time.sleep(step_delay_s)
            return real_step()

        sched.step = slow_step
    return HostWorker(store, sched, host_id=host_id)


def prompts_and_oracles(tiny, n, *, max_new=10, rng_seed=0):
    model, variables = tiny
    rng = np.random.default_rng(rng_seed)
    reqs, oracles = [], {}
    for i in range(n):
        prompt = rng.integers(0, 97, size=int(rng.integers(3, 7)))
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new))
        oracles[i] = greedy_oracle(model, variables, prompt, max_new)
    return reqs, oracles


def events_since(mark, name):
    return [e for e in recent_events(10_000)[mark:] if e.name == name]


def event_mark():
    return len(recent_events(10_000))


# -- store get_nowait promotion (exercised by every test here too) ---------
def test_get_nowait_all_backends(tmp_path):
    from pytorch_distributed_tpu.distributed.store import (
        FileStore, PrefixStore, Store,
    )

    stores = [
        HashStore(),
        FileStore(str(tmp_path / "fs")),
        PrefixStore("ns", HashStore()),
    ]
    for store in stores:
        assert store.get_nowait("absent") is None
        store.set("k", b"v")
        assert store.get_nowait("k") == b"v"
        store.delete_key("k")
        assert store.get_nowait("k") is None
    # PrefixStore actually namespaces the underlying key
    base = HashStore()
    PrefixStore("pg0", base).set("x", b"1")
    assert base.get_nowait("pg0/x") == b"1"
    assert base.get_nowait("x") is None
    # and the base API documents the contract
    with pytest.raises(NotImplementedError):
        Store().get_nowait("k")


# -- tier-1 smoke: 2 live workers, threads, graceful drain ------------------
def test_two_host_smoke_greedy_parity(tiny):
    store = HashStore()
    workers = [make_worker(store, tiny, f"host{i}") for i in range(2)]
    threads = [
        threading.Thread(target=w.serve_forever, daemon=True) for w in workers
    ]
    mark = event_mark()
    for t in threads:
        t.start()
    router = Router(store, heartbeat_ttl_s=30.0)
    reqs, oracles = prompts_and_oracles(tiny, 6, max_new=8)
    ids = [router.submit(r) for r in reqs]
    assert ids == list(range(6))
    finished = router.run(timeout_s=120)
    router.stop_hosts()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()

    # exactly once, all of them
    assert sorted(f.request_id for f in finished) == ids
    for f in finished:
        assert f.tokens == oracles[f.request_id], f.request_id
        assert f.reason == "length"
    # both hosts took a share (6 requests, 2+2 slots of headroom each)
    per_host = router.stats()["per_host_routed"]
    assert set(per_host) == {"host0", "host1"}
    assert all(v > 0 for v in per_host.values())
    # event reconciliation: one route per request, no evictions
    routes = events_since(mark, "serving.route")
    assert sorted(e.metadata["request_id"] for e in routes) == ids
    assert events_since(mark, "serving.host_evict") == []
    joins = events_since(mark, "serving.host_join")
    assert {e.metadata["host"] for e in joins} == {"host0", "host1"}


# -- forced eviction mid-decode: refeed parity ------------------------------
def test_eviction_mid_decode_refeed_matches_oracle(tiny):
    """Kill one host after it has committed a strict prefix of some
    streams; the survivor must finish every request with the exact oracle
    tokens, each request exactly once, and the trace must reconcile."""
    store = HashStore()
    w0 = make_worker(store, tiny, "host0")
    w1 = make_worker(store, tiny, "host1")
    w0.register()
    w1.register()
    router = Router(store, heartbeat_ttl_s=0.4)
    reqs, oracles = prompts_and_oracles(tiny, 4, max_new=12, rng_seed=1)
    ids = [router.submit(r) for r in reqs]
    mark = event_mark()

    finished = []
    finished.extend(router.step())  # discovers hosts, routes 2+2
    victims = [
        rid for rid, inf in router._inflight.items() if inf.chan == w0.chan
    ]
    assert len(victims) == 2  # least-loaded alternation split the load

    # let host0 commit a couple of tokens, then crash it mid-decode
    for _ in range(3):
        w0.step()
        w1.step()
        finished.extend(router.step())
    committed_before = {
        rid: list(router._inflight[rid].committed)
        for rid in victims if rid in router._inflight
    }
    assert any(len(v) > 0 for v in committed_before.values())
    assert any(
        len(v) < len(oracles[rid]) for rid, v in committed_before.items()
    )
    w0.kill()

    deadline = time.monotonic() + 60
    while (router._pending or router._inflight) and time.monotonic() < deadline:
        w1.step()
        finished.extend(router.step())
        time.sleep(0.01)

    assert sorted(f.request_id for f in finished) == ids  # exactly once
    for f in finished:
        assert f.tokens == oracles[f.request_id], (
            f"request {f.request_id}: refeed stream diverged from oracle"
        )
    evicts = events_since(mark, "serving.host_evict")
    assert len(evicts) == 1 and evicts[0].metadata["host"] == "host0"
    rebalances = events_since(mark, "serving.rebalance")
    assert {e.metadata["request_id"] for e in rebalances} == set(committed_before)
    for e in rebalances:
        assert e.metadata["committed"] == len(committed_before[e.metadata["request_id"]])
    # routes reconcile: one per submit + one per rebalance, and the
    # re-admitted ones are marked as refeeds onto the survivor
    routes = events_since(mark, "serving.route")
    assert len(routes) == len(ids) + len(rebalances)
    refeeds = [e for e in routes if e.metadata["refeed"]]
    assert {e.metadata["request_id"] for e in refeeds} == set(committed_before)
    assert {e.metadata["host"] for e in refeeds} == {"host1"}
    assert router.stats()["rebalances"] == len(rebalances)


def test_rejoin_after_eviction_gets_fresh_channel(tiny):
    """A recovered host rejoins by registering again: new channel, no
    replay of the dead channel's inbox, and it takes new traffic."""
    store = HashStore()
    w0 = make_worker(store, tiny, "host0")
    w0.register()
    router = Router(store, heartbeat_ttl_s=0.3)
    reqs, oracles = prompts_and_oracles(tiny, 2, max_new=6, rng_seed=2)
    ids = [router.submit(r) for r in reqs]
    finished = router.step()  # route to host0
    w0.kill()  # crash before any token is committed
    time.sleep(0.35)
    finished.extend(router.step())  # eviction; requests back to pending
    assert router.stats()["evictions"] == 1
    assert all(not hv.alive for hv in router.hosts.values())

    # "recovered host": same label, fresh registration
    w0b = make_worker(store, tiny, "host0")
    w0b.register()
    assert w0b.chan != w0.chan
    deadline = time.monotonic() + 60
    while (router._pending or router._inflight) and time.monotonic() < deadline:
        w0b.step()
        finished.extend(router.step())
    assert sorted(f.request_id for f in finished) == ids
    for f in finished:
        assert f.tokens == oracles[f.request_id]
    # the dead channel's inbox was never replayed onto the new worker
    assert w0b._in_cursor == len(ids)


# -- admission control ------------------------------------------------------
def test_backpressure_caps_outstanding_per_host(tiny):
    store = HashStore()
    w = make_worker(store, tiny, "host0", n_slots=1)
    w.register()
    router = Router(store, heartbeat_ttl_s=30.0, queue_depth=1)
    reqs, oracles = prompts_and_oracles(tiny, 5, max_new=5, rng_seed=3)
    ids = [router.submit(r) for r in reqs]
    finished = []
    max_out = 0
    deadline = time.monotonic() + 120
    while (router._pending or router._inflight) and time.monotonic() < deadline:
        finished.extend(router.step())
        hv = next(iter(router.hosts.values()))
        max_out = max(max_out, len(hv.outstanding))
        w.step()
    assert sorted(f.request_id for f in finished) == ids
    # capacity = n_slots + queue_depth = 2; backpressure held the rest back
    assert max_out <= 2
    for f in finished:
        assert f.tokens == oracles[f.request_id]


def test_router_rejects_unroutable_prompt(tiny):
    store = HashStore()
    w = make_worker(store, tiny, "host0", prefill_len=8)
    w.register()
    router = Router(store)
    router.submit(Request(prompt=np.arange(9), max_new_tokens=4))
    with pytest.raises(RuntimeError, match="prefill window"):
        router.step()


def test_worker_rejects_oversized_inbox_entry(tiny):
    """Belt-and-braces: a misconfigured router's oversized request comes
    back as a 'rejected' finish instead of crashing the serving loop."""
    store = HashStore()
    w = make_worker(store, tiny, "host0", prefill_len=8)
    w.register()
    keys = Keys()
    n = store.add(keys.in_seq(w.chan), 1) - 1
    store.set(keys.inbox(w.chan, n), protocol.dumps(protocol.wire_request(
        0, 0, list(range(20)), 4, None)))
    w.step()
    out = protocol.loads(store.get_nowait(keys.outbox(w.chan, 0)))
    assert out["type"] == "finished" and out["reason"] == "rejected"
    assert w.scheduler.n_active == 0


def test_queue_wait_of_a_routed_request_counts_from_the_router(tiny):
    """The router stamps the wall clock at which it took a request in; the
    worker turns it into an age on its own clock, so the time a request
    spent in the router and on the wire is part of its queue wait, and the
    router hands the host's ``queue_s`` on."""
    store = HashStore()
    w = make_worker(store, tiny, "host0")
    w.register()
    router = Router(store, heartbeat_ttl_s=30.0)
    router.submit(Request(prompt=[3, 1, 4], max_new_tokens=2,
                          arrival_s=time.perf_counter() - 0.2))
    router.step()                  # routes: the request is on the wire
    time.sleep(0.1)
    finished = []
    deadline = time.monotonic() + 60
    while not finished and time.monotonic() < deadline:
        w.step()
        finished.extend(router.step())
    (fin,) = finished
    assert fin.queue_s >= 0.3      # 0.2 s before the router, 0.1 s after
    assert fin.total_s >= 0.1


def test_duplicate_request_id_rejected(tiny):
    router = Router(HashStore())
    router.submit(Request(prompt=[1, 2], max_new_tokens=2, request_id=5))
    with pytest.raises(ValueError, match="duplicate"):
        router.submit(Request(prompt=[3], max_new_tokens=2, request_id=5))


# -- spec decode aggregation ------------------------------------------------
def test_spec_decode_accept_rate_aggregates_across_hosts(tiny):
    """Speculative hosts stream the same greedy tokens (greedy acceptance
    is exact-argmax) and the router aggregates their accept-rates."""
    store = HashStore()
    workers = [
        make_worker(store, tiny, f"host{i}", spec_k=2, draft_layers=1)
        for i in range(2)
    ]
    for w in workers:
        w.register()
    router = Router(store, heartbeat_ttl_s=30.0)
    reqs, oracles = prompts_and_oracles(tiny, 4, max_new=8, rng_seed=4)
    ids = [router.submit(r) for r in reqs]
    finished = []
    deadline = time.monotonic() + 120
    while (router._pending or router._inflight) and time.monotonic() < deadline:
        for w in workers:
            w.step()
        finished.extend(router.step())
    assert sorted(f.request_id for f in finished) == ids
    for f in finished:
        assert f.tokens == oracles[f.request_id]
    stats = router.stats()
    assert "accept_rate" in stats and 0.0 <= stats["accept_rate"] <= 1.0
    assert stats["per_host_accept_rate"]


# -- eos refeed edge case ---------------------------------------------------
def test_eos_request_roundtrip(tiny):
    model, variables = tiny
    store = HashStore()
    w = make_worker(store, tiny, "host0")
    w.register()
    router = Router(store)
    prompt = np.asarray([5, 11, 17], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 8)
    # stop mid-stream: EOS is the first token past the opening one that the
    # continuation has not emitted before (a token seen earlier would end
    # the request there; this oracle opens with one token five times over)
    stop = next(i for i in range(1, len(oracle)) if oracle[i] not in oracle[:i])
    eos = oracle[stop]
    rid = router.submit(Request(prompt=prompt, max_new_tokens=8, eos_token=eos))
    finished = []
    deadline = time.monotonic() + 60
    while (router._pending or router._inflight) and time.monotonic() < deadline:
        w.step()
        finished.extend(router.step())
    (f,) = [x for x in finished if x.request_id == rid]
    assert f.reason == "eos"
    assert f.tokens == oracle[: stop + 1]


# -- full churn with real processes + TCPStore (satellite: failover) -------
@pytest.mark.slow
def test_subprocess_worker_sigkill_failover(tiny):
    """Real multi-process failover: 2 worker processes over a TCPStore,
    one SIGKILLed mid-decode; every request finishes exactly once with
    oracle-parity streams reassembled across the kill."""
    from tests._subproc import free_port

    model, variables = tiny
    port = free_port()
    from pytorch_distributed_tpu.distributed.store import TCPStore

    master = TCPStore("127.0.0.1", port, is_master=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(REPO),
        MH_PORT=str(port),
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "mh_worker.py"),
             f"host{i}", "0.15"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for i in range(2)
    ]
    try:
        # TTL must exceed the worst-case scheduler stall — here that is
        # jit compilation inside the first step (a worker cannot
        # heartbeat from inside scheduler.step())
        router = Router(master, heartbeat_ttl_s=10.0)
        reqs, oracles = prompts_and_oracles(tiny, 6, max_new=14, rng_seed=5)
        ids = [router.submit(r) for r in reqs]
        finished = []
        # wait until the victim process has committed some tokens
        deadline = time.monotonic() + 300
        victim_chan = None
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                outs = [p.communicate()[0].decode() for p in procs
                        if p.poll() is not None]
                raise AssertionError(f"worker died early:\n" + "\n".join(outs))
            finished.extend(router.step())
            started = [
                inf for inf in router._inflight.values()
                if inf.chan is not None and inf.committed
                and len(inf.committed) < inf.max_new_tokens
            ]
            if len(router.hosts) == 2 and started:
                victim_chan = started[0].chan
                break
            time.sleep(0.02)
        assert victim_chan is not None, "workers never started decoding"
        victim = [
            hv for hv in router.hosts.values() if hv.chan == victim_chan
        ][0]
        idx = int(victim.host.removeprefix("host"))
        procs[idx].kill()

        finished.extend(router.run(timeout_s=180))
        assert sorted(f.request_id for f in finished) == ids
        for f in finished:
            assert f.tokens == oracles[f.request_id]
        assert router.stats()["evictions"] == 1
        router.stop_hosts()
        survivor = procs[1 - idx]
        survivor.wait(timeout=60)
        assert survivor.returncode == 0, survivor.stdout.read().decode()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        master.close()
