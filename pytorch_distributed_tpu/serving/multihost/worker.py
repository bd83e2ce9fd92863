"""HostWorker — one host's serving loop behind the store control plane.

Wraps the existing single-host :class:`~pytorch_distributed_tpu.serving.
scheduler.Scheduler` (one per host, the dp axis across hosts): drains its
channel inbox into the local FIFO queue, runs the continuous-batching
step, streams newly generated tokens back through the outbox in
sequence-numbered chunks, and publishes a combined load/heartbeat
snapshot every loop so the router can do admission control and declare
this host dead when the snapshot stops changing.

The worker never talks to other workers and never blocks on the store —
every read is ``get_nowait`` — so a wedged control plane degrades to "no
new work", not "decode stalls". Optionally it exposes the same
:class:`~pytorch_distributed_tpu.elastic.health.HealthCheckServer` the
elastic agent uses, so cluster tooling probes serving hosts exactly like
training hosts.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from pytorch_distributed_tpu.distributed.store import Store
from pytorch_distributed_tpu.observability import record_event
from pytorch_distributed_tpu.serving.multihost import protocol
from pytorch_distributed_tpu.serving.multihost.protocol import Keys
from pytorch_distributed_tpu.serving.scheduler import Request, Scheduler

__all__ = ["HostWorker"]


class HostWorker:
    """Serve one host's :class:`Scheduler` under a store-coordinated router.

    Args:
      store: any :class:`Store` (TCPStore across hosts, HashStore in tests).
      scheduler: the local continuous-batching scheduler to drive.
      host_id: human-readable label for events and the report (channel
        identity is assigned by :meth:`register`, not by this label — a
        restarted host reuses its label but gets a fresh channel).
      namespace: store key prefix; one namespace == one deployment.
      chunk_tokens: max tokens per outbox chunk (bounds per-key payload).
      idle_sleep_s: sleep when a loop iteration found no work.
      health_port: when set, start an elastic ``HealthCheckServer`` on it
        (0 picks a free port) and beat it every loop.
      param_loader: ``loader(ckpt_dir, step) -> variables`` for live weight
        pushes (``Router.push_weights``): called when the channel's weights
        key advances past the served version, and the result — on whatever
        mesh/layout the loader produced it — is swapped into the running
        scheduler between decode steps via the redistribution planner
        (``Scheduler.swap_params``). None ignores pushes.
    """

    def __init__(
        self,
        store: Store,
        scheduler: Scheduler,
        *,
        host_id: str,
        namespace: str = protocol.DEFAULT_NAMESPACE,
        chunk_tokens: int = 16,
        idle_sleep_s: float = 0.002,
        health_port: Optional[int] = None,
        emit_events: bool = True,
        param_loader=None,
    ):
        self.store = store
        self.scheduler = scheduler
        self.host_id = str(host_id)
        self.keys = Keys(namespace)
        self.chunk_tokens = int(chunk_tokens)
        self.idle_sleep_s = float(idle_sleep_s)
        self.emit_events = emit_events
        self.chan: Optional[int] = None
        self._in_cursor = 0
        self._out_seq = 0
        self._hb = 0
        self._sent: Dict[int, int] = {}      # request_id -> tokens flushed
        self._routes: Dict[int, int] = {}    # request_id -> route_id
        self._chunk_seq: Dict[int, int] = {}  # request_id -> next chunk seq
        self._killed = False
        self._health = None
        self._health_port = health_port
        self.param_loader = param_loader
        self.weights_version = 0

    # -- membership --------------------------------------------------------
    def register(self) -> int:
        """Claim a fresh channel and announce this host's profile.

        The join-counter pattern from the elastic rendezvous: ``add`` on
        the members counter hands out the slot, the announce key published
        after the bump carries the payload. Re-registration (a recovered
        host rejoining) is just another join — new channel, clean cursors.
        """
        eng = self.scheduler.engine
        self.chan = self.store.add(self.keys.members(), 1) - 1
        self._in_cursor = 0
        self._out_seq = 0
        self.store.set(
            self.keys.member(self.chan),
            protocol.dumps(protocol.announce_msg(
                self.host_id, self.chan, n_slots=eng.n_slots,
                prefill_len=eng.prefill_len, max_len=eng.max_len,
                spec_k=eng.spec_k,
                page_size=eng.page_size if eng.cache_kind == "paged" else 0,
            )),
        )
        self._publish_load()
        if self._health_port is not None and self._health is None:
            from pytorch_distributed_tpu.elastic.health import HealthCheckServer

            self._health = HealthCheckServer(
                self._load_snapshot, port=self._health_port, host="127.0.0.1"
            ).start()
        if self.emit_events:
            record_event(
                "serving.host_join", source="multihost",
                host=self.host_id, chan=self.chan,
                n_slots=eng.n_slots, prefill_len=eng.prefill_len,
            )
        return self.chan

    def kill(self) -> None:
        """Simulate a crash: the loop exits as soon as it observes the
        flag — no drain, no final flush, no more heartbeats."""
        self._killed = True

    # -- one loop iteration ------------------------------------------------
    def step(self) -> bool:
        """Drain inbox, run one scheduler step, flush results, publish
        load/heartbeat. Returns True if any work was done."""
        self._check_weights()
        admitted = self._drain_inbox()
        did_decode = False
        if self.scheduler.has_work:
            finished = self.scheduler.step()
            did_decode = True
            for fin in finished:
                self._flush_tokens(fin.request_id, fin.tokens)
                self._emit_finished(fin)
        # stream progress for requests still in flight
        for st in self.scheduler.slots:
            if st is not None:
                self._flush_tokens(st.request.request_id, st.tokens)
        self._publish_load()
        return admitted > 0 or did_decode

    def serve_forever(self) -> None:
        """Register (if needed) and loop until the stop key appears and
        all accepted work has drained, or :meth:`kill` fires."""
        if self.chan is None:
            self.register()
        while not self._killed:
            busy = self.step()
            if not busy and self._stop_requested() and not self.scheduler.has_work:
                self._publish_load(draining=True)
                break
            if not busy:
                time.sleep(self.idle_sleep_s)
        if self._health is not None:
            self._health.stop()
            self._health = None

    # -- internals ---------------------------------------------------------
    def _check_weights(self) -> None:
        """Swap in a pushed checkpoint (reshard-while-serving).

        Runs between scheduler steps — the only place a swap is safe — so
        in-flight decodes continue against the new weights on the next
        step. The loader may hand back weights on ANY mesh/layout; the
        scheduler's planner-backed swap lands them on this host's serving
        placement without recompiling, and (greedy, equal values) without
        perturbing a single token of the streams in flight.
        """
        if self.param_loader is None or self.chan is None:
            return
        raw = self.store.get_nowait(self.keys.weights(self.chan))
        if raw is None:
            return
        msg = protocol.loads(raw)
        version = int(msg["version"])
        if version <= self.weights_version:
            return
        variables = self.param_loader(msg["ckpt_dir"], msg["step"])
        cost = self.scheduler.swap_params(variables)
        self.weights_version = version
        if self.emit_events:
            record_event(
                "serving.weight_push", source="multihost",
                host=self.host_id, chan=self.chan, version=version,
                ckpt_dir=msg["ckpt_dir"], step=msg["step"],
                bytes_moved=cost.bytes_moved, peak_bytes=cost.peak_bytes,
            )

    def _stop_requested(self) -> bool:
        return self.store.get_nowait(self.keys.stop(self.chan)) is not None

    def _drain_inbox(self) -> int:
        n = 0
        while True:
            key = self.keys.inbox(self.chan, self._in_cursor)
            raw = self.store.get_nowait(key)
            if raw is None:
                return n
            self.store.delete_key(key)
            self._in_cursor += 1
            msg = protocol.loads(raw)
            rid = int(msg["request_id"])
            self._routes[rid] = int(msg["route_id"])
            self._chunk_seq.setdefault(rid, 0)
            self._sent.setdefault(rid, 0)
            prompt = np.asarray(msg["prompt"], np.int32)
            eng = self.scheduler.engine
            if prompt.shape[0] > eng.prefill_len or prompt.shape[0] >= eng.max_len:
                # router checks host profiles before routing; this is the
                # belt-and-braces path for a misconfigured deployment
                self._post(protocol.finished_msg(
                    rid, self._routes[rid], self._chunk_seq[rid],
                    reason="rejected", n_tokens=0, ttft_s=0.0, total_s=0.0,
                ))
                self._forget(rid)
                n += 1
                continue
            # the router's wall clock, turned into an age on this host's
            # own: the wait in the router and on the wire counts as queue
            sent = msg.get("arrival_unix")
            self.scheduler.submit(Request(
                prompt=prompt,
                max_new_tokens=int(msg["max_new_tokens"]),
                eos_token=msg["eos_token"],
                request_id=rid,
                arrival_s=None if sent is None else
                time.perf_counter() - max(0.0, time.time() - sent),
            ))
            n += 1

    def _flush_tokens(self, rid: int, tokens) -> None:
        sent = self._sent.get(rid, 0)
        route = self._routes.get(rid)
        if route is None:
            return
        while sent < len(tokens):
            chunk = [int(t) for t in tokens[sent:sent + self.chunk_tokens]]
            self._post(protocol.tokens_chunk(
                rid, route, self._chunk_seq[rid], chunk
            ))
            self._chunk_seq[rid] += 1
            sent += len(chunk)
        self._sent[rid] = sent

    def _emit_finished(self, fin) -> None:
        route = self._routes.get(fin.request_id)
        if route is None:
            return
        self._post(protocol.finished_msg(
            fin.request_id, route, self._chunk_seq[fin.request_id],
            reason=fin.reason, n_tokens=len(fin.tokens),
            ttft_s=fin.ttft_s, total_s=fin.total_s, queue_s=fin.queue_s,
        ))
        self._forget(fin.request_id)

    def _forget(self, rid: int) -> None:
        self._sent.pop(rid, None)
        self._routes.pop(rid, None)
        self._chunk_seq.pop(rid, None)

    def _post(self, msg) -> None:
        self.store.set(
            self.keys.outbox(self.chan, self._out_seq), protocol.dumps(msg)
        )
        self._out_seq += 1

    def _load_snapshot(self, draining: bool = False) -> dict:
        sched = self.scheduler
        return protocol.load_msg(
            hb=self._hb, active=sched.n_active, queued=len(sched.queue),
            n_slots=sched.engine.n_slots, draining=draining,
            accept_num=sched.accept_rate.num, accept_den=sched.accept_rate.den,
            weights_version=self.weights_version,
            free_pages=sched.free_pages,
        )

    def _publish_load(self, draining: bool = False) -> None:
        self._hb += 1
        self.store.set(
            self.keys.load(self.chan),
            protocol.dumps(self._load_snapshot(draining)),
        )
        if self._health is not None:
            self._health.heartbeat()
