"""Continuous batching — per-step join/evict over the engine's slot batch.

The Orca-style iteration-level scheduler: requests queue FIFO, every free
slot is filled by a prefill at the top of each step, one decode step then
advances ALL active slots together, and sequences that hit EOS / their
token budget / slot capacity are evicted at iteration granularity so their
slot is reusable on the very next step (finished slots are padding lanes
until then: the batch never reshapes, nothing recompiles). With a
speculative engine (``engine.spec_k > 0``) a step consumes 1..k+1 tokens a
slot from one draft+verify round, scanned token by token, so streams and
finish reasons are the one-token path's.

Every request latency starts at ARRIVAL: the instant the front end names
(``Request.arrival_s``: when the request was DUE), else the submission.
Under a profiler session a request's spans, each with its ``request_id``:
``pdt.sched.submit`` (``late_us``: submitted so long after it arrived),
``pdt.sched.admit`` > ``pdt.engine.prefill`` > ``.dispatch`` > ``.inputs``,
``.call``, then ``pdt.sched.evict``; ``pdt.sched.step`` > ``.consume`` say
where a step's host time went. From the host-clock intervals of its recent
engine calls the scheduler accounts every wait by cause, where it happens:
on ``sched.admit`` ``queue_us == wait_prefill_us + wait_decode_us +
wait_other_us`` (inside OTHER requests' prefills, inside decode steps, the
rest) and ``ttft_us == queue_us + admit_us``, exactly. ``FinishedRequest``
carries the waits with no session; ``Scheduler.stats()`` and the
``serving.*`` events (``emit_events``) are the operator's running view.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.observability import (
    LatencyTracker,
    RatioTracker,
    record_event,
    span,
)
from pytorch_distributed_tpu.serving.engine import InferenceEngine
from pytorch_distributed_tpu.serving.paging import (
    PageAllocator,
    RadixTree,
    fork_pages,
)

__all__ = ["Request", "FinishedRequest", "Scheduler"]


@dataclasses.dataclass
class Request:
    """One generation request.

    ``max_new_tokens`` counts generated tokens (the prompt is free);
    ``eos_token`` (if set) stops generation when sampled — the EOS itself
    is included in the output tokens.
    """

    prompt: Any  # 1-D int sequence
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    request_id: Optional[int] = None  # assigned by submit()
    #: host clock (``time.perf_counter``) at which the request arrived;
    #: ``submit()`` stamps it when left None. An open-loop front end passes
    #: the instant the request was DUE, a router the instant it took it in.
    arrival_s: Optional[float] = None


@dataclasses.dataclass
class FinishedRequest:
    request_id: int
    prompt: np.ndarray
    tokens: List[int]  # generated tokens (includes EOS if hit)
    reason: str  # "eos" | "length"
    ttft_s: float  # arrival -> first token (queue wait included)
    total_s: float  # arrival -> eviction
    queue_s: float = 0.0  # arrival -> admission
    wait_prefill_s: float = 0.0  # of queue_s: inside OTHERS' prefills
    wait_decode_s: float = 0.0  # of queue_s: inside decode steps


@dataclasses.dataclass
class _SlotState:
    request: Request
    prompt: np.ndarray
    tokens: List[int]
    queue_s: float
    ttft_s: float
    wait_prefill_s: float
    wait_decode_s: float


class Scheduler:
    """Drives an :class:`InferenceEngine` over a FIFO request queue.

    Usage::

        sched = Scheduler(engine)
        for r in requests:
            sched.submit(r)
        finished = sched.run()   # or step() in a serving loop
    """

    def __init__(self, engine: InferenceEngine, *, emit_events: bool = True):
        self.engine = engine
        self.cache = engine.init_cache()
        self.draft_cache = engine.init_draft_cache()
        self.emit_events = emit_events
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[_SlotState]] = [None] * engine.n_slots
        self.last_tokens = np.zeros((engine.n_slots,), np.int32)
        # token at position lengths-1 per slot (the separate-draft
        # catch-up refeed reads it; harmless otherwise)
        self.prev_tokens = np.zeros((engine.n_slots,), np.int32)
        self.active = np.zeros((engine.n_slots,), bool)
        self._n_active = 0  # == active.sum(), kept beside it by admit/evict
        # cache positions the active sequences hold (prompt + tokens - 1
        # each): what a decode step has to read at least; kept by admit /
        # consume / evict, never recounted
        self._kv_rows = 0
        self.ttft = LatencyTracker()
        self.decode_step = LatencyTracker()  # per decode step (whole batch)
        self.tokens_generated = 0
        self.steps = 0
        self.decode_steps = 0
        self.weight_swaps = 0
        # speculative-decoding efficiency counters
        self.accept_rate = RatioTracker()        # accepted / proposed
        self.tokens_per_forward = RatioTracker()  # decode tokens / forwards
        self._next_id = 0
        # paged-cache control plane (engine.cache_kind == "paged"): the
        # allocator owns pages and reservations, the radix tree maps prompt
        # prefixes to live chains; the device only sees the block tables
        if engine.cache_kind == "paged":
            self.allocator: Optional[PageAllocator] = PageAllocator(
                n_pages=engine.n_pages, page_size=engine.page_size,
                n_slots=engine.n_slots, max_pages=engine.max_pages,
            )
            self.radix: Optional[RadixTree] = RadixTree(engine.page_size)
        else:
            self.allocator = None
            self.radix = None
        self.prefill_tokens_total = 0   # prompt tokens across admissions
        self.prefill_tokens_cached = 0  # of those, served from the radix
        self.pages_reclaimed = 0        # pages the radix tree gave back
        # host-clock (t0, t1, was_prefill) of the newest engine calls (16 s
        # of 3.9 ms steps; bounded, so nothing grows): what ``_waited``
        # accounts a wait from
        self._engine_calls: Deque[Tuple[float, float, bool]] = deque(
            maxlen=4096)

    # -- queue -------------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Enqueue; returns the assigned request id (admission is FIFO)."""
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        with span("sched.submit", prompt_len=len(request.prompt),
                  queued=len(self.queue), n_active=self._n_active) as submit:
            if request.request_id is None:
                request.request_id = self._next_id
                self._next_id += 1
            else:
                self._next_id = max(self._next_id, request.request_id + 1)
            now = time.perf_counter()
            if request.arrival_s is None:
                request.arrival_s = now
            submit.set_metadata(request_id=request.request_id,
                                late_us=int((now - request.arrival_s) * 1e6))
            self.queue.append(request)
        return request.request_id

    @property
    def n_active(self) -> int:
        return self._n_active

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    # -- one iteration -----------------------------------------------------
    def step(self) -> List[FinishedRequest]:
        """Admit into free slots, run one decode step, evict finished.

        Returns the requests that completed during this step.
        """
        with span("sched.step", n_active=self._n_active,
                  queued=len(self.queue), **self._step_counts()):
            self.steps += 1
            return self._step()

    def _step(self) -> List[FinishedRequest]:
        finished: List[FinishedRequest] = []

        # join: fill every free slot from the queue (lowest slot first so
        # admission order is deterministic for a given free set)
        for slot in range(self.engine.n_slots):
            if not self.queue:
                break
            if self.slots[slot] is not None:
                continue
            plan = None
            if self.allocator is not None:
                plan = self._plan_admission(self.queue[0])
                if plan is None:
                    # page-pool backpressure: FIFO head can't reserve its
                    # worst-case span — stop admitting (no head-of-line
                    # skip, so admission order stays deterministic)
                    break
            finished.extend(self._admit(slot, self.queue.popleft(), plan))

        # decode: one token (or a verified speculative span) per active slot
        if self._n_active:
            if self.engine.spec_k > 0:
                finished.extend(self._spec_step())
            else:
                self._grow_chains(spec=False)
                t0 = time.perf_counter()
                self.cache, toks = self.engine.decode(
                    self.cache, self.last_tokens, self.active
                )
                dt = time.perf_counter() - t0
                self._engine_calls.append((t0, t0 + dt, False))
                with span("sched.consume") as consume:
                    self.decode_step.add(dt)
                    self.decode_steps += 1
                    n_act = self._n_active
                    self.tokens_generated += n_act
                    self._kv_rows += n_act
                    self.tokens_per_forward.add(n_act)
                    n_before = len(finished)
                    for slot in map(int, np.flatnonzero(self.active)):
                        st = self.slots[slot]
                        tok = int(toks[slot])
                        st.tokens.append(tok)
                        self.last_tokens[slot] = tok
                        finished.extend(self._maybe_finish(slot))
                    consume.set_metadata(
                        tokens=n_act, finished=len(finished) - n_before)
        return finished

    def _spec_step(self) -> List[FinishedRequest]:
        """One speculative round: draft k, verify once, consume the
        accepted span per slot (EOS / budget / capacity scanned token by
        token so finish semantics match the one-token path exactly)."""
        finished: List[FinishedRequest] = []
        k = self.engine.spec_k
        self._grow_chains(spec=True)
        t0 = time.perf_counter()
        (self.cache, self.draft_cache, emitted, counts,
         prev_next) = self.engine.spec_decode(
            self.cache, self.draft_cache, self.last_tokens,
            self.prev_tokens, self.active,
        )
        dt = time.perf_counter() - t0
        # the drafts and the verify are ONE engine call, one interval: a
        # request that waits for a slot waits out the whole round
        self._engine_calls.append((t0, t0 + dt, False))
        with span("sched.consume") as consume:
            n_before = len(finished)
            self.decode_step.add(dt)
            self.decode_steps += 1
            active_slots = list(map(int, np.flatnonzero(self.active)))
            n_act = len(active_slots)
            accepted = int(counts[self.active].sum()) - n_act
            self.accept_rate.add(accepted, k * n_act)
            consumed_total = 0
            step_counts = {}
            for slot in active_slots:
                st = self.slots[slot]
                n = int(counts[slot])
                consumed = 0
                for j in range(n):
                    tok = int(emitted[slot, j])
                    st.tokens.append(tok)
                    self.last_tokens[slot] = tok
                    consumed += 1
                    self._kv_rows += 1
                    done = self._maybe_finish(slot)
                    if done:
                        finished.extend(done)
                        break
                else:
                    # survived the whole span: the engine's bookkeeping token
                    # at lengths-1 feeds the next draft catch-up
                    self.prev_tokens[slot] = int(prev_next[slot])
                    if self.allocator is not None:
                        # page-granular rollback: pages acquired for the
                        # rejected tail of the span go back to the free list
                        # (position prompt+tokens-1 is the next write — its
                        # page stays); the reservation credit they drew is
                        # refunded so the same slot can re-acquire them
                        new_len = st.prompt.shape[0] + len(st.tokens) - 1
                        self.allocator.release_tail(slot, new_len)
                consumed_total += consumed
                step_counts[slot] = consumed
            self.tokens_generated += consumed_total
            self.tokens_per_forward.add(consumed_total)
            if self.emit_events:
                record_event(
                    "serving.spec_step", source="scheduler",
                    proposed=k * n_act, accepted=accepted,
                    consumed=step_counts,
                )
            consume.set_metadata(
                tokens=consumed_total, finished=len(finished) - n_before)
        return finished

    def swap_params(self, params, *, draft_params=None,
                    max_staging_bytes: Optional[int] = None):
        """Reshard-while-serving checkpoint swap, between decode steps.

        Delegates to :meth:`InferenceEngine.swap_params` — the new weights
        are redistributed onto the engine's current placement by the
        ``redistribute/`` planner, so in-flight sequences continue without
        recompiling and (for equal values) without perturbing a single
        token. ``step()`` is synchronous, so any moment outside a
        ``step()`` call is a safe swap point. ``self.weight_swaps`` counts
        the swaps made.
        """
        t0 = time.perf_counter()
        cost = self.engine.swap_params(
            params, draft_params=draft_params,
            max_staging_bytes=max_staging_bytes,
        )
        dt = time.perf_counter() - t0
        self.weight_swaps += 1
        if self.emit_events:
            record_event(
                "serving.weight_swap", source="scheduler",
                bytes_moved=cost.bytes_moved, peak_bytes=cost.peak_bytes,
                naive_gather_bytes=cost.naive_gather_bytes,
                duration_s=dt, n_active=self.n_active,
            )
        return cost

    def run(self, *, max_steps: Optional[int] = None) -> List[FinishedRequest]:
        """Step until the queue and all slots drain; returns all finished
        requests in completion order."""
        out: List[FinishedRequest] = []
        steps = 0
        while self.has_work:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out

    # -- paged-cache internals ---------------------------------------------
    def _span_pages(self, req: Request, prompt_len: int) -> int:
        """Worst-case pages a request can ever touch: prompt + its token
        budget (+ the speculative write margin), capped by max_len."""
        span = prompt_len + req.max_new_tokens + self.engine.spec_k
        return self.allocator.pages_for(min(span, self.engine.max_len))

    def _plan_admission(self, req: Request):
        """Probe whether the FIFO head can reserve its worst-case span
        (reclaiming LRU cached-prefix pages if short). Returns the
        admission plan ``(matched_pages, cached_len, cow_last, span_pages)``
        or None — the probe does not touch LRU/stats so backpressure
        retries don't skew them; the final (touching) match runs only once
        the plan is known to fit."""
        alloc = self.allocator
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        prompt_len = int(prompt.shape[0])
        span = self._span_pages(req, prompt_len)

        def _need():
            matched = self.radix.match(prompt, touch=False)
            cow = len(matched) * alloc.page_size >= prompt_len
            return matched, span - len(matched) + (1 if cow else 0)

        matched, need = _need()
        short = need - alloc.available_pages
        if short > 0:
            self.pages_reclaimed += self.radix.reclaim(alloc, short)
            matched, need = _need()  # reclaim may have dropped matched pages
        if need > alloc.available_pages:
            return None
        matched = self.radix.match(prompt)  # LRU touch + hit/miss stats
        cached_len = len(matched) * alloc.page_size
        cow_last = cached_len >= prompt_len
        if cow_last:
            cached_len = prompt_len - 1
        return matched, cached_len, cow_last, span, prompt_len

    def _sync_tables(self) -> None:
        if self.allocator is not None and self.allocator.dirty:
            self.cache = self.cache.replace(
                block_tables=jnp.asarray(self.allocator.tables)
            )
            self.allocator.dirty = False

    def _grow_chains(self, *, spec: bool) -> None:
        """Before a decode/spec step: every active slot's chain must cover
        its write span (next position, or the k-token speculative window).
        Draws on the slot's admission reservation, so it cannot fail."""
        if self.allocator is None:
            return
        margin = self.engine.spec_k if spec else 0
        for slot in map(int, np.flatnonzero(self.active)):
            st = self.slots[slot]
            next_pos = st.prompt.shape[0] + len(st.tokens) - 1
            need = min(next_pos + margin + 1, self.engine.max_len)
            self.allocator.ensure(slot, need)
        self._sync_tables()

    # -- internals ---------------------------------------------------------
    def _admit(self, slot: int, req: Request,
               plan=None) -> List[FinishedRequest]:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        wait_us, queue_s, prefill_s, decode_s = self._waited(req.arrival_s)
        with span("sched.admit", request_id=req.request_id, slot=slot,
                  prompt_len=int(prompt.shape[0]), **wait_us) as admit:
            cached_len = 0
            if self.allocator is not None:
                if plan is None:
                    plan = self._plan_admission(req)
                    if plan is None:
                        raise RuntimeError(
                            f"page reservation failed for request "
                            f"{req.request_id}"
                        )
                cached_len = self._attach_pages(slot, plan)
            admit.set_metadata(cached_len=cached_len)
            t0 = time.perf_counter()
            self.cache, first_tok = self.engine.prefill(
                self.cache, slot, prompt, cached_len=cached_len,
                request_id=req.request_id,
            )
            self._engine_calls.append((t0, time.perf_counter(), True))
            if self.draft_cache is not None:
                # the separate draft's slotted cache has no prefix sharing —
                # it always prefills the full prompt
                self.draft_cache = self.engine.prefill_draft(
                    self.draft_cache, slot, prompt
                )
            if self.radix is not None:
                # cache the prompt's full pages for future admissions (pins
                # them in the allocator so they outlive this sequence)
                self.radix.insert(prompt, self.allocator.chain(slot),
                                  self.allocator)
            # token at position lengths-1 == the prompt tail (draft catch-up)
            self.prev_tokens[slot] = int(prompt[-1])
            # from ARRIVAL: the wait in the queue is part of the time to the
            # first token (an open loop's requests wait for a free slot)
            ttft = time.perf_counter() - req.arrival_s
            self.ttft.add(ttft)
            # page bookkeeping + the request's own prefill, as an integer so
            # that ttft_us == queue_us + admit_us to the microsecond
            ttft_us = int(ttft * 1e6)
            admit.set_metadata(admit_us=ttft_us - wait_us["queue_us"],
                               ttft_us=ttft_us)
            self.slots[slot] = _SlotState(
                request=req, prompt=prompt, tokens=[first_tok],
                queue_s=queue_s, ttft_s=ttft,
                wait_prefill_s=prefill_s, wait_decode_s=decode_s,
            )
            self.last_tokens[slot] = first_tok
            self.active[slot] = True
            self._n_active += 1
            self._kv_rows += int(prompt.shape[0])
            self.tokens_generated += 1
            self.prefill_tokens_total += int(prompt.shape[0])
            self.prefill_tokens_cached += cached_len
            if self.emit_events:
                record_event(
                    "serving.admit", source="scheduler",
                    request_id=req.request_id, slot=slot,
                    prompt_len=int(prompt.shape[0]), ttft_s=ttft,
                    queue_s=queue_s, cached_len=cached_len,
                )
            # the prefill's own sampled token may already end the request
            return self._maybe_finish(slot)

    def _attach_pages(self, slot: int, plan) -> int:
        """Paged admission: attach the radix-matched chain by reference,
        reserve the worst-case remainder, COW-fork the last page when the
        WHOLE prompt is cached (the final token must still prefill — its
        logits seed sampling — and its K/V write may not touch a shared
        page). Returns the cached prefix length."""
        alloc = self.allocator
        matched, cached_len, cow_last, span, prompt_len = plan
        if not alloc.admit(slot, matched, span, cow_last=cow_last):
            raise RuntimeError("page reservation lost between plan and admit")
        if cow_last and matched:
            pair = alloc.cow(slot, len(matched) - 1)
            if pair is not None:
                self.cache = fork_pages(self.cache, pair[0], pair[1])
        # private pages for the uncached tail (reservation-backed)
        alloc.ensure(slot, prompt_len)
        self._sync_tables()
        return cached_len

    def _maybe_finish(self, slot: int) -> List[FinishedRequest]:
        st = self.slots[slot]
        req = st.request
        last = st.tokens[-1]
        reason = None
        if req.eos_token is not None and last == req.eos_token:
            reason = "eos"
        elif len(st.tokens) >= req.max_new_tokens:
            reason = "length"
        # cache capacity: the next decode writes at position
        # prompt_len + len(tokens) - 1, which must stay < max_len
        elif st.prompt.shape[0] + len(st.tokens) - 1 >= self.engine.max_len:
            reason = "length"
        if reason is None:
            return []
        return [self._evict(slot, reason)]

    def _evict(self, slot: int, reason: str) -> FinishedRequest:
        st = self.slots[slot]
        with span("sched.evict", request_id=st.request.request_id,
                  new_tokens=len(st.tokens), reason=reason):
            total = time.perf_counter() - st.request.arrival_s
            if self.allocator is not None:
                # drop the slot's reference on every chain page: private
                # pages go straight back to the free list; radix-pinned
                # prompt pages stay resident for the next same-prefix
                # admission
                self.allocator.free_slot(slot)
            self.cache = self.cache.evict(slot)
            self.slots[slot] = None
            self.active[slot] = False
            self._n_active -= 1
            self._kv_rows -= st.prompt.shape[0] + len(st.tokens) - 1
            fin = FinishedRequest(
                request_id=st.request.request_id,
                prompt=st.prompt,
                tokens=list(st.tokens),
                reason=reason,
                ttft_s=st.ttft_s,
                total_s=total,
                queue_s=st.queue_s,
                wait_prefill_s=st.wait_prefill_s,
                wait_decode_s=st.wait_decode_s,
            )
            if self.emit_events:
                record_event(
                    "serving.request_finished", source="scheduler",
                    request_id=fin.request_id, slot=slot, reason=reason,
                    prompt_len=int(st.prompt.shape[0]),
                    new_tokens=len(fin.tokens),
                    ttft_s=fin.ttft_s, total_s=fin.total_s,
                    queue_s=fin.queue_s,
                    wait_prefill_s=fin.wait_prefill_s,
                    wait_decode_s=fin.wait_decode_s,
                )
            return fin

    def _waited(self, arrival_s: float):
        """What filled the wait from ``arrival_s`` to now, the admission:
        the seconds the scheduler was inside OTHER requests'
        ``engine.prefill`` and inside decode steps, from the intervals of
        its newest engine calls, walked back until one ends before the
        arrival (a call in progress AT the arrival counts for its part
        after it, so a front end may name an arrival in the past). Returns
        the ``pdt.sched.admit`` span's wait stats, whole microseconds that
        add up exactly (``wait_other_us`` is the rest: consume,
        bookkeeping, the caller's loop, sleep, and whatever the history no
        longer holds), then the wait and its two parts in seconds."""
        queue_s = time.perf_counter() - arrival_s
        prefill_s = decode_s = 0.0
        prefills = 0
        for t0, t1, was_prefill in reversed(self._engine_calls):
            if t1 <= arrival_s:
                break
            inside = t1 - max(t0, arrival_s)
            if was_prefill:
                prefill_s += inside
                prefills += 1
            else:
                decode_s += inside
        queue_us = int(queue_s * 1e6)
        prefill_us = min(int(prefill_s * 1e6), queue_us)
        decode_us = min(int(decode_s * 1e6), queue_us - prefill_us)
        return ({"queue_us": queue_us, "wait_prefill_us": prefill_us,
                 "prefills_ahead": prefills, "wait_decode_us": decode_us,
                 "wait_other_us": queue_us - prefill_us - decode_us},
                queue_s, min(prefill_s, queue_s), min(decode_s, queue_s))

    # -- stats -------------------------------------------------------------
    def _step_counts(self) -> Dict[str, int]:
        """What ``pdt.sched.step`` says beside the queue and the active
        slots: the step's number, the rows the active sequences hold and,
        of a paged cache, the pages there are, those free (reservations
        apart) and those held, the pages the radix tree gave back since the
        step before began, and its hits and misses so far. (Down here: a
        line above ``_step`` and ``_admit`` moves every serving program's
        compile-cache key.)"""
        counts = {"step": self.steps, "kv_rows": self._kv_rows}
        if self.allocator is not None:
            free = int(self.allocator.free_pages)
            pages = self.allocator.n_pages - 1    # but the trash page
            counts.update(
                pages=pages, pages_free=free, pages_held=pages - free,
                pages_reclaimed=self.pages_reclaimed - self._reclaimed_seen,
                radix_hits=self.radix.hits, radix_misses=self.radix.misses)
            self._reclaimed_seen = self.pages_reclaimed
        return counts

    _reclaimed_seen = 0

    @property
    def free_pages(self) -> int:
        """Admission capacity in pages — the multihost load snapshot's
        occupancy signal. Paged: physically free pages net of outstanding
        reservations. Slotted: free slots in page-equivalents (each slot
        is a ``max_len`` worth of pages), so routers compare the two cache
        kinds on one scale."""
        if self.allocator is not None:
            return int(self.allocator.available_pages)
        return (self.engine.n_slots - self.n_active) * self.engine.max_pages

    def stats(self) -> Dict[str, float]:
        """Aggregate serving stats (feeds the decode benchmark report).

        ``tokens_per_target_forward`` counts decode-phase tokens over
        decode/spec step invocations (prefills excluded); without
        speculation it equals the active-slot average, with speculation it
        grows toward ``(1 + accept_rate * spec_k)`` per slot.
        """
        d = self.decode_step.summary()
        out = {
            "tokens_generated": float(self.tokens_generated),
            "decode_steps": float(self.decode_steps),
            "decode_step_p50_s": d["p50_s"],
            "decode_step_p99_s": d["p99_s"],
            "decode_step_mean_s": d["mean_s"],
            "ttft_p50_s": self.ttft.percentile(50),
            "ttft_p99_s": self.ttft.percentile(99),
            "tokens_per_target_forward": self.tokens_per_forward.rate(),
        }
        if self.engine.spec_k > 0:
            out["spec_k"] = float(self.engine.spec_k)
            out["accept_rate"] = self.accept_rate.rate()
        out["cache_kind"] = self.engine.cache_kind
        if self.allocator is not None:
            out["free_pages"] = float(self.allocator.available_pages)
            out["page_size"] = float(self.allocator.page_size)
            out["n_pages"] = float(self.allocator.n_pages)
            out["radix_hits"] = float(self.radix.hits)
            out["radix_misses"] = float(self.radix.misses)
            out["pages_reclaimed"] = float(self.pages_reclaimed)
            out["prefill_tokens_total"] = float(self.prefill_tokens_total)
            out["prefill_tokens_cached"] = float(self.prefill_tokens_cached)
        return out
