"""Eager process groups: Backend / Work / ProcessGroup.

Capability parity (SURVEY.md §2.1): ``c10d::Backend`` (virtual collective set
— ``Backend.hpp:158-400``), ``c10d::Work`` (async handle with
``wait(timeout)`` — ``Work.hpp:113``), ``c10d::ProcessGroup`` (facade +
sequence numbers), ``FakeProcessGroup`` (no-op backend) and
``ProcessGroupWrapper`` (shadow-verification of op/shape agreement under
debug mode — ``ProcessGroupWrapper.hpp:21``).

Role in a TPU framework (SURVEY §5.8): the *compute-path* collectives are
compiled (XLA over ICI; see ``ops.collectives``); this eager layer is the
control plane — rank bootstrap, object collectives, barriers, debug
verification — and the host-tensor fallback (the gloo role), riding the C++
TCPStore over DCN. Payloads are numpy arrays; device arrays round-trip
through host memory here by design (eager collectives are not the hot path).
"""

from __future__ import annotations

import io
import pickle
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from pytorch_distributed_tpu.distributed.store import PrefixStore, Store

from pytorch_distributed_tpu.observability import (
    record_event,
    span,
)


__all__ = [
    "ReduceOp",
    "Work",
    "Backend",
    "StoreBackend",
    "FakeBackend",
    "ProcessGroup",
    "ProcessGroupWrapper",
]


class ReduceOp(Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "prod"

    def apply(self, arrays: Sequence[np.ndarray]) -> np.ndarray:
        stack = np.stack(arrays)
        # dtype-preserving like torch's all_reduce (numpy would promote
        # int32 sums to the platform int); AVG keeps numpy's float mean
        if self is ReduceOp.SUM:
            return stack.sum(axis=0, dtype=stack.dtype)
        if self is ReduceOp.AVG:
            return stack.mean(axis=0)
        if self is ReduceOp.MAX:
            return stack.max(axis=0)
        if self is ReduceOp.MIN:
            return stack.min(axis=0)
        return stack.prod(axis=0, dtype=stack.dtype)


class Work:
    """Async op handle (c10d::Work). ``wait()`` re-raises backend errors."""

    def __init__(self, future: Future, op_name: str):
        self._future = future
        self.op_name = op_name

    def wait(self, timeout: Optional[timedelta] = None):
        t = timeout.total_seconds() if timeout is not None else None
        return self._future.result(timeout=t)

    def is_completed(self) -> bool:
        return self._future.done()

    def is_success(self) -> bool:
        return (
            self._future.done()
            and self._future.exception() is None
        )

    def result(self):
        # Blocks until completion, like torch's Work.result() (ADVICE.md
        # round 1: timeout=0 raised TimeoutError on pending async work).
        return self._future.result()

    def exception(self):
        return self._future.exception()


class _DoneWork(Work):
    def __init__(self, value=None, op_name: str = ""):
        f: Future = Future()
        f.set_result(value)
        super().__init__(f, op_name)


def _dump(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _load(b: bytes) -> np.ndarray:
    return np.load(io.BytesIO(b), allow_pickle=False)


class Backend:
    """Abstract collective backend over host arrays (c10d::Backend)."""

    def __init__(self, store: Store, rank: int, world_size: int):
        self.store = store
        self.rank = rank
        self.world_size = world_size

    # every method returns the result synchronously; ProcessGroup wraps
    # them in Works via its executor
    def broadcast(self, arr: np.ndarray, src: int, seq: int) -> np.ndarray:
        raise NotImplementedError

    def all_reduce(self, arr, op: ReduceOp, seq: int) -> np.ndarray:
        raise NotImplementedError

    def reduce(self, arr, dst: int, op: ReduceOp, seq: int) -> Optional[np.ndarray]:
        raise NotImplementedError

    def all_gather(self, arr, seq: int) -> List[np.ndarray]:
        raise NotImplementedError

    def gather(self, arr, dst: int, seq: int) -> Optional[List[np.ndarray]]:
        raise NotImplementedError

    def scatter(self, arrs: Optional[List[np.ndarray]], src: int, seq: int) -> np.ndarray:
        raise NotImplementedError

    def reduce_scatter(self, arr, op: ReduceOp, seq: int) -> np.ndarray:
        raise NotImplementedError

    def all_to_all(self, arrs: List[np.ndarray], seq: int) -> List[np.ndarray]:
        raise NotImplementedError

    def send(self, arr, dst: int, tag: int) -> None:
        raise NotImplementedError

    def recv(self, src: int, tag: int) -> np.ndarray:
        raise NotImplementedError

    def barrier(self, seq: int) -> None:
        raise NotImplementedError

    def abort(self) -> None:
        pass

    def shutdown(self) -> None:
        pass


class StoreBackend(Backend):
    """Collectives over the coordination store (the gloo-role / CPU path).

    Every rank posts its contribution under a sequence-numbered key and reads
    peers' contributions; an ack counter lets the last reader GC the round's
    keys so long runs don't leak store memory.
    """

    def __init__(self, store: Store, rank: int, world_size: int,
                 timeout: timedelta = timedelta(seconds=300)):
        super().__init__(store, rank, world_size)
        self.timeout = timeout

    # -- key helpers -------------------------------------------------------
    def _post(self, kind: str, seq: int, rank: int, payload: bytes):
        self.store.set(f"{kind}/{seq}/{rank}", payload)

    def _read(self, kind: str, seq: int, rank: int) -> bytes:
        return self.store.get(f"{kind}/{seq}/{rank}", self.timeout)

    def _gc(self, kind: str, seq: int, nkeys: Optional[int] = None):
        """Last rank to ack deletes the round's keys."""
        acks = self.store.add(f"{kind}/{seq}/acks", 1)
        if acks == self.world_size:
            n = nkeys if nkeys is not None else self.world_size
            for r in range(n):
                self.store.delete_key(f"{kind}/{seq}/{r}")
            self.store.delete_key(f"{kind}/{seq}/acks")

    # -- collectives -------------------------------------------------------
    def all_gather(self, arr, seq: int) -> List[np.ndarray]:
        arr = np.asarray(arr)
        self._post("ag", seq, self.rank, _dump(arr))
        out = [
            arr.copy() if r == self.rank else _load(self._read("ag", seq, r))
            for r in range(self.world_size)
        ]
        self._gc("ag", seq)
        return out

    def all_reduce(self, arr, op: ReduceOp, seq: int) -> np.ndarray:
        return op.apply(self.all_gather(arr, seq))

    def broadcast(self, arr, src: int, seq: int) -> np.ndarray:
        arr = np.asarray(arr)
        if self.rank == src:
            self._post("bc", seq, src, _dump(arr))
            out = arr.copy()
        else:
            out = _load(self._read("bc", seq, src))
        acks = self.store.add(f"bc/{seq}/acks", 1)
        if acks == self.world_size:
            self.store.delete_key(f"bc/{seq}/{src}")
            self.store.delete_key(f"bc/{seq}/acks")
        return out

    def reduce(self, arr, dst: int, op: ReduceOp, seq: int):
        gathered = self.all_gather(arr, seq)
        return op.apply(gathered) if self.rank == dst else None

    def gather(self, arr, dst: int, seq: int):
        gathered = self.all_gather(arr, seq)
        return gathered if self.rank == dst else None

    def scatter(self, arrs, src: int, seq: int) -> np.ndarray:
        if self.rank == src:
            if arrs is None or len(arrs) != self.world_size:
                raise ValueError("scatter src needs world_size arrays")
            for r in range(self.world_size):
                self._post("sc", seq, r, _dump(np.asarray(arrs[r])))
        out = _load(self._read("sc", seq, self.rank))
        self._gc("sc", seq)
        return out

    def reduce_scatter(self, arr, op: ReduceOp, seq: int) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.shape[0] % self.world_size:
            raise ValueError(
                f"reduce_scatter dim 0 ({arr.shape[0]}) not divisible by "
                f"world size {self.world_size}"
            )
        full = op.apply(self.all_gather(arr, seq))
        chunk = arr.shape[0] // self.world_size
        return full[self.rank * chunk : (self.rank + 1) * chunk]

    def all_to_all(self, arrs, seq: int) -> List[np.ndarray]:
        if len(arrs) != self.world_size:
            raise ValueError("all_to_all needs world_size input chunks")
        for r in range(self.world_size):
            self.store.set(
                f"a2a/{seq}/{self.rank}->{r}", _dump(np.asarray(arrs[r]))
            )
        out = []
        for r in range(self.world_size):
            key = f"a2a/{seq}/{r}->{self.rank}"
            out.append(_load(self.store.get(key, self.timeout)))
        acks = self.store.add(f"a2a/{seq}/acks", 1)
        if acks == self.world_size:
            for i in range(self.world_size):
                for j in range(self.world_size):
                    self.store.delete_key(f"a2a/{seq}/{i}->{j}")
            self.store.delete_key(f"a2a/{seq}/acks")
        return out

    # -- P2P ---------------------------------------------------------------
    def send(self, arr, dst: int, tag: int) -> None:
        seq = self.store.add(f"p2p/{self.rank}->{dst}/{tag}/sent", 1)
        self.store.set(
            f"p2p/{self.rank}->{dst}/{tag}/{seq}", _dump(np.asarray(arr))
        )

    def recv(self, src: int, tag: int) -> np.ndarray:
        seq = self.store.add(f"p2p/{src}->{self.rank}/{tag}/recvd", 1)
        key = f"p2p/{src}->{self.rank}/{tag}/{seq}"
        try:
            data = _load(self.store.get(key, self.timeout))
        except Exception:
            # roll the reservation back: a timed-out recv must not skew
            # the channel by one message forever (r4 review)
            self.store.add(f"p2p/{src}->{self.rank}/{tag}/recvd", -1)
            raise
        self.store.delete_key(key)
        return data

    def barrier(self, seq: int) -> None:
        self.store.barrier_id(
            f"barrier/{seq}", self.rank, self.world_size, self.timeout
        )
        # GC the round's keys once every rank has passed the barrier
        acks = self.store.add(f"barrier/{seq}/acks", 1)
        if acks == self.world_size:
            self.store.delete_key(f"barrier/{seq}/arrived")
            self.store.delete_key(f"barrier/{seq}/done")
            self.store.delete_key(f"barrier/{seq}/acks")


class FakeBackend(Backend):
    """No-op backend (c10d FakeProcessGroup): ops return immediately with
    identity results — single-process simulation of any world size."""

    def broadcast(self, arr, src, seq):
        return np.asarray(arr).copy()

    def all_reduce(self, arr, op, seq):
        return np.asarray(arr).copy()

    def reduce(self, arr, dst, op, seq):
        return np.asarray(arr).copy() if self.rank == dst else None

    def all_gather(self, arr, seq):
        return [np.asarray(arr).copy() for _ in range(self.world_size)]

    def gather(self, arr, dst, seq):
        if self.rank == dst:
            return [np.asarray(arr).copy() for _ in range(self.world_size)]
        return None

    def scatter(self, arrs, src, seq):
        if self.rank == src and arrs:
            return np.asarray(arrs[self.rank]).copy()
        return np.zeros(())

    def reduce_scatter(self, arr, op, seq):
        arr = np.asarray(arr)
        chunk = arr.shape[0] // self.world_size
        return arr[self.rank * chunk : (self.rank + 1) * chunk].copy()

    def all_to_all(self, arrs, seq):
        return [np.asarray(a).copy() for a in arrs]

    def send(self, arr, dst, tag):
        pass

    def recv(self, src, tag):
        raise RuntimeError("FakeBackend cannot recv (no peer data)")

    def barrier(self, seq):
        pass


class ProcessGroup:
    """Collective facade with sequence numbers + async Work handles.

    Sequence numbers serve two jobs (c10d parity): keying each collective
    round in the store, and desync detection — every rank must issue the
    same ops in the same order (verified by ProcessGroupWrapper).
    """

    def __init__(self, backend: Backend, group_name: str = "default"):
        self.backend = backend
        self.group_name = group_name
        self._seq = 0
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix=f"pg-{group_name}"
        )
        # object collectives stage their size exchange through this
        # preallocated scratch instead of building a fresh int64 array
        # per call; guarded by its own lock — next_seq takes self._lock
        # inside every collective, so reusing that here would deadlock
        self._size_scratch = np.zeros(1, np.int64)
        self._obj_lock = threading.Lock()
        # every eager collective is recorded in the C++ flight recorder
        # (dump-on-hang post-mortems — SURVEY §2.6); never let observability
        # break the data path
        try:
            from pytorch_distributed_tpu.observability.flight_recorder import (
                get_flight_recorder,
            )

            self._fr = get_flight_recorder()
        except Exception:  # pragma: no cover - native lib unavailable
            self._fr = None

    @property
    def rank(self) -> int:
        return self.backend.rank

    @property
    def world_size(self) -> int:
        return self.backend.world_size

    def next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _submit(self, fn: Callable, op_name: str, async_op: bool,
                nbytes: int = 0):
        fr = self._fr
        entry = fr.record(op_name, self.group_name, nbytes) if fr else None

        def run():
            # per-collective trace events (ParamCommsUtils role, SURVEY
            # §5.1): a host span in the profiler's trace + a structured
            # event with op, bytes, and group metadata, and a per-op
            # counter metric.
            t0 = time.perf_counter()
            try:
                with span(f"pg.{op_name}", group=self.group_name,
                          nbytes=nbytes):
                    out = fn()
            except Exception:
                if fr:
                    fr.complete(entry, ok=False)
                record_event(
                    "collective_failed", op=op_name,
                    group=self.group_name, nbytes=nbytes,
                )
                raise
            if fr:
                fr.complete(entry, ok=True)
            record_event(
                "collective", op=op_name, group=self.group_name,
                nbytes=nbytes, world_size=self.world_size,
                duration_ms=round((time.perf_counter() - t0) * 1e3, 3),
            )
            return out

        if async_op:
            return Work(self._pool.submit(run), op_name)
        return _DoneWork(run(), op_name)

    # -- collective API (numpy in/out) ------------------------------------
    def broadcast(self, arr, src: int = 0, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.broadcast(arr, src, seq), "broadcast", async_op
        )

    def all_reduce(self, arr, op: ReduceOp = ReduceOp.SUM, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.all_reduce(arr, op, seq), "all_reduce", async_op
        )

    def reduce(self, arr, dst: int, op: ReduceOp = ReduceOp.SUM, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.reduce(arr, dst, op, seq), "reduce", async_op
        )

    def all_gather(self, arr, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.all_gather(arr, seq), "all_gather", async_op
        )

    def gather(self, arr, dst: int = 0, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.gather(arr, dst, seq), "gather", async_op
        )

    def scatter(self, arrs, src: int = 0, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.scatter(arrs, src, seq), "scatter", async_op
        )

    def reduce_scatter(self, arr, op: ReduceOp = ReduceOp.SUM, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.reduce_scatter(arr, op, seq),
            "reduce_scatter", async_op,
        )

    def all_to_all(self, arrs, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.all_to_all(arrs, seq), "all_to_all", async_op
        )

    def send(self, arr, dst: int, tag: int = 0):
        self.backend.send(arr, dst, tag)

    def recv(self, src: int, tag: int = 0) -> np.ndarray:
        return self.backend.recv(src, tag)

    def isend(self, arr, dst: int, tag: int = 0) -> Work:
        return Work(
            self._pool.submit(self.backend.send, arr, dst, tag), "send"
        )

    def irecv(self, src: int, tag: int = 0) -> Work:
        return Work(self._pool.submit(self.backend.recv, src, tag), "recv")

    def barrier(self, *, async_op=False):
        seq = self.next_seq()
        return self._submit(
            lambda: self.backend.barrier(seq), "barrier", async_op
        )

    # -- object collectives (pickle payloads) ------------------------------
    # Torch-style two-phase: exchange payload LENGTHS first, then pad every
    # payload to the max so all ranks issue identically-shaped tensor
    # collectives — required for the desync-verification wrapper to hold for
    # object collectives too (torch all_gather_object does the same).
    def _padded_payload(self, obj: Any) -> tuple:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        with self._obj_lock:
            self._size_scratch[0] = payload.size
            gathered = self.all_gather(self._size_scratch).result()
            sizes = [int(s[0]) for s in gathered]
        padded = np.zeros(max(sizes), np.uint8)
        padded[: payload.size] = payload
        return padded, sizes

    def all_gather_object(self, obj: Any) -> List[Any]:
        padded, sizes = self._padded_payload(obj)
        gathered = self.all_gather(padded).result()
        return [
            pickle.loads(np.asarray(a[:n]).tobytes())
            for a, n in zip(gathered, sizes)
        ]

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        # pickle once, on the source rank only — non-src ranks previously
        # serialized their (ignored) local obj just to size the buffer
        data = pickle.dumps(obj) if self.rank == src else None
        with self._obj_lock:
            self._size_scratch[0] = len(data) if data is not None else 0
            size = self.broadcast(self._size_scratch, src).result()
            n = int(size[0])
        buf = np.zeros(n, np.uint8)
        if self.rank == src:
            buf[:] = np.frombuffer(data, dtype=np.uint8)
        out = self.broadcast(buf, src).result()
        return pickle.loads(np.asarray(out).tobytes())

    def gather_object(self, obj: Any, dst: int = 0) -> Optional[List[Any]]:
        padded, sizes = self._padded_payload(obj)
        out = self.gather(padded, dst).result()
        if out is None:
            return None
        return [
            pickle.loads(np.asarray(a[:n]).tobytes())
            for a, n in zip(out, sizes)
        ]

    def shutdown(self):
        self.backend.shutdown()
        self._pool.shutdown(wait=False, cancel_futures=True)


class ProcessGroupWrapper(ProcessGroup):
    """Shadow-verification wrapper (TORCH_DISTRIBUTED_DEBUG=DETAIL parity):
    before each collective, all ranks exchange (op, shape, dtype) through the
    store and any mismatch raises with a per-rank report — catching desync /
    ordering races before they corrupt data."""

    def __init__(self, backend: Backend, group_name: str = "default"):
        super().__init__(backend, group_name)

    def _verify(self, op_name: str, arr) -> None:
        desc = {
            "op": op_name,
            "shape": tuple(np.asarray(arr).shape) if arr is not None else None,
            "dtype": str(np.asarray(arr).dtype) if arr is not None else None,
        }
        seq = self.next_seq()
        payload = np.frombuffer(pickle.dumps(desc), dtype=np.uint8)
        gathered = self.backend.all_gather(payload, seq)
        descs = [pickle.loads(a.tobytes()) for a in gathered]
        if any(d != descs[0] for d in descs[1:]):
            report = "\n".join(f"  rank {i}: {d}" for i, d in enumerate(descs))
            raise RuntimeError(
                f"collective desync detected in group "
                f"{self.group_name!r}:\n{report}"
            )

    def broadcast(self, arr, src: int = 0, *, async_op=False):
        self._verify("broadcast", arr)
        return super().broadcast(arr, src, async_op=async_op)

    def all_reduce(self, arr, op=ReduceOp.SUM, *, async_op=False):
        self._verify(f"all_reduce.{op.value}", arr)
        return super().all_reduce(arr, op, async_op=async_op)

    def reduce_scatter(self, arr, op=ReduceOp.SUM, *, async_op=False):
        self._verify(f"reduce_scatter.{op.value}", arr)
        return super().reduce_scatter(arr, op, async_op=async_op)

    def all_gather(self, arr, *, async_op=False):
        self._verify("all_gather", arr)
        return super().all_gather(arr, async_op=async_op)

    def barrier(self, *, async_op=False):
        self._verify("barrier", None)
        return super().barrier(async_op=async_op)
