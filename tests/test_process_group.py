"""Process-group tests — N ranks as N threads over one C++ TCPStore (the
MultiThreadedTestCase ladder rung, SURVEY.md §4 item 2)."""

import threading
from datetime import timedelta

import numpy as np
import pytest

import pytorch_distributed_tpu.distributed as dist
from pytorch_distributed_tpu.distributed import (
    FakeBackend,
    HashStore,
    PrefixStore,
    ProcessGroup,
    ProcessGroupWrapper,
    ReduceOp,
    StoreBackend,
    TCPStore,
)

WS = 4


def run_ranks(world_size, fn, *, wrapper=False, store=None, backend="store"):
    """Run fn(rank, pg) on world_size threads sharing one store; returns
    per-rank results and re-raises the first failure. ``backend`` selects
    the collective implementation: "store" (TCP KV round-trip) or "xla"
    (compiled device-path collectives)."""
    master = store or TCPStore("127.0.0.1", 0, world_size, is_master=True,
                               timeout=timedelta(seconds=30))
    results = [None] * world_size
    errors = []

    def worker(rank):
        try:
            if rank == 0:
                s = master
            else:
                s = TCPStore("127.0.0.1", master.port, world_size,
                             timeout=timedelta(seconds=30))
            prefixed = PrefixStore("test", s)
            if backend == "xla":
                from pytorch_distributed_tpu.distributed.xla_backend import (
                    XlaBackend,
                )

                be = XlaBackend(prefixed, rank, world_size,
                                timeout=timedelta(seconds=30))
            else:
                be = StoreBackend(prefixed, rank, world_size,
                                  timeout=timedelta(seconds=30))
            cls = ProcessGroupWrapper if wrapper else ProcessGroup
            results[rank] = fn(rank, cls(be))
        except Exception as e:  # pragma: no cover - surfaced via raise below
            errors.append((rank, e))

    threads = [
        threading.Thread(target=worker, args=(r,)) for r in range(world_size)
    ]
    [t.start() for t in threads]
    [t.join(timeout=60) for t in threads]
    if errors:
        raise errors[0][1]
    return results


class TestCollectives:
    BACKEND = "store"

    def _run(self, fn, **kw):
        return run_ranks(WS, fn, backend=self.BACKEND, **kw)

    def test_all_reduce_sum(self):
        def fn(rank, pg):
            return pg.all_reduce(np.full(3, float(rank + 1))).result()

        for out in self._run(fn):
            np.testing.assert_allclose(out, np.full(3, 10.0))  # 1+2+3+4

    def test_all_reduce_ops(self):
        def fn(rank, pg):
            x = np.array([float(rank + 1)])
            return {
                "max": pg.all_reduce(x, ReduceOp.MAX).result()[0],
                "min": pg.all_reduce(x, ReduceOp.MIN).result()[0],
                "avg": pg.all_reduce(x, ReduceOp.AVG).result()[0],
                "prod": pg.all_reduce(x, ReduceOp.PRODUCT).result()[0],
            }

        for out in self._run(fn):
            assert out == {"max": 4.0, "min": 1.0, "avg": 2.5, "prod": 24.0}

    def test_broadcast(self):
        def fn(rank, pg):
            x = np.full(2, float(rank))
            return pg.broadcast(x, src=2).result()

        for out in self._run(fn):
            np.testing.assert_allclose(out, [2.0, 2.0])

    def test_all_gather(self):
        def fn(rank, pg):
            return pg.all_gather(np.array([rank, rank * 10])).result()

        for out in self._run(fn):
            assert len(out) == WS
            for r, arr in enumerate(out):
                np.testing.assert_array_equal(arr, [r, r * 10])

    def test_reduce_to_dst(self):
        def fn(rank, pg):
            return pg.reduce(np.array([1.0]), dst=1).result()

        results = self._run(fn)
        assert results[1][0] == 4.0
        assert all(r is None for i, r in enumerate(results) if i != 1)

    def test_scatter(self):
        def fn(rank, pg):
            arrs = (
                [np.array([10.0 * r]) for r in range(WS)] if rank == 0 else None
            )
            return pg.scatter(arrs, src=0).result()

        for r, out in enumerate(self._run(fn)):
            np.testing.assert_allclose(out, [10.0 * r])

    def test_reduce_scatter(self):
        def fn(rank, pg):
            x = np.arange(8.0)  # same on all ranks
            return pg.reduce_scatter(x).result()

        for r, out in enumerate(self._run(fn)):
            np.testing.assert_allclose(out, np.arange(8.0)[r * 2:(r + 1) * 2] * WS)

    def test_all_to_all(self):
        def fn(rank, pg):
            chunks = [np.array([rank * 10 + c]) for c in range(WS)]
            return pg.all_to_all(chunks).result()

        for r, out in enumerate(self._run(fn)):
            np.testing.assert_array_equal(
                np.concatenate(out), [s * 10 + r for s in range(WS)]
            )

    def test_send_recv(self):
        def fn(rank, pg):
            if rank == 0:
                pg.send(np.array([42.0]), dst=3)
                return None
            if rank == 3:
                return pg.recv(src=0)
            return None

        results = self._run(fn)
        np.testing.assert_allclose(results[3], [42.0])

    def test_barrier_and_async(self):
        order = []

        def fn(rank, pg):
            w = pg.barrier(async_op=True)
            w.wait(timeout=timedelta(seconds=30))
            order.append(rank)
            return w.is_success()

        assert all(self._run(fn))
        assert sorted(order) == list(range(WS))

    def test_object_collectives(self):
        def fn(rank, pg):
            objs = pg.all_gather_object({"rank": rank, "data": [rank] * 2})
            bc = pg.broadcast_object("hello" if rank == 0 else None, src=0)
            return objs, bc

        for objs, bc in self._run(fn):
            assert [o["rank"] for o in objs] == list(range(WS))
            assert bc == "hello"

    def test_store_keys_gced(self):
        """Collective rounds must not leak store keys."""
        master = TCPStore("127.0.0.1", 0, WS, is_master=True,
                          timeout=timedelta(seconds=30))

        def fn(rank, pg):
            for _ in range(5):
                pg.all_reduce(np.ones(4)).result()
            pg.barrier().result()
            return True

        self._run(fn, store=master)
        # p2p/barrier counters remain; bulk payload keys must be gone
        leaked = master.num_keys()
        assert leaked <= 8, f"leaked {leaked} keys"
        master.close()


class TestCollectivesXla(TestCollectives):
    """The SAME collective contract against the device-path backend
    (VERDICT round-1 item 7: eager XLA backend, cached compiled
    collectives, one device per rank on the virtual mesh)."""

    BACKEND = "xla"


class TestWrapperDesyncDetection:
    def test_matching_ops_pass(self):
        def fn(rank, pg):
            return pg.all_reduce(np.ones(3)).result()

        for out in run_ranks(WS, fn, wrapper=True):
            np.testing.assert_allclose(out, np.full(3, 4.0))

    def test_object_collectives_pass_verification(self):
        """Unequal objects (different pickle sizes) must NOT trip the
        desync detector — payloads are length-exchanged and padded."""

        def fn(rank, pg):
            objs = pg.all_gather_object("x" * (rank * 100 + 1))
            bc = pg.broadcast_object({"big": "B" * 500} if rank == 0 else None)
            return objs, bc

        for objs, bc in run_ranks(WS, fn, wrapper=True):
            assert [len(o) for o in objs] == [1, 101, 201, 301]
            assert bc == {"big": "B" * 500}

    def test_shape_mismatch_detected(self):
        def fn(rank, pg):
            shape = 3 if rank != 2 else 5  # rank 2 desyncs
            with pytest.raises(RuntimeError, match="desync"):
                pg.all_reduce(np.ones(shape)).result()
            return True

        assert all(run_ranks(WS, fn, wrapper=True))


class TestFakeBackend:
    def test_identity_semantics(self):
        pg = ProcessGroup(FakeBackend(HashStore(), rank=2, world_size=8))
        x = np.arange(8.0)
        np.testing.assert_array_equal(pg.all_reduce(x).result(), x)
        assert len(pg.all_gather(x).result()) == 8
        np.testing.assert_array_equal(
            pg.reduce_scatter(x).result(), x[2:3]
        )
        pg.barrier().result()
        assert pg.rank == 2 and pg.world_size == 8


class TestModuleAPI:
    def test_init_lifecycle_fake(self):
        dist.init_process_group(
            "fake", store=HashStore(), rank=0, world_size=4
        )
        try:
            assert dist.is_initialized()
            assert dist.get_rank() == 0
            assert dist.get_world_size() == 4
            out = dist.all_reduce(np.ones(2))
            np.testing.assert_array_equal(out, np.ones(2))
            sub = dist.new_group([0, 1])  # inherits the fake backend
            assert sub is not None and sub.world_size == 2
            assert isinstance(sub.backend, FakeBackend)
            np.testing.assert_array_equal(
                sub.all_reduce(np.ones(2)).result(), np.ones(2)
            )
            none_grp = dist.new_group([1, 2], backend="fake")
            assert none_grp is None
        finally:
            dist.destroy_process_group()
        assert not dist.is_initialized()

    def test_double_init_raises(self):
        dist.init_process_group("fake", store=HashStore(), rank=0, world_size=1)
        try:
            with pytest.raises(RuntimeError):
                dist.init_process_group(
                    "fake", store=HashStore(), rank=0, world_size=1
                )
        finally:
            dist.destroy_process_group()

    def test_plugin_registry(self):
        calls = []

        def creator(store, rank, ws, timeout):
            calls.append((rank, ws))
            return FakeBackend(store, rank, ws)

        dist.register_backend("testplugin", creator)
        dist.init_process_group(
            "testplugin", store=HashStore(), rank=1, world_size=3
        )
        try:
            assert calls == [(1, 3)]
            assert dist.get_rank() == 1
        finally:
            dist.destroy_process_group()
        with pytest.raises(ValueError):
            dist.register_backend("fake", creator)  # duplicate

    def test_debug_detail_uses_wrapper(self, monkeypatch):
        monkeypatch.setenv("TPU_DISTRIBUTED_DEBUG", "DETAIL")
        dist.init_process_group("fake", store=HashStore(), rank=0, world_size=1)
        try:
            assert isinstance(dist.get_default_group(), ProcessGroupWrapper)
        finally:
            dist.destroy_process_group()


class TestXlaDevicePath:
    """Device-path specifics: results live on the rank's device, and the
    compiled-program cache holds exactly one executable per (op, signature)
    across repeated calls (SURVEY §7 hard part 2: no per-call recompiles)."""

    def test_results_device_resident_and_cache_stable(self):
        import jax

        devices = jax.devices()

        def fn(rank, pg):
            be = pg.backend
            for _ in range(5):
                out = pg.all_reduce(np.full(3, float(rank))).result()
            assert isinstance(out, jax.Array)
            assert list(out.devices()) == [devices[rank]]
            for _ in range(3):
                pg.reduce_scatter(np.arange(8.0)).result()
            return be.cache_stats()

        for stats in run_ranks(WS, fn, backend="xla"):
            # one jit-cache entry per op signature despite repeated calls
            assert stats["all_reduce_sum"] == 1, stats
            assert stats["reduce_scatter_sum"] == 1, stats

    def test_two_shapes_two_cache_entries(self):
        def fn(rank, pg):
            pg.all_reduce(np.ones(4)).result()
            pg.all_reduce(np.ones(4)).result()
            pg.all_reduce(np.ones((2, 3))).result()
            return pg.backend.cache_stats()["all_reduce_sum"]

        assert all(n == 2 for n in run_ranks(WS, fn, backend="xla"))

    def test_init_process_group_xla(self):
        """The north star seam end-to-end: init_process_group(backend='xla')."""
        import jax

        store = HashStore()
        results = [None] * 2
        errs = []

        def worker(rank):
            try:
                from pytorch_distributed_tpu.distributed.xla_backend import (
                    XlaBackend,
                )

                be = XlaBackend(PrefixStore("ipg", store), rank, 2)
                pg = ProcessGroup(be)
                results[rank] = np.asarray(
                    pg.all_reduce(np.array([float(rank + 1)])).result()
                )
            except Exception as e:
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert not errs, errs
        for out in results:
            np.testing.assert_allclose(out, [3.0])

        # and via the module API (rank 0 path of a world of 1)
        dist.init_process_group("xla", store=HashStore(), rank=0, world_size=1)
        try:
            out = dist.all_reduce(np.ones(2))
            assert isinstance(out, jax.Array)
            np.testing.assert_allclose(np.asarray(out), np.ones(2))
        finally:
            dist.destroy_process_group()

    def test_subgroup_devices_via_set_device(self):
        """A subgroup whose members own devices {2,3} must build its mesh
        and route P2P over THOSE devices, not devices[:W] (r2 weak #3).
        Members declare their device via set_device (torch
        cuda.set_device parity); device publication goes over the store."""
        import jax
        from pytorch_distributed_tpu.distributed.xla_backend import (
            XlaBackend,
            set_device,
        )

        devices = jax.devices()
        store = HashStore()
        results = [None] * 2
        errs = []

        def worker(sub_rank):
            try:
                global_device = devices[2 + sub_rank]
                set_device(global_device)
                be = XlaBackend(PrefixStore("sub", store), sub_rank, 2)
                assert be.group_devices == [devices[2], devices[3]]
                pg = ProcessGroup(be)
                if sub_rank == 0:
                    pg.send(np.arange(3.0), dst=1, tag=7)
                    out = pg.all_reduce(np.ones(2)).result()
                else:
                    got = pg.recv(src=0, tag=7)
                    # the received array landed on the RECEIVER's device
                    assert list(got.devices()) == [devices[3]], got.devices()
                    np.testing.assert_allclose(np.asarray(got), [0, 1, 2])
                    out = pg.all_reduce(np.ones(2)).result()
                # collective results live on the member's own device
                assert list(out.devices()) == [global_device]
                results[sub_rank] = np.asarray(out)
            except Exception as e:
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        assert not errs, errs
        for out in results:
            np.testing.assert_allclose(out, [2.0, 2.0])

    def test_shutdown_clears_exchange_for_reinit(self):
        """destroy + re-init of a same-named group over a persistent store
        must start a fresh exchange, not join the stale one (r2 advice,
        medium): shutdown deletes the store token and the exchange."""
        from pytorch_distributed_tpu.distributed import xla_backend as xb

        store = HashStore()

        def one_life(value):
            results = [None] * 2
            errs = []

            def worker(rank):
                try:
                    be = xb.XlaBackend(PrefixStore("life", store), rank, 2)
                    pg = ProcessGroup(be)
                    results[rank] = np.asarray(
                        pg.all_reduce(np.array([value])).result()
                    )
                    pg.shutdown()
                except Exception as e:
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
            [t.start() for t in ts]
            [t.join(60) for t in ts]
            assert not errs, errs
            return results

        before = len(xb._EXCHANGES)
        for out in one_life(1.0):
            np.testing.assert_allclose(out, [2.0])
        assert len(xb._EXCHANGES) == before  # shutdown dropped the entry
        assert store.check(["xla_backend/token/ws2"]) is False \
            or not store.get("xla_backend/token/ws2")
        # second incarnation over the SAME store: works, fresh exchange
        for out in one_life(2.0):
            np.testing.assert_allclose(out, [4.0])
        assert len(xb._EXCHANGES) == before


class TestBatchOpsAndShrink:
    """batch_isend_irecv, the coalescing manager, and shrink_group (torch
    distributed_c10d.py:2990/2837/6368 — r2 component #13)."""

    def test_batch_isend_irecv_ring(self):
        """The canonical deadlock-prone pattern batching exists for: every
        rank sends right and receives left, posting both before waiting."""
        from pytorch_distributed_tpu.distributed import (
            P2POp,
            batch_isend_irecv,
        )

        def fn(rank, pg):
            right = (rank + 1) % WS
            left = (rank - 1) % WS
            works = batch_isend_irecv(pg, [
                P2POp("isend", np.full(3, float(rank)), right, tag=1),
                P2POp("irecv", None, left, tag=1),
            ])
            got = np.asarray(works[1].result())
            works[0].wait()
            return got

        for rank, got in enumerate(run_ranks(WS, fn)):
            np.testing.assert_allclose(got, np.full(3, float((rank - 1) % WS)))

    def test_coalescing_manager_one_wire_op(self):
        """N same-dtype all_reduces inside the context become ONE backend
        collective; every slot still gets its exact reduced result."""
        from pytorch_distributed_tpu.distributed import coalescing_manager

        def fn(rank, pg):
            calls = {"n": 0}
            orig = pg.backend.all_reduce

            def counting(arr, op, seq):
                calls["n"] += 1
                return orig(arr, op, seq)

            pg.backend.all_reduce = counting
            a = np.full((2, 2), float(rank))
            b = np.arange(3, dtype=np.float64) + rank
            c = np.full(4, float(rank), np.float32)
            with coalescing_manager(pg) as cm:
                ha = cm.all_reduce(a)
                hb = cm.all_reduce(b)  # f64: same group as a? dtype split
                hc = cm.all_reduce(c)  # f32: its own group
            return calls["n"], ha.result, hb.result, hc.result

        S = sum(range(WS))
        for n_calls, ra, rb, rc in run_ranks(WS, fn):
            assert n_calls == 2  # one per dtype group, not one per tensor
            np.testing.assert_allclose(ra, np.full((2, 2), float(S)))
            np.testing.assert_allclose(
                rb, np.arange(3, dtype=np.float64) * WS + S)
            np.testing.assert_allclose(rc, np.full(4, float(S), np.float32))

    def test_p2pop_validation(self):
        from pytorch_distributed_tpu.distributed import P2POp

        with pytest.raises(ValueError, match="isend|irecv"):
            P2POp("send", np.ones(1), 0)
        with pytest.raises(ValueError, match="needs a tensor"):
            P2POp("isend", None, 0)

    def test_shrink_group_survivors_recover(self):
        """Ranks {0,2,3} shrink dead rank 1 out and the new group's
        collectives work with contiguous new ranks — no full restart."""
        import pytorch_distributed_tpu.distributed as dist
        from pytorch_distributed_tpu.distributed.store import HashStore

        store = HashStore()
        results = {}
        errs = []
        import threading as _th

        # module-level world is per process; drive the internals directly
        # the way shrink would run inside each surviving worker process:
        from pytorch_distributed_tpu.distributed import (
            ProcessGroup,
            StoreBackend,
        )
        from pytorch_distributed_tpu.distributed.store import PrefixStore

        survivors = [0, 2, 3]

        def worker(old_rank):
            try:
                # old group exists but rank 1 is dead; survivors form the
                # shrunk group over a fresh namespace in old-rank order
                new_rank = survivors.index(old_rank)
                pg = ProcessGroup(StoreBackend(
                    PrefixStore("pg:shrink1:1", store), new_rank,
                    len(survivors),
                ), "shrink1:1")
                out = pg.all_reduce(np.array([float(old_rank)])).result()
                results[old_rank] = float(np.asarray(out)[0])
            except Exception as e:
                errs.append(e)

        ts = [_th.Thread(target=worker, args=(r,)) for r in survivors]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert not errs, errs
        assert all(v == 5.0 for v in results.values()), results  # 0+2+3

    def test_shrink_group_module_api(self):
        """The public shrink_group path on a world of 1 (module world is
        per-process): argument validation + fresh group creation."""
        import pytorch_distributed_tpu.distributed as dist
        from pytorch_distributed_tpu.distributed.store import HashStore

        dist.init_process_group("store", store=HashStore(), rank=0,
                                world_size=2)
        try:
            with pytest.raises(ValueError, match="cannot shrink itself"):
                dist.shrink_group([0])
            pg = dist.shrink_group([1])  # rank 1 presumed dead
            assert pg.world_size == 1 and pg.rank == 0
            out = pg.all_reduce(np.ones(2)).result()
            np.testing.assert_allclose(np.asarray(out), np.ones(2))
        finally:
            dist.destroy_process_group()


class TestCollectiveEvents:
    """Per-collective trace events (ParamCommsUtils role, SURVEY §5.1)."""

    def test_events_recorded_per_collective(self):
        from pytorch_distributed_tpu.observability.logging_utils import (
            recent_events,
        )

        def fn(rank, pg):
            pg.all_reduce(np.ones(8)).result()
            pg.barrier().result()
            return True

        run_ranks(2, fn)
        evs = [e for e in recent_events(200) if e.name == "collective"]
        ops = {e.metadata["op"] for e in evs if e.metadata}
        assert "all_reduce" in ops and "barrier" in ops
        ar = [e for e in evs if e.metadata and e.metadata["op"] == "all_reduce"]
        assert all("duration_ms" in e.metadata for e in ar)
