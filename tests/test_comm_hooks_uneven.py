"""DDP comm hooks + uneven-input handling (VERDICT r2 missing #6; torch
``ddp_comm_hooks/default_hooks.py:35,96,116`` and ``algorithms/join.py``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import DataLoader, pad_batch
from pytorch_distributed_tpu.models import resnet18
from pytorch_distributed_tpu.mesh import init_hybrid_mesh
from pytorch_distributed_tpu.parallel import (
    DataParallel,
    bf16_compress,
    fp16_compress,
    get_comm_hook,
)
from pytorch_distributed_tpu.trainer import Trainer, classification_loss


def _data(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    return x, y


def _assert_no_gradient_sized_all_reduce(stablehlo: str, limit=4096,
                                         require_some=False):
    """Every f32 all_reduce in the program must be small (loss / metric /
    batch-stat pmeans) — a gradient-sized one means the hook's lowering
    regressed to plain all-reduce. stablehlo.all_reduce is a MULTI-LINE
    op (its reduction region sits between the op and its type), so the
    scan needs re.S — a line regex silently matches nothing."""
    regions = re.findall(
        r"stablehlo\.all_reduce.*?\)\s*:\s*\(tensor<([0-9x]*)xf32>\)",
        stablehlo, re.S,
    )
    if require_some:
        # sanity for callers whose program MUST contain small f32 pmeans
        # (loss/metrics): an empty scan would mean the pattern broke
        assert regions, "no f32 all_reduce found at all — pattern broke?"
    for dims in regions:
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        assert n < limit, f"gradient-sized f32 all_reduce: {dims}"


class TestCommHooks:
    def _losses(self, hook, steps=3):
        mesh = ptd.init_device_mesh((8,), ("dp",))
        x, y = _data()
        tr = Trainer(
            resnet18(num_classes=10, cifar_stem=True, bn_axis_name="dp"),
            optax.sgd(0.05, momentum=0.9),
            DataParallel(mesh),
            loss_fn=classification_loss,
            comm_hook=hook,
        )
        s = tr.init(jax.random.key(0), (x, y))
        out = []
        for _ in range(steps):
            s, m = tr.step(s, (x, y))
            out.append(float(m["loss"]))
        return out, tr, s, (x, y)

    def test_allreduce_hook_matches_global_view(self):
        """Manual-DDP (per-shard grads + explicit hook) with the plain
        allreduce hook must reproduce the GSPMD global-view step exactly
        (SyncBN via bn_axis_name inside shard_map)."""
        mesh = ptd.init_device_mesh((8,), ("dp",))
        x, y = _data()
        base_tr = Trainer(
            resnet18(num_classes=10, cifar_stem=True),
            optax.sgd(0.05, momentum=0.9),
            DataParallel(mesh),
            loss_fn=classification_loss,
        )
        s = base_tr.init(jax.random.key(0), (x, y))
        base = []
        for _ in range(3):
            s, m = base_tr.step(s, (x, y))
            base.append(float(m["loss"]))
        hooked, _, _, _ = self._losses("allreduce")
        np.testing.assert_allclose(hooked, base, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("hook", ["bf16_compress", "fp16_compress"])
    def test_compressed_hooks_track_fp32(self, hook):
        full, _, _, _ = self._losses("allreduce")
        comp, _, _, _ = self._losses(hook)
        np.testing.assert_allclose(comp, full, rtol=5e-2, atol=5e-2)
        assert comp != full  # compression really happened

    def test_bf16_on_the_wire(self):
        """The program the hook emits must request bf16 all-reduces — the
        compression exists at the collective, not just in the math.
        Asserted on the lowered StableHLO: the CPU backend then PROMOTES
        small-dtype collectives back to f32 (a backend policy; the TPU
        backend executes them in bf16), so the compiled-HLO dtype is not
        the portable signal."""
        _, tr, s, batch = self._losses("bf16_compress", steps=1)
        bd = tr._place_batch(batch)
        sh = tr._step_fn.lower(s, bd, jax.random.key(0)).as_text()
        regions = re.findall(
            r"stablehlo\.all_reduce.*?\)\s*:\s*\(tensor<[^>]*>\)", sh, re.S
        )
        bf16 = [
            r for r in regions
            if re.search(r":\s*\(tensor<[0-9x]*xbf16>\)", r)
        ]
        assert bf16, "no bf16-operand all_reduce in the hooked program"

    def test_hybrid_mesh_dcn_hook(self):
        """The hook with the real TPU story: bf16-compressed gradient
        all-reduce over the DCN (inter-slice) axis of a hybrid mesh,
        verified numerically vs full precision (torch HSDP inter-node
        all-reduce, _runtime_utils.py:866-877)."""
        mesh = init_hybrid_mesh((4,), (2,), ("dcn", "fsdp"),
                                stub_slices=True)
        rng = np.random.default_rng(1)
        grads = {
            "w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((16,)), jnp.float32),
        }

        def run(hook):
            def per_slice(g):
                return hook(g, "dcn")

            return shard_map(
                per_slice, mesh=mesh.jax_mesh,
                in_specs=(P("dcn"),), out_specs=P("dcn"),
                check_vma=False,
            )({k: jnp.stack([v] * 2) for k, v in grads.items()})

        full = run(get_comm_hook("allreduce"))
        comp = run(bf16_compress)
        for k in grads:
            np.testing.assert_allclose(
                np.asarray(comp[k]), np.asarray(full[k]),
                rtol=1e-2, atol=1e-2,
            )

    def test_reduce_scatter_hook_matches_allreduce(self):
        """The bucketed rs+ag lowering (the overlap-friendly op class —
        VERDICT r4 #1) must reproduce the plain all-reduce mean to float
        tolerance over 3 real train steps."""
        full, _, _, _ = self._losses("allreduce")
        rs, _, _, _ = self._losses("reduce_scatter")
        np.testing.assert_allclose(rs, full, rtol=1e-5, atol=1e-5)

    def test_reduce_scatter_buckets_and_padding(self):
        """Direct hook math across bucket boundaries: a tiny cap forces
        multiple buckets, sizes not divisible by the axis force padding,
        an int leaf takes the pmean path — result == pmean everywhere."""
        from pytorch_distributed_tpu.parallel import make_bucketed_rs_hook

        mesh = ptd.init_device_mesh((8,), ("dp",))
        rng = np.random.default_rng(7)
        grads = {
            "a": jnp.asarray(rng.standard_normal((8, 13, 5)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((8, 3)), jnp.float32),
            "c": jnp.asarray(
                rng.standard_normal((8, 1000)), jnp.bfloat16
            ),
            "n": jnp.tile(jnp.arange(8, dtype=jnp.int32)[:, None], (1, 4)),
        }
        hook = make_bucketed_rs_hook(bucket_cap_mb=1e-4)  # ~100 bytes

        def run(h):
            return shard_map(
                lambda g: h(g, "dp"), mesh=mesh.jax_mesh,
                in_specs=(P("dp"),), out_specs=P("dp"),
                check_vma=False,
            )(grads)

        got = run(hook)
        want = run(get_comm_hook("allreduce"))
        for k in grads:
            np.testing.assert_allclose(
                np.asarray(got[k], np.float32),
                np.asarray(want[k], np.float32),
                rtol=1e-6, atol=1e-6,
            )

    def test_ring_allreduce_hook_matches_allreduce(self):
        """The hand-rolled ppermute ring (the op class the TPU scheduler
        provably asyncifies — perf/dp_overlap_sweep.json) must reproduce
        the all-reduce mean over 3 real train steps (ring summation
        order differs, hence float tolerance)."""
        full, _, _, _ = self._losses("allreduce")
        ring, _, _, _ = self._losses("ring_allreduce")
        np.testing.assert_allclose(ring, full, rtol=1e-4, atol=1e-4)

    def test_ring_allreduce_math_and_buckets(self):
        """Direct ring math vs pmean across bucket boundaries, padding,
        ragged sizes, and the int pmean path — and the lowered program
        must carry the sync as collective_permute hops, with no
        gradient-sized all_reduce."""
        from pytorch_distributed_tpu.parallel import (
            make_ring_allreduce_hook,
        )

        mesh = ptd.init_device_mesh((8,), ("dp",))
        rng = np.random.default_rng(3)
        grads = {
            "a": jnp.asarray(rng.standard_normal((8, 13, 5)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((8, 3)), jnp.float32),
            "c": jnp.asarray(rng.standard_normal((8, 500)), jnp.bfloat16),
            "n": jnp.tile(jnp.arange(8, dtype=jnp.int32)[:, None], (1, 4)),
        }
        hook = make_ring_allreduce_hook(bucket_cap_mb=1e-4)

        def run(h):
            return shard_map(
                lambda g: h(g, "dp"), mesh=mesh.jax_mesh,
                in_specs=(P("dp"),), out_specs=P("dp"),
                check_vma=False,
            )(grads)

        got = run(hook)
        want = run(get_comm_hook("allreduce"))
        for k in grads:
            # the bf16 bucket accumulates its 7 ring hops honestly in
            # bf16, while the CPU backend PROMOTES pmean operands to f32
            # (see test_bf16_on_the_wire) — hence the bf16 tolerance
            tol = (
                dict(rtol=5e-2, atol=1e-1)
                if grads[k].dtype == jnp.bfloat16
                else dict(rtol=1e-5, atol=1e-5)
            )
            np.testing.assert_allclose(
                np.asarray(got[k], np.float32),
                np.asarray(want[k], np.float32), **tol,
            )
        lowered = jax.jit(
            shard_map(
                lambda g: hook(g, "dp"), mesh=mesh.jax_mesh,
                in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
            )
        ).lower(grads).as_text()
        assert "collective_permute" in lowered
        _assert_no_gradient_sized_all_reduce(lowered)

    def test_reduce_scatter_on_the_wire(self):
        """The program must carry the sync as reduce_scatter + all_gather
        (the op class the TPU scheduler overlaps — perf/overlap_aot_
        result.json), not as all_reduce.  Asserted on the lowered
        StableHLO: the CPU backend later expands reduce-scatter, so the
        compiled HLO is not the portable signal (see tpu-env notes)."""
        _, tr, s, batch = self._losses("reduce_scatter", steps=1)
        bd = tr._place_batch(batch)
        sh = tr._step_fn.lower(s, bd, jax.random.key(0)).as_text()
        assert "stablehlo.reduce_scatter" in sh
        assert "stablehlo.all_gather" in sh
        # float gradient buckets ride rs+ag; the remaining all_reduces are
        # loss/metric/batch-stat pmeans, all small
        _assert_no_gradient_sized_all_reduce(sh, require_some=True)

    def test_unknown_hook_rejected(self):
        with pytest.raises(ValueError, match="unknown comm hook"):
            get_comm_hook("gzip")
        from pytorch_distributed_tpu.parallel import FullyShardedDataParallel

        fsdp_mesh = ptd.init_device_mesh((8,), ("fsdp",))
        with pytest.raises(ValueError, match="dp_axis"):
            Trainer(
                resnet18(num_classes=10, cifar_stem=True),
                optax.sgd(0.1),
                FullyShardedDataParallel(fsdp_mesh),
                comm_hook="allreduce",
            )


class TestUnevenInputs:
    def test_pad_batch_shapes_and_mask(self):
        x = np.ones((5, 4), np.float32)
        y = np.arange(5, dtype=np.int32)
        px, py, mask = pad_batch((x, y), 8)
        assert px.shape == (8, 4) and py.shape == (8,)
        np.testing.assert_array_equal(mask, [1, 1, 1, 1, 1, 0, 0, 0])
        with pytest.raises(ValueError):
            pad_batch((x, y), 4)

    def test_masked_loss_equals_unpadded_loss(self):
        """The padded+masked step must produce exactly the loss and grads
        of the true (smaller) batch — padding contributes nothing."""
        mesh = ptd.init_device_mesh((8,), ("dp",))
        x, y = _data(n=8)
        model = resnet18(num_classes=10, cifar_stem=True)
        tr = Trainer(model, optax.sgd(0.05), DataParallel(mesh),
                     loss_fn=classification_loss)
        state = tr.init(jax.random.key(0), (x, y))
        variables = {"params": state.params, **state.model_state}

        # direct loss of the REAL 6 examples (global view, full batch stat
        # caveat: use eval mode so BN stats don't differ with batch size)
        ref, _ = classification_loss(
            model, variables, (x[:6], y[:6]), False, None
        )
        padded = pad_batch((x[:6], y[:6]), 8)
        got, _ = classification_loss(model, variables, padded, False, None)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)

    def test_uneven_dataset_end_to_end(self):
        """Dataset size not divisible by the batch: the final partial
        batch is padded+masked and the run completes with finite,
        decreasing loss (the e2e uneven-inputs contract)."""
        mesh = ptd.init_device_mesh((8,), ("dp",))
        x, y = _data(n=21)  # 21 % 8 != 0
        ds = list(zip(x, y))
        loader = DataLoader(ds, batch_size=8, drop_last=False)
        tr = Trainer(
            resnet18(num_classes=10, cifar_stem=True),
            optax.sgd(0.05, momentum=0.9),
            DataParallel(mesh),
            loss_fn=classification_loss,
        )
        state = tr.init(jax.random.key(0), (x[:8], y[:8]))
        first = last = None
        for epoch in range(2):
            for bx, by in loader:
                batch = pad_batch((bx, by), 8)
                state, m = tr.step(state, batch)
                loss = float(m["loss"])
                assert np.isfinite(loss)
                first = first if first is not None else loss
                last = loss
        assert last < first

class TestModelAveraging:
    """torch model_averaging parity: post-local-SGD periodic averaging
    over the eager group + in-jit EMA."""

    def test_periodic_averager_post_local_sgd(self):
        from tests.test_process_group import run_ranks
        from pytorch_distributed_tpu.parallel import PeriodicModelAverager

        def fn(rank, pg):
            avg = PeriodicModelAverager(pg, period=2, warmup_steps=1)
            params = {"w": np.full(3, float(rank)), "b": np.float32(rank)}
            hist = []
            for _ in range(4):  # steps 1(warm),2,3(avg),4
                params = jax.tree_util.tree_map(np.asarray,
                                                avg.average(params))
                hist.append(params["w"].copy())
            return hist

        outs = run_ranks(4, fn)
        mean_w = np.full(3, np.mean(range(4)))
        for rank, hist in enumerate(outs):
            # step 1 (warmup) and 2 (period offset) keep local params
            np.testing.assert_allclose(hist[0], np.full(3, float(rank)))
            # step 3 averages; step 4 keeps the averaged value
            np.testing.assert_allclose(hist[2], mean_w)
            np.testing.assert_allclose(hist[3], mean_w)

    def test_average_parameters_one_wire_op(self):
        from tests.test_process_group import run_ranks
        from pytorch_distributed_tpu.parallel import average_parameters

        def fn(rank, pg):
            calls = {"n": 0}
            orig = pg.backend.all_reduce

            def counting(arr, op, seq):
                calls["n"] += 1
                return orig(arr, op, seq)

            pg.backend.all_reduce = counting
            params = {
                "a": np.full((2, 2), float(rank), np.float32),
                "b": np.arange(3, dtype=np.float64),
            }
            out = average_parameters(params, pg)
            return calls["n"], out

        for n, out in run_ranks(4, fn):
            assert n == 2  # one coalesced transfer per dtype
            np.testing.assert_allclose(out["a"], np.full((2, 2), 1.5))

    def test_ema_averager(self):
        from pytorch_distributed_tpu.parallel import EMAAverager

        ema = EMAAverager(decay=0.5)
        shadow = ema.init({"w": jnp.ones(2)})
        shadow = ema.update(shadow, {"w": jnp.zeros(2)})
        np.testing.assert_allclose(np.asarray(shadow["w"]), [0.5, 0.5])
        with pytest.raises(ValueError):
            EMAAverager(decay=1.5)


class TestCollectiveEvents:
    """Per-collective trace events (ParamCommsUtils role, SURVEY §5.1)."""

    def test_events_recorded_per_collective(self):
        from tests.test_process_group import run_ranks
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


class TestMaskedGradients:
    def test_padding_contributes_nothing_to_grads(self):
        """The docstring's gradient claim, tested on a BN-free model
        (GPT-2): grads of the padded+masked batch equal grads of the true
        smaller batch exactly."""
        from pytorch_distributed_tpu.models import GPT2, GPT2Config
        from pytorch_distributed_tpu.trainer import lm_loss

        cfg = GPT2Config(vocab_size=32, n_positions=8, n_embd=16,
                         n_layer=1, n_head=2)
        model = GPT2(cfg)
        rng = np.random.default_rng(0)
        tok = rng.integers(0, 32, (6, 8)).astype(np.int32)
        tgt = np.roll(tok, -1, 1).astype(np.int32)
        params = model.init(jax.random.key(0), jnp.asarray(tok))

        def loss_of(batch):
            def f(p):
                loss, _ = lm_loss(model, p, batch, True, None)
                return loss

            return f

        g_true = jax.grad(loss_of((tok, tgt)))(params)
        padded = pad_batch((tok, tgt), 8)
        g_pad = jax.grad(loss_of(padded))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_true),
                        jax.tree_util.tree_leaves(g_pad)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
