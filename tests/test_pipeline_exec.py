"""Pipelined step executor (pipeline_exec.AsyncRunner) — tier-1 CPU.

The load-bearing guarantees:

  * **bit-exact parity** — the runner's per-step losses and final state
    are IDENTICAL (not close: equal float32 bits) to sequential
    ``Trainer.step`` calls on the same batches; the pipeline reorders
    host work, never device math.
  * **donation safety** — the runner never re-reads a donated input:
    after each submit the prior state/ring is unreachable from the
    runner (on TPU a retained reference would be a deleted buffer).
  * **drain windows** — the on-device metric ring drains every
    ``drain_every`` steps plus a tail remainder at finish(); every step's
    metric lands exactly once at its index.
"""

import gc
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.pipeline_exec import (
    AsyncRunner,
    MetricHistory,
    MetricRing,
)
from pytorch_distributed_tpu.trainer import Trainer


class MLP(nn.Module):
    width: int = 32
    n_out: int = 10

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.width)(x)
        x = nn.relu(x)
        return nn.Dense(self.n_out)(x)


def mlp_loss(model, variables, batch, train, rngs=None):
    x, y = batch
    logits = model.apply(variables, x, train=train)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y
    ).mean()
    return loss, ({}, {"acc": (logits.argmax(-1) == y).mean()})


def make_batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    return x, y


def make_trainer(mesh8, **kw):
    return Trainer(
        MLP(), optax.sgd(0.1), DataParallel(mesh8), loss_fn=mlp_loss, **kw
    )


class TestMetricRing:
    def test_push_wraps(self):
        ring = MetricRing.create(["loss"], 3)
        for i in range(5):
            ring = ring.push({"loss": jnp.float32(i)})
        # slots after 5 pushes into size 3: [3, 4, 2]
        np.testing.assert_array_equal(
            np.asarray(ring.buf["loss"]), [3.0, 4.0, 2.0]
        )
        assert int(ring.idx) == 5

    def test_stacked_row_order_is_sorted_names(self):
        ring = MetricRing.create(["loss", "acc"], 2)
        ring = ring.push({"loss": jnp.float32(7), "acc": jnp.float32(1)})
        snap = np.asarray(ring.stacked())
        assert snap.shape == (2, 2)
        assert snap[0, 0] == 1.0  # acc sorts first
        assert snap[1, 0] == 7.0

    def test_create_validates(self):
        with pytest.raises(ValueError):
            MetricRing.create(["loss"], 0)
        with pytest.raises(ValueError):
            MetricRing.create([], 4)


class TestParity:
    """The oracle: pipelined == sequential, bit for bit."""

    N_STEPS = 11

    def _sequential(self, mesh8):
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        losses, accs = [], []
        for i in range(self.N_STEPS):
            state, m = trainer.step(state, make_batch(seed=i))
            losses.append(np.float32(m["loss"]))
            accs.append(np.float32(m["acc"]))
        return np.array(losses), np.array(accs), state

    def _pipelined(self, mesh8, depth, drain_every):
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=depth, drain_every=drain_every)
        runner.start(state, make_batch())
        for i in range(self.N_STEPS):
            runner.submit(make_batch(seed=i))
        return runner.finish()

    @pytest.mark.parametrize("depth,drain_every", [(1, 4), (3, 4), (2, 16)])
    def test_bit_exact_losses_and_state(self, mesh8, depth, drain_every):
        losses, accs, seq_state = self._sequential(mesh8)
        state, hist = self._pipelined(mesh8, depth, drain_every)
        assert hist.n_steps == self.N_STEPS
        # equal, not allclose: same program order, same math
        np.testing.assert_array_equal(hist["loss"], losses)
        np.testing.assert_array_equal(hist["acc"], accs)
        seq_leaves = jax.tree_util.tree_leaves(seq_state)
        run_leaves = jax.tree_util.tree_leaves(state)
        assert len(seq_leaves) == len(run_leaves)
        for a, b in zip(seq_leaves, run_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_trainer_run_facade(self, mesh8):
        losses, _, _ = self._sequential(mesh8)
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        batches = [make_batch(seed=i) for i in range(self.N_STEPS)]
        state, hist = trainer.run(state, batches, depth=2, drain_every=4)
        np.testing.assert_array_equal(hist["loss"], losses)
        assert hist.first("loss") == losses[0]
        assert hist.last("loss") == losses[-1]

    def test_empty_stream(self, mesh8):
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        out_state, hist = trainer.run(state, [])
        assert out_state is state
        assert hist.n_steps == 0

    def test_prefetch_composes(self, mesh8):
        from pytorch_distributed_tpu.data.loader import prefetch_to_mesh

        losses, _, _ = self._sequential(mesh8)
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        placed = prefetch_to_mesh(
            (make_batch(seed=i) for i in range(self.N_STEPS)),
            mesh8, ("dp",), depth=3,
        )
        state, hist = trainer.run(state, placed, depth=2, drain_every=4)
        np.testing.assert_array_equal(hist["loss"], losses)


class TestDrainWindows:
    def test_multiple_drains_plus_tail(self, mesh8):
        # 11 steps, drain_every=4: two full async drains + 3-step tail
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=2, drain_every=4)
        runner.start(state, make_batch())
        for i in range(11):
            runner.submit(make_batch(seed=i))
        assert len(runner._drains) == 2
        _, hist = runner.finish()
        assert hist.n_steps == 11
        assert np.isfinite(hist["loss"]).all()
        # every step distinct data -> the series is not a repeated window
        assert len({float(v) for v in hist["loss"]}) > 4

    def test_restart_reuses_compiled_step(self, mesh8):
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=2, drain_every=4)
        state, h1 = runner.run(state, [make_batch(seed=i) for i in range(3)])
        pstep = runner._pstep
        assert pstep is not None
        state, h2 = runner.run(state, [make_batch(seed=i) for i in range(3, 6)])
        assert runner._pstep is pstep  # no re-jit across start() calls
        assert h1.n_steps == h2.n_steps == 3


class TestDonationSafety:
    def test_prior_state_unreachable_after_submit(self, mesh8):
        """pstep donates (state, ring); on TPU their buffers are gone the
        moment the call returns. The runner must therefore drop every
        reference to the donated inputs — holding one would be a read
        of a deleted buffer waiting to happen."""
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=3, drain_every=4)
        runner.start(state, make_batch())
        runner.submit(make_batch(seed=0))
        prev_state = runner._state
        prev_ring = runner._ring
        runner.submit(make_batch(seed=1))
        assert runner._state is not prev_state
        assert runner._ring is not prev_ring
        refs = [
            weakref.ref(leaf)
            for leaf in jax.tree_util.tree_leaves(prev_state)
        ] + [weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(prev_ring)]
        del prev_state, prev_ring, state
        gc.collect()
        assert all(r() is None for r in refs), (
            "runner retained a reference to a donated input"
        )

    def test_simulated_donation_completes(self, mesh8):
        """Delete the prior state's buffers right after the next submit
        (what donation does on TPU) — the pipeline must still run to
        completion and produce the exact sequential result, proving no
        code path re-reads a donated input."""
        trainer = make_trainer(mesh8)
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=3, drain_every=4)
        runner.start(state, make_batch())
        for i in range(6):
            prev = runner._state
            runner.submit(make_batch(seed=i))
            if runner._state is not prev:
                for leaf in jax.tree_util.tree_leaves(prev):
                    leaf.delete()
        state, hist = runner.finish()
        assert hist.n_steps == 6
        assert np.isfinite(hist["loss"]).all()
        assert all(
            not leaf.is_deleted()
            for leaf in jax.tree_util.tree_leaves(state)
        )


class TestValidation:
    def test_depth_and_drain_validate(self, mesh8):
        trainer = make_trainer(mesh8)
        with pytest.raises(ValueError, match="depth"):
            AsyncRunner(trainer, depth=0)
        with pytest.raises(ValueError, match="drain_every"):
            AsyncRunner(trainer, drain_every=0)

    def test_submit_before_start_raises(self, mesh8):
        runner = AsyncRunner(make_trainer(mesh8))
        with pytest.raises(RuntimeError, match="start"):
            runner.submit(make_batch())
        with pytest.raises(RuntimeError, match="start"):
            runner.finish()

    def test_non_scalar_metric_rejected(self, mesh8):
        def vec_loss(model, variables, batch, train, rngs=None):
            x, y = batch
            logits = model.apply(variables, x, train=train)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            )
            return per.mean(), ({}, {"per_example": per})

        trainer = Trainer(
            MLP(), optax.sgd(0.1), DataParallel(mesh8), loss_fn=vec_loss,
        )
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer)
        with pytest.raises(ValueError, match="scalar"):
            runner.start(state, make_batch())


class TestMetricHistory:
    def test_accessors(self):
        h = MetricHistory({"loss": np.array([3.0, 2.0, 1.0], np.float32)})
        assert "loss" in h and "acc" not in h
        assert list(h.keys()) == ["loss"]
        assert h.n_steps == 3
        assert h.first() == 3.0
        assert h.last() == 1.0
        np.testing.assert_array_equal(h["loss"], [3.0, 2.0, 1.0])
