"""ZeRO sharded weight update + compiler-scheduled FSDP — tier-1 `zero`.

The oracle (ISSUE 18): the sharded update must be a pure *layout* change.
Same seeds, same data → the loss trace and final params are IDENTICAL
(float32 bit-equality, not allclose) to the unsharded update, including
through the AMP GradScaler and through the pipelined executor's donation
chain. The memory win is asserted separately (``TestMemoryPerChip``).

Fast subset runs tier-1; the full strategy × AMP × clip grid is `slow`.

Known 1-ulp caveat, pinned here so it can't silently widen: global-norm
*clipping* makes the step nonlinear in reduction order, and XLA fuses the
norm differently across layouts — with ``clip_norm`` set, even the
pre-existing DP↔FSDP pair differs by ~1 ulp on the CPU backend. The grid
therefore asserts bit-equality everywhere except the clip rows, which get
a 1e-6 band. NoShard keeps its replicated batch (different reduction
order by construction) and is compared at the rtol the pre-existing
parity tests use.
"""

import gc
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_tpu.mesh import init_device_mesh
from pytorch_distributed_tpu.parallel import (
    DataParallel,
    FullyShardedDataParallel,
    NoShard,
    ZeRO1,
    shard_spec_with_reason,
)
from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
from pytorch_distributed_tpu.trainer import Trainer

pytestmark = pytest.mark.zero


class MLP(nn.Module):
    width: int = 64
    n_out: int = 10

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.width)(x)
        x = nn.relu(x)
        x = nn.Dense(self.width)(x)
        x = nn.relu(x)
        return nn.Dense(self.n_out)(x)


def mlp_loss(model, variables, batch, train, rngs=None):
    x, y = batch
    logits = model.apply(variables, x, train=train)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y
    ).mean()
    return loss, ({}, {})


def make_batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    return x, y


def run_trace(strategy, steps=5, policy="fp32", clip=None, optimizer=None,
              scaler_kw=None):
    """(losses, grad_norms, final params as numpy, final state)."""
    tx = optimizer or optax.sgd(0.1, momentum=0.9)
    kw = {}
    if scaler_kw:
        from pytorch_distributed_tpu.amp import GradScaler

        kw["scaler"] = GradScaler(**scaler_kw)
    trainer = Trainer(
        MLP(), tx, strategy, loss_fn=mlp_loss, policy=policy,
        clip_norm=clip, **kw,
    )
    state = trainer.init(jax.random.key(0), make_batch())
    losses, norms = [], []
    for i in range(steps):
        state, m = trainer.step(state, make_batch(seed=i))
        losses.append(np.float32(m["loss"]))
        norms.append(np.float32(m["grad_norm"]))
    params = jax.tree.map(np.asarray, state.params)
    return np.array(losses), np.array(norms), params, state


def assert_params_equal(pa, pb, **tol):
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(pa),
        jax.tree_util.tree_leaves_with_path(pb),
    ):
        assert ka == kb
        if tol:
            np.testing.assert_allclose(a, b, err_msg=str(ka), **tol)
        else:
            np.testing.assert_array_equal(a, b, err_msg=str(ka))


# -- _shard_largest_divisible_dim edge cases (satellite 2) ------------------

class TestShardSpecReasons:
    """Every replication fallback is explicit and named — no silent
    replication."""

    def test_scalar(self):
        assert shard_spec_with_reason((), "dp", 8, 0) == (P(), "scalar")

    def test_trivial_axis(self):
        # dp=1: sharding is a no-op — replicate rather than annotate
        assert shard_spec_with_reason((64, 64), "dp", 1, 0) == (
            P(), "trivial_axis")

    def test_small(self):
        assert shard_spec_with_reason((8, 8), "dp", 8, 1024) == (
            P(), "small")

    def test_indivisible(self):
        assert shard_spec_with_reason((7, 9), "dp", 8, 0) == (
            P(), "indivisible")

    def test_zero_dim_never_sharded(self):
        # 0 % 8 == 0 but an 8-way shard of nothing is meaningless
        assert shard_spec_with_reason((0, 3), "dp", 8, 0) == (
            P(), "indivisible")

    def test_sharded_largest_dim(self):
        spec, reason = shard_spec_with_reason((16, 64), "dp", 8, 0)
        assert (spec, reason) == (P(None, "dp"), "sharded")

    def test_tie_breaks_to_first_dim(self):
        # deterministic choice → deterministic jit cache key
        spec, reason = shard_spec_with_reason((64, 64), "dp", 8, 0)
        assert (spec, reason) == (P("dp", None), "sharded")

    def test_small_wins_over_indivisible(self):
        # the min-size wrap policy is checked before divisibility
        assert shard_spec_with_reason((7,), "dp", 8, 1024) == (P(), "small")


# -- bit-exact parity: fast tier-1 subset -----------------------------------

class TestBitExactFast:
    def test_zero_update_matches_dp_fp32(self, mesh8):
        dp = run_trace(DataParallel(mesh8))
        z = run_trace(ZeRO1(mesh8, min_shard_size=8))
        np.testing.assert_array_equal(dp[0], z[0])  # loss trace
        np.testing.assert_array_equal(dp[1], z[1])  # grad_norm trace
        assert_params_equal(dp[2], z[2])

    def test_zero_update_matches_dp_fp16_scaler(self, mesh8):
        dp = run_trace(DataParallel(mesh8), policy="fp16")
        z = run_trace(ZeRO1(mesh8, min_shard_size=8), policy="fp16")
        np.testing.assert_array_equal(dp[0], z[0])
        assert_params_equal(dp[2], z[2])

    def test_opt_state_arrays_actually_sharded(self, mesh8):
        """The parity above must not come from XLA silently replicating:
        the momentum buffers live as 1/8 shards on device."""
        _, _, _, state = run_trace(ZeRO1(mesh8, min_shard_size=8))
        flat = jax.tree_util.tree_leaves_with_path(state.opt_state)
        mu = [v for path, v in flat
              if "kernel" in str(path) and hasattr(v, "addressable_shards")]
        assert mu, "no momentum leaves found"
        kernel_mu = [v for v in mu if v.ndim == 2 and v.shape == (64, 64)]
        assert kernel_mu
        shapes = {s.data.shape for s in kernel_mu[0].addressable_shards}
        assert shapes in ({(8, 64)}, {(64, 8)})
        # params stay replicated (ZeRO-1, not FSDP)
        leaf = jax.tree.leaves(state.params)[0]
        assert len(leaf.sharding.device_set) == 8
        assert leaf.sharding.is_fully_replicated

    def test_sharded_update_flag_defaults(self, mesh8):
        mesh_f = init_device_mesh((8,), ("fsdp",))
        assert ZeRO1(mesh8).sharded_update is True
        assert FullyShardedDataParallel(mesh_f).sharded_update is True
        assert DataParallel(mesh8).sharded_update is False
        assert NoShard(mesh8).sharded_update is False


# -- full strategy × AMP × clip grid (slow) ----------------------------------

def _grid_strategies(mesh8):
    mesh_f = init_device_mesh((8,), ("fsdp",))
    return {
        "zero1_update": ZeRO1(mesh8, min_shard_size=8),
        "fsdp": FullyShardedDataParallel(mesh_f, min_shard_size=8),
    }


@pytest.mark.slow
class TestStrategyGridSlow:
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    @pytest.mark.parametrize("clip", [None, 1.0])
    @pytest.mark.parametrize("name", ["zero1_update", "fsdp"])
    def test_grid_vs_dp(self, mesh8, name, policy, clip):
        strat = _grid_strategies(mesh8)[name]
        dp = run_trace(DataParallel(mesh8), policy=policy, clip=clip)
        other = run_trace(strat, policy=policy, clip=clip)
        if clip is None:
            np.testing.assert_array_equal(dp[0], other[0])
            assert_params_equal(dp[2], other[2])
        else:
            # clip makes the step nonlinear in the norm's reduction
            # order; even DP↔FSDP differs by ~1 ulp here (module docstring)
            np.testing.assert_allclose(dp[0], other[0], rtol=2e-6)
            assert_params_equal(dp[2], other[2], rtol=2e-6, atol=1e-7)

    def test_noshard_reference(self, mesh8):
        # replicated batch → different grad reduction order by
        # construction: rtol-level only, same as tests/test_parallel.py
        ns = run_trace(NoShard(init_device_mesh((8,), ("x",))))
        z = run_trace(ZeRO1(mesh8, min_shard_size=8))
        np.testing.assert_allclose(ns[0], z[0], rtol=1e-5)

    def test_adamw_weight_decay_bit_exact(self, mesh8):
        # decoupled weight decay reads params inside the sharded step
        tx = optax.adamw(1e-3, weight_decay=0.1)
        dp = run_trace(DataParallel(mesh8), optimizer=tx)
        z = run_trace(ZeRO1(mesh8, min_shard_size=8), optimizer=tx)
        np.testing.assert_array_equal(dp[0], z[0])
        assert_params_equal(dp[2], z[2])

    def test_skip_on_inf_parity(self, mesh8):
        # force a backoff: tiny growth_interval + huge init scale overflows
        # fp16 grads on step 0, so the skip/backoff path runs sharded too
        kw = dict(init_scale=2.0**24, growth_interval=2)
        dp = run_trace(DataParallel(mesh8), policy="fp16", scaler_kw=kw)
        z = run_trace(
            ZeRO1(mesh8, min_shard_size=8), policy="fp16", scaler_kw=kw)
        np.testing.assert_array_equal(dp[0], z[0])
        assert_params_equal(dp[2], z[2])


# -- donation safety through the pipelined executor (satellite 3) ------------

class TestShardedDonationSafety:
    def test_donated_sharded_buffers_unreachable(self, mesh8):
        """The runner donates (state, ring); with ZeRO1 the opt-state
        leaves are 1/8 shards — a retained reference to one is a read of
        a deleted buffer on TPU exactly as for replicated state."""
        trainer = Trainer(
            MLP(), optax.sgd(0.1, momentum=0.9),
            ZeRO1(mesh8, min_shard_size=8), loss_fn=mlp_loss,
        )
        state = trainer.init(jax.random.key(0), make_batch())
        runner = AsyncRunner(trainer, depth=3, drain_every=4)
        assert runner.sharded_update is True
        assert runner.programs_per_step == 1.0
        runner.start(state, make_batch())
        runner.submit(make_batch(seed=0))
        prev_state = runner._state
        runner.submit(make_batch(seed=1))
        assert runner._state is not prev_state
        refs = [
            weakref.ref(leaf)
            for leaf in jax.tree_util.tree_leaves(prev_state)
        ]
        n_opt_leaves = len(jax.tree_util.tree_leaves(prev_state.opt_state))
        assert n_opt_leaves > 0
        del prev_state, state
        gc.collect()
        assert all(r() is None for r in refs), (
            "runner retained a reference to a donated (sharded) input"
        )

    def test_runner_parity_bit_exact_zero1(self, mesh8):
        """Pipelined ZeRO1 == sequential ZeRO1, float-bit equality —
        the sharded update composes with the donation chain untouched."""
        def seq():
            trainer = Trainer(
                MLP(), optax.sgd(0.1, momentum=0.9),
                ZeRO1(mesh8, min_shard_size=8), loss_fn=mlp_loss,
            )
            state = trainer.init(jax.random.key(0), make_batch())
            losses = []
            for i in range(6):
                state, m = trainer.step(state, make_batch(seed=i))
                losses.append(np.float32(m["loss"]))
            return np.array(losses), jax.tree.map(np.asarray, state.params)

        def piped():
            trainer = Trainer(
                MLP(), optax.sgd(0.1, momentum=0.9),
                ZeRO1(mesh8, min_shard_size=8), loss_fn=mlp_loss,
            )
            state = trainer.init(jax.random.key(0), make_batch())
            runner = AsyncRunner(trainer, depth=3, drain_every=4)
            runner.start(state, make_batch())
            for i in range(6):
                runner.submit(make_batch(seed=i))
            state, hist = runner.finish()
            return (hist["loss"].astype(np.float32),
                    jax.tree.map(np.asarray, state.params))

        sl, sp = seq()
        pl, pp = piped()
        np.testing.assert_array_equal(sl, pl)
        assert_params_equal(sp, pp)


# -- the memory win: bytes on a chip, from the shardings Trainer.init pins ----

def _bytes_per_chip(strategy):
    """``{params, opt}`` bytes one chip holds of ResNet-18's state under
    ``strategy``: the shard shapes of the layout ``Trainer.init`` derives
    (shapes only, nothing is placed)."""
    from pytorch_distributed_tpu.models import resnet18
    from pytorch_distributed_tpu.trainer import classification_loss

    trainer = Trainer(resnet18(num_classes=10, cifar_stem=True),
                      optax.sgd(0.1, momentum=0.9), strategy,
                      loss_fn=classification_loss)
    sample = (np.zeros((1, 32, 32, 3), np.float32), np.zeros((1,), np.int32))
    shapes = jax.eval_shape(lambda k: trainer.init(k, sample),
                            jax.random.key(0))

    def held(part):
        return sum(
            int(np.prod(s.shard_shape(a.shape))) * a.dtype.itemsize
            for a, s in zip(jax.tree.leaves(getattr(shapes, part)),
                            jax.tree.leaves(getattr(trainer.state_shardings,
                                                    part))))

    return {"params": held("params"), "opt": held("opt_state")}


class TestMemoryPerChip:
    def test_resnet_opt_state_is_one_over_dp(self, mesh8):
        """Optimizer-state bytes a chip on the ResNet path at ~1/dp of
        DataParallel's (within rounding from min_shard_size replication of
        tiny BN params); FSDP also shards the resident params."""
        dp = _bytes_per_chip(DataParallel(mesh8))
        zero = _bytes_per_chip(ZeRO1(mesh8))
        fsdp = _bytes_per_chip(FullyShardedDataParallel(
            init_device_mesh((8,), ("fsdp",))))
        assert dp == _bytes_per_chip(NoShard(mesh8))
        assert 1 / 8 <= zero["opt"] / dp["opt"] <= 1.25 / 8, (zero, dp)
        assert zero["params"] == dp["params"]
        assert fsdp["params"] < dp["params"] / 6
