"""Strategy/spec-derivation tests + DP↔FSDP↔single-device parity.

The reference's parity methodology (SURVEY.md §4: common_fsdp.py runs the
same model sharded vs unsharded and asserts equality) is reproduced here:
identical seeds, identical data → loss trajectories must match across
NoShard / DataParallel / FSDP / ZeRO1 to float tolerance.
"""

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
    TrainState,
    ZeRO1,
    make_state_specs,
)
from pytorch_distributed_tpu.trainer import Trainer, classification_loss


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
    y = (rng.integers(0, 10, n)).astype(np.int32)
    return x, y


def run_steps(strategy, n_steps=5, accum=1, **trainer_kw):
    model = MLP()
    trainer = Trainer(
        model,
        optax.sgd(0.1),
        strategy,
        loss_fn=mlp_loss,
        grad_accum_steps=accum,
        **trainer_kw,
    )
    batch = make_batch()
    state = trainer.init(jax.random.key(0), batch)
    losses = []
    for i in range(n_steps):
        state, m = trainer.step(state, make_batch(seed=i))
        losses.append(float(m["loss"]))
    return losses, state


class TestSpecs:
    def _shapes(self, strategy):
        model = MLP()
        tx = optax.adam(1e-3)

        def init_fn(rng):
            variables = model.init(rng, jnp.ones((1, 8, 8, 1)))
            p = variables["params"]
            return TrainState(
                step=jnp.int32(0), params=p, model_state={},
                opt_state=tx.init(p), scaler=None,
            )

        return jax.eval_shape(init_fn, jax.random.key(0))

    def test_dp_replicates_params(self, mesh8):
        s = DataParallel(mesh8)
        specs = make_state_specs(self._shapes(s), s)
        assert all(
            spec == P() for spec in jax.tree.leaves(
                specs.params, is_leaf=lambda x: isinstance(x, P))
        )

    def test_fsdp_shards_params_and_opt(self):
        mesh = init_device_mesh((8,), ("fsdp",))
        s = FullyShardedDataParallel(mesh, min_shard_size=8)
        specs = make_state_specs(self._shapes(s), s)
        kernel_spec = specs.params["Dense_1"]["kernel"]
        assert kernel_spec == P("fsdp", None) or kernel_spec == P(None, "fsdp")
        # adam mu follows the param sharding
        flat = jax.tree_util.tree_flatten_with_path(
            specs.opt_state, is_leaf=lambda x: isinstance(x, P))[0]
        mu_specs = [s for path, s in flat if "mu" in str(path) and "Dense_1" in str(path) and "kernel" in str(path)]
        assert mu_specs and mu_specs[0] == kernel_spec
        # scalar count leaf replicated
        count_specs = [s for path, s in flat if "count" in str(path)]
        assert all(c == P() for c in count_specs)

    def test_zero1_shards_only_opt(self, mesh8):
        s = ZeRO1(mesh8, min_shard_size=8)
        specs = make_state_specs(self._shapes(s), s)
        assert all(
            spec == P() for spec in jax.tree.leaves(
                specs.params, is_leaf=lambda x: isinstance(x, P))
        )
        flat = jax.tree_util.tree_flatten_with_path(
            specs.opt_state, is_leaf=lambda x: isinstance(x, P))[0]
        mu_specs = [s for path, s in flat if "mu" in str(path) and "kernel" in str(path)]
        assert any("dp" in str(s) for s in mu_specs)

    def test_small_params_replicated(self):
        mesh = init_device_mesh((8,), ("fsdp",))
        s = FullyShardedDataParallel(mesh, min_shard_size=10_000_000)
        specs = make_state_specs(self._shapes(s), s)
        assert all(
            spec == P() for spec in jax.tree.leaves(
                specs.params, is_leaf=lambda x: isinstance(x, P))
        )


class TestParity:
    """Same seed + data → same loss trajectory across strategies."""

    def test_dp_matches_single(self, mesh8):
        ref, _ = run_steps(NoShard(init_device_mesh((8,), ("dp",))))
        dp, _ = run_steps(DataParallel(mesh8))
        np.testing.assert_allclose(ref, dp, rtol=1e-5)

    def test_fsdp_matches_dp(self, mesh8):
        mesh_f = init_device_mesh((8,), ("fsdp",))
        dp, _ = run_steps(DataParallel(mesh8))
        fsdp, _ = run_steps(
            FullyShardedDataParallel(mesh_f, min_shard_size=8))
        np.testing.assert_allclose(dp, fsdp, rtol=1e-4)

    def test_zero1_matches_dp(self, mesh8):
        dp, _ = run_steps(DataParallel(mesh8))
        z1, _ = run_steps(ZeRO1(mesh8, min_shard_size=8))
        np.testing.assert_allclose(dp, z1, rtol=1e-4)

    def test_grad_accum_matches_full_batch(self, mesh8):
        full, _ = run_steps(DataParallel(mesh8), accum=1)
        accum, _ = run_steps(DataParallel(mesh8), accum=4)
        np.testing.assert_allclose(full, accum, rtol=1e-4)

    def test_hsdp_matches_single(self):
        from pytorch_distributed_tpu.parallel import HybridShard

        mesh = init_device_mesh((2, 4), ("dcn", "fsdp"))
        s = HybridShard(mesh, min_shard_size=8)
        assert s.batch_axes == ("dcn", "fsdp")
        assert s.data_shard_count == 8
        hsdp, state = run_steps(s)
        ref, _ = run_steps(NoShard(init_device_mesh((8,), ("x",))))
        np.testing.assert_allclose(ref, hsdp, rtol=1e-4)
        # params sharded over fsdp only: 4-way shards, replicated over dcn
        kernel = state.params["Dense_1"]["kernel"]
        shard_shapes = {sh.data.shape for sh in kernel.addressable_shards}
        assert shard_shapes in ({(16, 64)}, {(64, 16)})

    def test_2d_fsdp_dp(self):
        mesh = init_device_mesh((2, 4), ("dp", "fsdp"))
        s = FullyShardedDataParallel(mesh, dp_axis="dp", min_shard_size=8)
        assert s.data_shard_count == 8
        losses, _ = run_steps(s)
        ref, _ = run_steps(NoShard(init_device_mesh((8,), ("x",))))
        np.testing.assert_allclose(ref, losses, rtol=1e-4)

    def test_loss_decreases_resnet(self, mesh8):
        from pytorch_distributed_tpu.models import resnet18

        model = resnet18(num_classes=10, cifar_stem=True)
        trainer = Trainer(
            model, optax.sgd(0.05, momentum=0.9), DataParallel(mesh8),
            loss_fn=classification_loss,
        )
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 10, 16).astype(np.int32)
        state = trainer.init(jax.random.key(0), (x, y))
        losses = []
        for _ in range(8):
            state, m = trainer.step(state, (x, y))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 8
        # params actually sharded as annotated (replicated under DP)
        leaf = jax.tree.leaves(state.params)[0]
        assert len(leaf.sharding.device_set) == 8


class TestClipAndSharding:
    def test_clip_norm(self, mesh8):
        model = MLP()
        batch = make_batch()

        def run(clip):
            trainer = Trainer(
                model, optax.sgd(0.1), DataParallel(mesh8),
                loss_fn=mlp_loss, clip_norm=clip,
            )
            state = trainer.init(jax.random.key(0), batch)
            p0 = jax.tree.map(np.asarray, state.params)
            state, m = trainer.step(state, batch)
            p1 = jax.tree.map(np.asarray, state.params)
            delta = sum(
                float(np.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))
            )
            return delta, float(m["grad_norm"])

        d_tiny, gnorm = run(1e-8)
        d_none, _ = run(None)
        assert gnorm > 0.1  # grads are real
        assert d_tiny < 1e-6  # clipped to ~zero step
        assert d_none > 1e-3  # unclipped step moves params

    def test_fsdp_param_arrays_are_sharded(self):
        mesh = init_device_mesh((8,), ("fsdp",))
        _, state = run_steps(
            FullyShardedDataParallel(mesh, min_shard_size=8))
        kernel = state.params["Dense_1"]["kernel"]
        shard_shapes = {s.data.shape for s in kernel.addressable_shards}
        assert shard_shapes == {(8, 64)} or shard_shapes == {(64, 8)}


# -- the activation pin (PERF.md, PR 29) --------------------------------------
# A strategy that shards parameters over an axis the batch is sharded over
# too holds the activations to the batch layout, so the partitioner gathers
# the parameter at each use. The sharded step must stay the one-device step.

def _gpt2_steps(strategy, loss_fn, n_steps=4):
    from pytorch_distributed_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=257, n_positions=32, n_embd=64, n_layer=2,
                     n_head=4)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    batch = (tokens, np.roll(tokens, -1, axis=1))
    # SGD, not Adam: an update linear in the gradient keeps the parameters
    # comparable to float32 tolerance (Adam turns the rounding of a
    # near-zero gradient into a whole step of the learning rate)
    trainer = Trainer(GPT2(cfg), optax.sgd(0.1, momentum=0.9), strategy,
                      loss_fn=loss_fn)
    state = trainer.init(jax.random.key(0), batch)
    losses = []
    for _ in range(n_steps):
        state, m = trainer.step(state, batch)
        losses.append(float(m["loss"]))
    return np.asarray(losses), jax.device_get(state.params), trainer


def _pinned_strategy(name):
    from pytorch_distributed_tpu.parallel import HybridShard

    if name == "fsdp_1x4":
        mesh = init_device_mesh((1, 4), ("dp", "fsdp"),
                                devices=jax.devices()[:4])
        return FullyShardedDataParallel(mesh, min_shard_size=8)
    mesh = init_device_mesh((2, 4), ("dcn", "fsdp"))
    return HybridShard(mesh, min_shard_size=8)


class TestActivationPin:
    @pytest.mark.parametrize("loss", ["lm_loss", "chunked"])
    @pytest.mark.parametrize("name", ["fsdp_1x4", "hybrid_2x4"])
    def test_pinned_step_matches_one_device(self, name, loss):
        from pytorch_distributed_tpu.trainer import (
            lm_loss, make_chunked_lm_loss,
        )

        loss_fn = lm_loss if loss == "lm_loss" else make_chunked_lm_loss(4)
        one = NoShard(init_device_mesh((1,), ("x",),
                                       devices=jax.devices()[:1]))
        ref_losses, ref_params, _ = _gpt2_steps(one, loss_fn)
        strategy = _pinned_strategy(name)
        losses, params, trainer = _gpt2_steps(strategy, loss_fn)
        # the pin is in place: the strategy states the batch layout ...
        specs = jax.tree.map(lambda s: s.spec, trainer.state_shardings.params)
        assert strategy.activation_pin(specs) == strategy.batch_pspec()
        # ... and the step is still the one-device step
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
        assert losses[-1] < losses[0]
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            params, ref_params,
        )

    @pytest.mark.parametrize("name,axes,shape,pinned", [
        ("fsdp", ("dp", "fsdp"), (1, 4), True),
        ("fsdp", ("dp", "fsdp"), (4, 1), False),   # the shared axis is 1 wide
        ("fsdp", ("dp", "fsdp"), (1, 1), False),
        ("dp", ("dp",), (4,), False),              # replicated parameters
        ("zero1", ("dp",), (4,), False),
    ])
    def test_pin_is_read_off_mesh_and_specs(self, name, axes, shape, pinned):
        n = int(np.prod(shape))
        mesh = init_device_mesh(shape, axes, devices=jax.devices()[:n])
        strategy = {
            "fsdp": lambda: FullyShardedDataParallel(mesh, min_shard_size=8),
            "dp": lambda: DataParallel(mesh),
            "zero1": lambda: ZeRO1(mesh, min_shard_size=8),
        }[name]()
        specs = {"w": strategy.param_pspec("w", (64, 256)),
                 "b": strategy.param_pspec("b", (3,))}
        want = strategy.batch_pspec() if pinned else None
        assert strategy.activation_pin(specs) == want

    def test_pin_activation_outside_a_trainer_is_the_identity(self):
        from jax.sharding import NamedSharding

        from pytorch_distributed_tpu.mesh import (
            activation_layout, pin_activation,
        )

        x = jnp.ones((8, 4))
        assert pin_activation(x) is x
        mesh = init_device_mesh((4,), ("fsdp",), devices=jax.devices()[:4])
        layout = NamedSharding(mesh.jax_mesh, P("fsdp"))
        with activation_layout(layout):
            assert pin_activation(x).sharding.spec == P("fsdp")
            # a leading dimension the axis does not divide is left alone
            odd = jnp.ones((6, 4))
            assert pin_activation(odd) is odd
            with activation_layout(None):
                assert pin_activation(x) is x
        assert pin_activation(x) is x
