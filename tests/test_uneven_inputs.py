"""Uneven-input handling: a padded, masked batch is the smaller batch
(torch ``algorithms/join.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import DataLoader, pad_batch
from pytorch_distributed_tpu.models import resnet18
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.trainer import Trainer, classification_loss


def _data(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    return x, y


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
