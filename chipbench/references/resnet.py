"""Plain reference of ResNet-50 (He et al. 2015, table 1) in the v1.5 form
torchvision ships: bottleneck blocks (1x1, 3x3, 1x1 with 4x expansion), the
stride of a stage's first block on its 3x3, a 7x7/2 stem with 3x3/2 max
pooling, global average pooling and one fully connected layer. Training-mode
BatchNorm (statistics of the batch, eps 1e-5). Straightforward ``jax.numpy``
in float32, NHWC, matmul precision ``highest``; it reads the program's
parameter tree (``conv_init``, ``bn_init``, ``stage<i>_block<j>/{Conv_k,
BatchNorm_k, downsample, downsample_bn}``, ``fc``).

Departure: a stride-2 3x3 convolution pads as XLA's ``SAME`` does (0 before,
1 after) where torchvision pads 1 and 1; the program does the same, and the
output sizes agree. Weights are random (the program's initialiser; the last
BatchNorm scale of each block starts at zero as in torchvision's
``zero_init_residual``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv(x, p, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps=1e-5):
    mu = x.mean((0, 1, 2))
    var = ((x - mu) ** 2).mean((0, 1, 2))
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _pad(k):
    return [(k // 2, k // 2)] * 2


def _block(x, blk, stride: int):
    y = jax.nn.relu(_batch_norm(_conv(x, blk["Conv_0"]), blk["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(
        _conv(y, blk["Conv_1"], stride), blk["BatchNorm_1"]))
    y = _batch_norm(_conv(y, blk["Conv_2"]), blk["BatchNorm_2"])
    if "downsample" in blk:
        x = _batch_norm(_conv(x, blk["downsample"], stride, "VALID"),
                        blk["downsample_bn"])
    return jax.nn.relu(y + x)


def forward(params, images, *, stage_sizes):
    """``images [B, H, W, 3]`` -> logits ``[B, classes]`` in float32. Each
    block is a ``jax.checkpoint``: that changes no value, and lets the
    gradient of a whole batch (BatchNorm needs it whole) fit the chip."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        x = images.astype(jnp.float32)
        x = _conv(x, params["conv_init"], 2, _pad(7))
        x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        block = jax.checkpoint(_block, static_argnums=(2,))
        for i, n_blocks in enumerate(stage_sizes):
            for j in range(n_blocks):
                x = block(x, params[f"stage{i}_block{j}"],
                          2 if i > 0 and j == 0 else 1)
        x = x.mean((1, 2))
        return x @ params["fc"]["kernel"] + params["fc"]["bias"]


def loss(params, images, labels, **sizes):
    logp = jax.nn.log_softmax(forward(params, images, **sizes))
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss_and_grad(params, images, labels, **sizes):
    """``loss`` and its gradient by the parameters."""
    return jax.value_and_grad(loss)(params, images, labels, **sizes)
