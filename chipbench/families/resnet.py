"""The ``resnet`` family: a configuration file of bottleneck-ResNet sizes
becomes the program's model, a training task and a reference check."""

from __future__ import annotations

import math
from typing import Any, Dict

from chipbench import flops
from chipbench.families import TrainTask
from chipbench.references import resnet as reference


def build_model(config: Dict[str, Any]):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.resnet import Bottleneck, ResNet

    assumed = config["assumed"]
    return ResNet(
        stage_sizes=tuple(config["stage_sizes"]), block=Bottleneck,
        num_classes=config["num_classes"], num_filters=config["num_filters"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
    )


def train_task(config: Dict[str, Any], traffic: Dict[str, Any]) -> TrainTask:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.trainer import classification_loss

    batch, px = traffic["batch"], config["image_size"]
    classes = config["num_classes"]
    stage_sizes = tuple(config["stage_sizes"])

    def make_batch(key):
        k_img, k_lab = jax.random.split(key)
        images = jax.random.normal(k_img, (batch, px, px, 3), jnp.float32)
        labels = jax.random.randint(k_lab, (batch,), 0, classes, jnp.int32)
        return images, labels

    def reference_loss_and_grad(params, one_batch):
        return reference.loss_and_grad(params, *one_batch,
                                       stage_sizes=stage_sizes)

    return TrainTask(
        loss_fn=classification_loss, make_batch=make_batch,
        sample_batch=(np.zeros((1, px, px, 3), np.float32),
                      np.zeros((1,), np.int32)),
        units_per_step=batch, untrained_loss=math.log(classes),
        flops_per_unit=flops.resnet_train_flops_per_image(config),
        reference_loss_and_grad=reference_loss_and_grad,
    )
