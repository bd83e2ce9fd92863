"""The ``gpt2`` family: how a configuration file of GPT-2 sizes becomes the
program's model, a training task and a reference check."""

from __future__ import annotations

import math
from typing import Any, Dict

from chipbench import flops
from chipbench.families import TrainTask
from chipbench.references import gpt2 as reference


def build_model(config: Dict[str, Any]):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import GPT2, GPT2Config

    assumed = config["assumed"]
    return GPT2(GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"], layer_norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
    ))


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"n_layer": config["n_layer"], "n_head": config["n_head"],
            "eps": config["layer_norm_epsilon"]}


def train_task(config: Dict[str, Any], traffic: Dict[str, Any]) -> TrainTask:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.trainer import lm_loss

    batch, seq = traffic["batch"], traffic["seq_len"]
    vocab = config["vocab_size"]
    sizes = reference_sizes(config)

    def make_batch(key):
        tokens = jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)
        return tokens, jnp.roll(tokens, -1, axis=1)

    def reference_loss_and_grad(params, one_batch):
        return reference.loss_and_grad(params, *one_batch, **sizes)

    blank = np.zeros((1, seq), np.int32)
    return TrainTask(
        loss_fn=lm_loss, make_batch=make_batch, sample_batch=(blank, blank),
        units_per_step=batch * seq, untrained_loss=math.log(vocab),
        flops_per_unit=flops.gpt2_train_flops_per_token(config, seq),
        reference_loss_and_grad=reference_loss_and_grad,
    )
