"""One module per model family: ``build_model(config)`` gives the program's
own model at the sizes of a configuration file, ``train_task(config,
traffic)`` what a training cell needs beside it. A new family is a new file
here and its plain reference under ``references/``."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple


@dataclasses.dataclass(frozen=True)
class TrainTask:
    loss_fn: Callable                  # the program's loss for this family
    make_batch: Callable               # key -> one global batch, traceable
    sample_batch: Tuple[Any, ...]      # host arrays, one example: init shapes
    units_per_step: int                # tokens or images in a global batch
    untrained_loss: float              # ln(classes): loss of random weights
    flops_per_unit: float              # forward + backward, from shapes
    reference_loss_and_grad: Callable  # (params, batch) -> float32 (loss, grads)
