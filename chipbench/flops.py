"""Operations the forward and backward passes NEED, from shapes alone.

These are the numerators of every utilization figure; they count what the
algorithm requires (a multiply-add is 2 FLOPs, the backward pass twice the
forward), never what XLA emits and never recomputation.
"""

from __future__ import annotations

from typing import Dict, Sequence


def gpt2_params_matmul(config: Dict) -> int:
    """Parameters that take part in a matrix multiplication, the tied head
    counted once: per block 12 d^2 (qkv 3d^2, proj d^2, MLP 8d^2), plus the
    V x d embedding used as the output head. Positions, biases and norms
    do no multiply-adds worth counting."""
    d, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    return 12 * d * d * L + V * d


def gpt2_train_flops_per_token(config: Dict, seq_len: int) -> float:
    """6 N + 12 L T d (the PaLM / Chinchilla accounting): 2 N for the forward
    matmuls and 4 N for the backward ones, and for attention's two T x T
    products 2 * 2 * T * d a token forward, three times that with the
    backward. Causal masking is NOT subtracted: the program computes the
    full square, and so does the usual definition."""
    d, L = config["n_embd"], config["n_layer"]
    return 6.0 * gpt2_params_matmul(config) + 12.0 * L * seq_len * d


def resnet_forward_macs(stage_sizes: Sequence[int], image_size: int,
                        num_classes: int, num_filters: int = 64) -> int:
    """Multiply-adds of one image's forward pass through a bottleneck
    ResNet (v1.5: the stride sits on the 3x3): every convolution and the
    fully connected layer; BatchNorm, ReLU and pooling are not counted."""
    def conv(hw_out, k, c_in, c_out):
        return hw_out * hw_out * k * k * c_in * c_out

    hw = image_size // 2                      # 7x7 stride 2
    macs = conv(hw, 7, 3, num_filters)
    hw //= 2                                  # 3x3 max pool stride 2
    c_in = num_filters
    for i, n_blocks in enumerate(stage_sizes):
        width = num_filters * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            hw_out = hw // stride
            macs += conv(hw, 1, c_in, width)            # 1x1 at input size
            macs += conv(hw_out, 3, width, width)       # 3x3, strided
            macs += conv(hw_out, 1, width, 4 * width)   # 1x1 expand
            if c_in != 4 * width or stride != 1:
                macs += conv(hw_out, 1, c_in, 4 * width)  # projection
            c_in, hw = 4 * width, hw_out
    return macs + c_in * num_classes


def resnet_train_flops_per_image(config: Dict) -> float:
    """3 x 2 x multiply-adds: 2 FLOPs a multiply-add, and the backward pass
    (gradients to activations and to weights) twice the forward."""
    return 6.0 * resnet_forward_macs(
        config["stage_sizes"], config["image_size"], config["num_classes"],
        config.get("num_filters", 64))
