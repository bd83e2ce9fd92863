"""A dropless mixture of experts: sort the (token, expert) pairs by expert
and run each expert's rows through its own matrices with a grouped matmul.

``parallel/expert.py::MoEMLP`` (training) gives every expert ``capacity``
places and drops what does not fit (``make_dispatch_masks``, an ``[n, E,
capacity]`` mask). Here no token is dropped at any imbalance: the ``n * k``
pairs are sorted by expert (stable: a token's order inside an expert is its
order in the batch), ``jax.lax.ragged_dot`` multiplies each expert's
contiguous group of rows by that expert's matrix (the TPU compiler lowers
it natively: the FLOPs are those of the rows present, 1.41 GFLOP for 192
rows of ``[3584, 1024]``, not E times that; compile result, PERF.md PR 33),
and the results go back to their tokens by the inverse permutation and are
summed under their gates in float32. One expert taking every token is one
group of ``n * k`` rows and 63 empty ones.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["route_sigmoid_topk", "dropless_experts", "held_share"]


def route_sigmoid_topk(x, w_router, bias, k: int, scaling: float
                       ) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3 section 2.1.2 (``noaux_tc``, one group): float32 scores
    ``s = sigmoid(x W_g)``; the k largest of ``s + bias`` choose the
    experts; the gates are ``s_i / (sum of the k + 1e-20) * scaling``, the
    bias steering the choice only. ``x [n, d]`` -> ``(experts [n, k] int32,
    gates [n, k] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    gates = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    return experts.astype(jnp.int32), gates


def dropless_experts(x, experts, gates, w_gate, w_up, w_down
                     ) -> Tuple[jax.Array, jax.Array]:
    """``sum_i gates[:, i] * FFN_{experts[:, i]}(x)`` with ``FFN(x) =
    (silu(x W_gate) * x W_up) W_down``. ``x [n, d]``; ``experts, gates
    [n, k]``; ``w_gate, w_up [E, d, F]``, ``w_down [E, F, d]``. Returns
    ``(y [n, d] in x's dtype, hit)``, ``hit`` the number of experts that
    got at least one token."""
    n, k = experts.shape
    n_experts = w_gate.shape[0]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    rows = x[order // k]                                   # [n * k, d]

    def grouped(a, w):
        return jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32).astype(x.dtype)

    hidden = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    out = grouped(hidden, w_down)
    back = out[jnp.argsort(order)].reshape(n, k, -1)
    y = jnp.einsum("nkd,nk->nd", back.astype(jnp.float32), gates)
    return y.astype(x.dtype), (sizes > 0).sum().astype(jnp.int32)


def held_share(experts, gates, first: int, count: int
               ) -> Tuple[jax.Array, jax.Array]:
    """The part of a routing that a holder of experts ``first .. first +
    count - 1`` computes: ``(experts, gates)`` with a held expert under its
    own number among the held (``0 .. count - 1``) and every other pair
    under ``count`` with gate 0. ``dropless_experts`` over ``count`` experts'
    matrices sorts such pairs behind every group, counts them in no group
    (their rows are multiplied by nothing) and adds them under a zero gate:
    they are another holder's part. A holder of all experts gets back what
    it gave."""
    own = experts - first
    held = (own >= 0) & (own < count)
    return (jnp.where(held, own, count).astype(jnp.int32),
            jnp.where(held, gates, 0.0))
