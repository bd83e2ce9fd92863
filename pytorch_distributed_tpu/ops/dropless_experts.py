"""A dropless mixture of experts: sort the (token, expert) pairs by expert
and run each expert's rows through its own matrices with a grouped matmul.

``parallel/expert.py::MoEMLP`` (training) gives every expert ``capacity``
places and drops what does not fit (``make_dispatch_masks``, an ``[n, E,
capacity]`` mask). Here no token is dropped at any imbalance: the ``n * k``
pairs are sorted by expert (stable: a token's order inside an expert is its
order in the batch), a grouped product multiplies each expert's contiguous
group of rows by that expert's matrix (``ops.grouped_matmul``'s Pallas
kernel on a TPU, ``jax.lax.ragged_dot`` on any other backend and for shapes
the kernel cannot tile: the FLOPs are those of the rows present, 1.41 GFLOP
for 192 rows of ``[3584, 1024]``, not E times that; float32 sums, a result
in ``x``'s dtype either way), and the results go back to their tokens by
the inverse permutation and are summed under their gates in float32. One
expert taking every token is one group of ``n * k`` rows and 63 empty ones.

A HOLDER of ``count`` of ``num_experts`` experts (``held_share``; an
expert-parallel deployment's exchange hands a chip its own pairs and no
others) does all of that over its own pairs only. The one sort puts them
first; the row gather, the three grouped products and their casts then take
``share_rows`` of them at a time: a buffer of a STATIC size, twice the share
the holder expects (8,192 rows of the 32,768 pairs that 4,096 tokens make
for 16 of 128), from which each token's ``k`` rows are gathered (a pair that
is another holder's gathers zeros) and summed under the gates in float32,
as above. A routing that crowds more pairs onto the holder than one buffer
takes goes round again over the same buffer (``share_passes``: the first
pass in line, the others in a loop whose trip count is the held pairs over
the rows less one, none in practice), so no pair is dropped and nothing is
approximated: in one pass the result is the whole sort's to the bit, in
several its float32 sum over a token's experts in another order. The buffer follows from the operands' shapes alone; a holder
of every expert takes all ``n * k`` pairs in one pass, which is the first
paragraph to the letter.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops.grouped_matmul import (
    grouped_matmul,
    grouped_schedule,
    kernel_groups,
)

__all__ = ["route_sigmoid_topk", "dropless_experts", "held_share",
           "share_rows", "share_passes"]

#: rows of a tile of the grouped product: a buffer of pairs is whole tiles
_ROW_TILE = 128


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


def share_rows(pairs: int, count: int, num_experts: int) -> int:
    """Rows of the buffer that a holder of ``count`` of ``num_experts``
    experts sorts, gathers and multiplies at a time, of ``pairs`` (token,
    expert) pairs routed among all ``num_experts``: twice the share it
    expects, in whole row tiles of the grouped product, never more than
    the pairs there are. A holder of every expert takes them all."""
    if count >= num_experts:
        return pairs
    rows = -(-2 * pairs * count // num_experts)
    return min(-(-rows // _ROW_TILE) * _ROW_TILE, pairs)


def share_passes(experts, count: int, num_experts: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """``(held, passes)`` of a routing as ``held_share`` gives it: the
    pairs whose expert is one of the holder's ``count``, and how many
    buffers of ``share_rows`` they fill (one, but for a chunk whose tokens
    crowd onto this holder: ``dropless_experts`` then goes round again)."""
    held = (experts < count).sum().astype(jnp.int32)
    return held, -(-held // share_rows(experts.size, count, num_experts))


def dropless_experts(x, experts, gates, w_gate, w_up, w_down,
                     num_experts: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """``sum_i gates[:, i] * FFN_{experts[:, i]}(x)`` with ``FFN(x) =
    (silu(x W_gate) * x W_up) W_down``. ``x [n, d]``; ``experts, gates
    [n, k]``; ``w_gate, w_up [E, d, F]``, ``w_down [E, F, d]``. Returns
    ``(y [n, d] in x's dtype, hit)``, ``hit`` the number of experts that
    got at least one token.

    ``num_experts`` is how many the router chose among, where the ``E``
    matrices are a holder's share of them and ``experts`` is what
    ``held_share`` made of the routing (module docstring: the held pairs
    alone are gathered and multiplied, ``share_rows`` of them a pass).
    Without it such a routing's pairs are all sorted and multiplied at
    once, and what the other holders' pairs add is zero only where the
    grouped product leaves the rows past its groups zero: the kernel writes
    zeros there, ``ragged_dot`` does on the CPU (on the TPU they once read
    NaN: PERF.md, PR 41).

    The grouped products are ``ops.grouped_matmul``'s Pallas kernel where
    it can take them (``kernel_groups``: a TPU, whole tiles) and
    ``jax.lax.ragged_dot`` anywhere else, decided here on the operands'
    shapes. The arithmetic is one module-level ``jax.jit``
    (``_dropless_experts``): the layers of a program share one trace and one
    lowered function of it (PERF.md, "where a warm set-up goes")."""
    n, k = experts.shape
    rows = jax.ShapeDtypeStruct(
        (share_rows(n * k, w_gate.shape[0], num_experts or w_gate.shape[0]),
         x.shape[-1]), x.dtype)
    return _dropless_experts(x, experts, gates, w_gate, w_up, w_down,
                             num_experts=num_experts,
                             kernel=kernel_groups(rows, w_gate))


@functools.partial(jax.jit, static_argnames=("num_experts", "kernel"))
def _dropless_experts(x, experts, gates, w_gate, w_up, w_down, *,
                      num_experts, kernel):
    n, k = experts.shape
    n_experts = w_gate.shape[0]
    cap = share_rows(n * k, n_experts, num_experts or n_experts)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)

    def ffn(rows, sizes):
        if kernel:
            # one schedule of row tiles and groups for the three products
            schedule = grouped_schedule(sizes, rows.shape[0])

            def grouped(a, w):
                return grouped_matmul(a, w, sizes, schedule=schedule)
        else:
            def grouped(a, w):
                return jax.lax.ragged_dot(
                    a, w, sizes, preferred_element_type=jnp.float32
                ).astype(x.dtype)

        return grouped(
            jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up), w_down)

    def under_gates(back):                  # a token's k rows, summed
        return jnp.einsum("nkd,nk->nd",
                          back.reshape(n, k, -1).astype(jnp.float32), gates)

    if cap == n * k:
        # every pair has a row: un-sort them and sum each token's k
        out = ffn(x[order // k], sizes)                    # [n * k, d]
        y = under_gates(out[jnp.argsort(order)])
    else:
        held, ends = sizes.sum(), jnp.cumsum(sizes)
        at = jnp.argsort(order)         # where a pair lies among the sorted
        at = jnp.where(at < held, at, -1)
        order = jnp.pad(order, (0, -(n * k) % cap))

        def one_pass(i, y):
            first = i * cap
            upto = jnp.clip(ends - first, 0, cap)
            pairs = jax.lax.dynamic_slice(order, (first,), (cap,))
            out = ffn(x[pairs // k], jnp.diff(upto, prepend=0))  # [cap, d]
            # a pair of another pass or another holder takes no row: zeros
            row = jnp.where((at >= first) & (at < first + cap), at - first,
                            cap)
            return y + under_gates(
                jnp.take(out, row, axis=0, mode="fill", fill_value=0))

        y = jax.lax.fori_loop(
            1, -(-held // cap), one_pass,
            one_pass(0, jnp.zeros((n, x.shape[-1]), jnp.float32)))
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
