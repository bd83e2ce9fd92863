"""The grouped products of a dropless mixture of experts as one Pallas
kernel: ``rows [m, K]`` lie sorted by expert, group ``e`` is ``sizes[e]``
consecutive rows, and each group is multiplied by its own ``w[e] [K, N]``.

What ``jax.lax.ragged_dot`` computes, with three differences that are the
kernel's reason (PERF.md, PR 48): the float32 sums stay in VMEM and the
result is written once, in ``rows``' dtype (``ragged_dot`` writes a float32
``[m, N]`` to HBM that its caller casts back); only row tiles that hold
pairs are multiplied and an empty group's matrix is never read; and the
rows past the last group are WRITTEN, as zeros (``ragged_dot`` leaves them
to the backend: on the TPU they once read NaN).

One algorithm for both of serving's regimes. The grid is ``(N tiles,
visits, K tiles)``: a VISIT is one (row tile, group) pair that shares rows,
listed by ``grouped_schedule`` from ``sizes`` (scalar prefetch) in the
order of the rows, a tile that straddles groups once a group. ``K`` is
taken whole wherever a ``[K, tn]`` block of a matrix fits the budget, so
the matrix block of consecutive visits of one group is the same block and
is copied once: a decode step (128-512 rows in all, a few a group) reads
each HIT expert's matrix once and is bound by that; a prefill (thousands
of rows, hundreds a group) keeps a group's block in VMEM over the group's
row tiles and is bound by the MXU. The rows past the last group are one
more group, without a matrix: its visits multiply nothing, copy nothing
(their block indices are the last real visit's) and store zeros.

Tile sizes are a pure function of ``(m, K, N, dtype)``; nothing is timed
or searched. THE ONE WAY INTO THE ``pallas_call`` IS ``_grouped_call``, a
module-level ``jax.jit``: a program that multiplies in twenty-four places
(layers x traces of the experts' function x gate, up, down) lowers the
kernel once a distinct ``(m, K, N)`` and calls that function from each
place, where a bare ``pallas_call`` is lowered to a Mosaic module at every
call site of every program of every process, compile cache warm or not
(``tests/test_chip_compile.py`` counts them).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Schedule", "grouped_matmul", "grouped_schedule", "kernel_groups",
           "row_tile"]

#: a block of one expert's matrix (two are in flight), bytes
_MATRIX_BLOCK_BYTES = 8 << 20


def _vmem_bytes(tiles: Tuple[int, int, int], itemsize: int) -> int:
    """What the kernel holds in VMEM: two blocks each of a matrix, of rows
    and of results, the float32 sums, and a quarter more for the compiler.
    Asked for to the byte, not generously: what a kernel reserves the
    compiler takes from every other operation of the program, which keeps
    its own intermediates there (with 64 MiB reserved the 100 MB result of
    cell 6's down product stayed in HBM and the gather that un-sorts it
    took 3.08 ms for 0.61: my chip run, PR 48)."""
    tm, tk, tn = tiles
    blocks = 2 * (tk * tn + tm * tk + tm * tn) * itemsize + tm * tn * 4
    return blocks + blocks // 4 + (1 << 20)


class Schedule(NamedTuple):
    """The visits of one ``sizes`` over ``m`` rows in tiles of ``tm``
    (``grouped_schedule``): int32 arrays of ``m // tm + E`` places, of
    which the first ``visits`` count."""

    group: jax.Array        # [V] the visit's group; E = the rows past them
    tile: jax.Array         # [V] the row tile it writes
    rows_tile: jax.Array    # [V] the row tile it reads (a real visit's own)
    matrix: jax.Array       # [V] the matrix it reads (a real visit's own)
    bounds: jax.Array       # [E + 2] row at which each group starts
    visits: jax.Array       # [] how many there are


def row_tile(m: int) -> int:
    """Rows of a tile, from ``m`` alone so that the three products of one
    ``sizes`` share a schedule: the largest of 128, 64, 32, 16 that divides
    ``m`` (192 rows are three tiles of 64: no padded copy). Small tiles
    waste less of the MXU on a tile that straddles groups, and a group's
    matrix block stays in VMEM from tile to tile whatever their size."""
    for tm in (128, 64, 32, 16):
        if m % tm == 0:
            return tm
    raise ValueError(f"{m} rows are not whole tiles of 16")


def _column_tiles(K: int, N: int, itemsize: int,
                  block_bytes: int = _MATRIX_BLOCK_BYTES) -> Tuple[int, int]:
    """``(tk, tn)``: the widest whole-lane-tile divisor of ``N`` whose
    ``[K, tn]`` block fits ``block_bytes``, ``K`` whole; a ``K`` too long
    even for 128 columns is cut into its largest whole-lane-tile divisor."""
    fits = [tn for tn in range(128, N + 1, 128)
            if N % tn == 0 and K * tn * itemsize <= block_bytes]
    if fits:
        return K, fits[-1]
    return max(tk for tk in range(128, K, 128)
               if K % tk == 0 and tk * 128 * itemsize <= block_bytes), 128


def kernel_groups(rows: jax.Array, w: jax.Array) -> bool:
    """Whether the kernel can multiply operands of these shapes on this
    backend: Mosaic runs on a TPU and wants whole lane tiles of ``K`` and
    ``N`` and whole sublane tiles of rows."""
    from pytorch_distributed_tpu.ops.decode_attention import _platform

    (m, K), N = rows.shape, w.shape[-1]
    return (_platform() == "tpu" and rows.dtype == w.dtype
            and K % 128 == 0 and N % 128 == 0 and m % 16 == 0)


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def grouped_schedule(sizes: jax.Array, m: int,
                     tm: Optional[int] = None) -> Schedule:
    """The (row tile, group) pairs that share rows, in the order of the
    rows, for groups of ``sizes [E]`` consecutive rows of ``m``; the rows
    past the last group are group ``E``. Computed once for the products
    that share ``sizes``.

    A few dozen integers, so the form is the one the chip runs in a few
    fused passes: running sums as comparisons against an index grid and
    look-ups as one-hot sums (``cumsum``, ``repeat`` and gathers were
    thirty operations of 20 us each: 0.65 ms a call, my chip run, PR 48).
    A ``jax.jit`` of its own for the same reason as ``_grouped_call``."""
    tm = tm or row_tile(m)
    E = sizes.shape[0]
    V = m // tm + E               # every tile once + every group's first
    sizes = sizes.astype(jnp.int32)
    held = sizes.sum()
    sizes = jnp.concatenate([sizes, (m - held)[None]])          # [E + 1]
    upto = jnp.arange(E + 1)[:, None] >= jnp.arange(E + 1)[None, :]
    ends = (upto * sizes[None, :]).sum(-1)                      # cumsum
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    last = (upto * tiles[None, :]).sum(-1)          # visits up to a group's
    visit = jnp.arange(V, dtype=jnp.int32)
    group = jnp.minimum((last[None, :] <= visit[:, None]).sum(-1), E)
    of = (group[:, None] == jnp.arange(E + 1)[None, :])         # [V, E + 1]
    tile = jnp.minimum(
        visit + (of * (first - last + tiles)[None, :]).sum(-1), m // tm - 1)
    real = group < E
    last_hit = jnp.max(jnp.where(sizes[:E] > 0, jnp.arange(E), 0))
    return Schedule(
        group=group, tile=tile,
        rows_tile=jnp.where(real, tile, jnp.maximum(held - 1, 0) // tm),
        matrix=jnp.where(real, group, last_hit),
        bounds=jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
        visits=tiles.sum())


def _kernel(group_ref, tile_ref, rows_tile_ref, matrix_ref, bounds_ref,
            rows_ref, w_ref, out_ref, acc_ref, *, tm, n_groups):
    v, k = pl.program_id(1), pl.program_id(2)
    g = group_ref[v]

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < n_groups)              # the rows past the groups: nothing
    def _():
        acc_ref[...] += jnp.dot(rows_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        tile = tile_ref[v]
        row = tile * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        own = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
        # a tile's visits are consecutive and between them own every row
        # of it once: the first writes zeros where the later ones will
        opens = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile)

        @pl.when(opens)
        def _():
            out_ref[...] = jnp.where(own, acc_ref[...], 0.0).astype(
                out_ref.dtype)

        @pl.when(jnp.logical_not(opens))
        def _():
            out_ref[...] = jnp.where(
                own, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_call(schedule: Schedule, rows, w, *, tiles, interpret=False):
    """The kernel over one schedule. A ``jax.jit`` of its own, at module
    level, and the only caller of the ``pallas_call``: every call site of a
    program with the same shapes and tiles shares one lowering."""
    tm, tk, tn = tiles
    (m, K), (E, _, N) = rows.shape, w.shape
    dtype = jnp.dtype(rows.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, n_groups=E),
        out_shape=jax.ShapeDtypeStruct((m, N), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, schedule.visits, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, k, g, t, rt, mx, b: (rt[v], k)),
                pl.BlockSpec((None, tk, tn),
                             lambda n, v, k, g, t, rt, mx, b: (mx[v], k, n)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, k, g, t, rt, mx, b: (t[v], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tiles, dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * K * N, transcendentals=0,
            bytes_accessed=(m * K * (N // tn) + E * K * N + m * N)
            * dtype.itemsize),
        name="grouped_matmul",
        interpret=interpret,
    )(schedule.group, schedule.tile, schedule.rows_tile, schedule.matrix,
      schedule.bounds, rows, w)


def grouped_matmul(rows: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   schedule: Optional[Schedule] = None,
                   interpret: bool = False) -> jax.Array:
    """``out[r] = rows[r] @ w[e]`` for the rows ``r`` of group ``e``, the
    ``sizes[e]`` consecutive rows after those of the groups before it, and
    zeros in the rows past the last group. ``rows [m, K]``, ``w [E, K,
    N]`` of one dtype, ``sizes [E]`` int32 summing to ``m`` at most; ``[m,
    N]`` in that dtype from float32 sums. ``schedule`` is
    ``grouped_schedule(sizes, m)`` where several products share it."""
    (m, K), N = rows.shape, w.shape[-1]
    if schedule is None:
        schedule = grouped_schedule(sizes, m)
    tiles = (row_tile(m),) + _column_tiles(K, N, rows.dtype.itemsize)
    return _grouped_call(schedule, rows, w, tiles=tiles, interpret=interpret)
