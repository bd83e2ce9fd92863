"""In-jit collectives over mesh axes — the ICI-native op set.

Capability parity: the collective op set of ``c10d::Backend``
(``Backend.hpp:158-400`` — broadcast / allreduce / allgather / reduce_scatter /
alltoall / send / recv / barrier; SURVEY.md §2.1) and torch's *functional*
collectives (``distributed/_functional_collectives.py`` — traceable,
tensor-returning; SURVEY.md §2.1 "Functional collectives").

TPU-first design: these are thin wrappers over ``jax.lax`` collective
primitives, usable only inside ``shard_map``/``pmap``-style per-device code.
XLA schedules them on the ICI torus (or DCN for cross-slice axes) and overlaps
them with compute via its latency-hiding scheduler — there is no Work handle to
wait on because asynchrony is the compiler's job, not the caller's.

Every wrapper takes ``axis``: a mesh axis name, tuple of names, or a
``SubMesh`` view from ``DeviceMesh.__getitem__``.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from pytorch_distributed_tpu.mesh import DeviceMesh, SubMesh

AxisLike = Union[str, Sequence[str]]

__all__ = [
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "broadcast",
    "all_to_all",
    "permute",
    "send_to",
    "recv_from",
    "barrier",
    "axis_index",
    "axis_size",
    "shard_map",
]


def _axis(axis) -> Union[str, tuple]:
    """Accept an axis name, tuple of names, or SubMesh view."""
    if isinstance(axis, SubMesh):
        return axis.collective_axes
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return axis


def axis_index(axis) -> jax.Array:
    """This device's coordinate along ``axis`` (torch: ``dist.get_rank(group)``)."""
    return lax.axis_index(_axis(axis))


def axis_size(axis) -> int:
    """Number of devices along ``axis`` (torch: ``dist.get_world_size(group)``)."""
    a = _axis(axis)
    if isinstance(a, tuple):
        out = 1
        for name in a:
            out *= lax.axis_size(name)
        return out
    return lax.axis_size(a)


def all_reduce(x, axis, op: str = "sum"):
    """All-reduce over a mesh axis (torch: ``dist.all_reduce`` /
    ``distributed_c10d.py:3156``). op in {sum, mean, max, min, prod}."""
    a = _axis(axis)
    if op == "sum":
        return lax.psum(x, a)
    if op in ("mean", "avg"):
        return lax.pmean(x, a)
    if op == "max":
        return lax.pmax(x, a)
    if op == "min":
        return lax.pmin(x, a)
    if op in ("prod", "product"):
        return jnp.prod(lax.all_gather(x, a, axis=0, tiled=False), axis=0)
    raise ValueError(f"unknown reduce op {op!r}")


def all_gather(x, axis, *, gather_dim: int = 0, tiled: bool = True):
    """All-gather shards along ``axis`` (torch: ``all_gather_into_tensor``).

    ``tiled=True`` concatenates along ``gather_dim`` (the _allgather_base
    layout); ``tiled=False`` stacks a new leading axis-sized dim.
    """
    return lax.all_gather(x, _axis(axis), axis=gather_dim, tiled=tiled)


def reduce_scatter(x, axis, *, op: str = "sum", scatter_dim: int = 0):
    """Reduce-scatter over ``axis`` (torch: ``reduce_scatter_tensor`` /
    ``_reduce_scatter_base``). Input's ``scatter_dim`` must be divisible by
    the axis size; each device keeps its shard of the sum."""
    if op not in ("sum", "mean", "avg"):
        raise ValueError("reduce_scatter supports sum/mean")
    out = lax.psum_scatter(x, _axis(axis), scatter_dimension=scatter_dim, tiled=True)
    if op in ("mean", "avg"):
        out = out / axis_size(axis)
    return out


def broadcast(x, axis, *, src: int = 0):
    """Broadcast ``src``'s value to all devices on ``axis`` (torch:
    ``dist.broadcast`` / ``distributed_c10d.py:3086``)."""
    a = _axis(axis)
    n = axis_size(a)
    if not 0 <= src < n:
        raise ValueError(f"broadcast src {src} out of range for axis size {n}")
    idx = lax.axis_index(a)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, a)


def all_to_all(x, axis, *, split_dim: int, concat_dim: int, tiled: bool = True):
    """All-to-all over ``axis`` (torch: ``all_to_all_single`` /
    ``_functional_collectives.py:539``; the EP dispatch primitive —
    SURVEY.md §2.2 "EP")."""
    return lax.all_to_all(
        x, _axis(axis), split_axis=split_dim, concat_axis=concat_dim, tiled=tiled
    )


def permute(x, axis, perm: Sequence[tuple]):
    """Collective permute (``lax.ppermute``): ``perm`` is (src, dst) pairs.
    The ring-attention KV rotation primitive (SURVEY.md §5.7)."""
    return lax.ppermute(x, _axis(axis), perm=list(perm))


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def send_to(x, axis, *, dst_offset: int = 1):
    """Ring-shift send: each device's ``x`` moves ``dst_offset`` hops forward
    along the axis ring, so device i receives device (i - dst_offset)'s value
    (P2P send/recv analog — torch ``send:2713/recv:2757`` — expressed as the
    SPMD ppermute pattern)."""
    a = _axis(axis)
    n = lax.axis_size(a)
    return lax.ppermute(x, a, perm=_ring_perm(n, dst_offset))


def recv_from(x, axis, *, src_offset: int = 1):
    """Ring-shift receive: device i gets device (i + src_offset)'s value —
    the mirror of :func:`send_to` (``recv_from(src_offset=k)`` receives what
    ``send_to(dst_offset=-k)`` delivers)."""
    a = _axis(axis)
    n = lax.axis_size(a)
    return lax.ppermute(x, a, perm=_ring_perm(n, -src_offset))


def barrier(axis):
    """Synchronization point on ``axis`` (torch: ``dist.barrier``). Inside a
    compiled program this is a scheduling edge: a tiny psum all devices must
    reach. Returns a zero-dim token to thread as a data dependency."""
    return lax.psum(jnp.zeros((), jnp.int32), _axis(axis))


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` accepting a DeviceMesh (per-device SPMD regions where
    the collectives above are used)."""
    m = mesh.jax_mesh if isinstance(mesh, DeviceMesh) else mesh
    return jax.shard_map(
        f, mesh=m, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )
