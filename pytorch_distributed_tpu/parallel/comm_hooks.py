"""Gradient communication hooks — torch DDP comm-hook parity
(``distributed/algorithms/ddp_comm_hooks/default_hooks.py:35,96,116``).

In the GSPMD world XLA inserts the gradient all-reduce from shardings, so
there is nothing to "hook" by default. These hooks exist for the cases
where the WIRE matters and the user wants to trade precision for
bandwidth — above all the HSDP inter-slice gradient all-reduce that rides
DCN (torch ``_runtime_utils.py:866-877`` hybrid branch): compressing that
transfer to bf16 halves cross-datacenter traffic.

Two usage levels:

  * inside any ``shard_map``: ``bf16_compress(grads, axis_name)`` — cast,
    psum-mean on the axis, cast back. Verified to place the all-reduce on
    the wire in bf16 (tests assert the HLO all-reduce operand dtype).
  * ``Trainer(comm_hook=...)`` with :class:`DataParallel`: the step
    computes per-shard grads inside shard_map (no automatic sync) and
    applies the hook explicitly — the manual-DDP structure torch's hooks
    assume.

Scope note: the bucketed reduce-scatter hook (``make_bucketed_rs_hook``)
and the ppermute ring predate the sharded-update engine
(``parallel/sharded_update.py``). For the memory/scheduling story they
approximated by hand — reduce-scatter the grads, step on a shard,
all-gather — use ``ZeRO1``/``FullyShardedDataParallel`` with
``sharded_update`` instead: the compiler inserts and overlaps the same
collectives inside the ONE fused step program, with none of the
pad/flatten bucket bookkeeping (and graftlint's hand-rolled-reshard rule
now flags new hand-written per-param gather/scatter loops). The hooks
remain the *wire-format* layer — bf16/fp16/PowerSGD compression where
bandwidth, not memory, is the constraint — and the ring remains a
scheduling experiment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax import lax

__all__ = [
    "allreduce_hook",
    "bf16_compress",
    "fp16_compress",
    "make_bucketed_rs_hook",
    "make_ring_allreduce_hook",
    "reduce_scatter_hook",
    "ring_allreduce_hook",
    "get_comm_hook",
]


def allreduce_hook(grads, axis_name: str):
    """Plain full-precision mean all-reduce (torch ``allreduce_hook:35``)."""
    return jtu.tree_map(lambda g: lax.pmean(g, axis_name), grads)


def _compress_hook(dtype):
    def hook(grads, axis_name: str):
        def one(g):
            if not jnp.issubdtype(g.dtype, jnp.floating):
                return lax.pmean(g, axis_name)
            return lax.pmean(g.astype(dtype), axis_name).astype(g.dtype)

        return jtu.tree_map(one, grads)

    return hook


#: bf16-compressed mean all-reduce (torch ``bf16_compress_hook:116``) —
#: the hook with a real TPU story: halves DCN gradient traffic
bf16_compress = _compress_hook(jnp.bfloat16)

#: fp16-compressed mean all-reduce (torch ``fp16_compress_hook:96``)
fp16_compress = _compress_hook(jnp.float16)

def _make_bucketed_hook(cap_bytes: int, reduce_flat):
    """Shared bucketing scaffolding for the flat-bucket hooks: group
    consecutive same-dtype floating leaves up to ``cap_bytes`` (non-float
    leaves take a plain pmean), pack each bucket into one padded flat
    vector, hand it to ``reduce_flat(flat, axis_name, n) -> mean`` and
    scatter the result back into leaf shapes."""

    def hook(grads, axis_name: str):
        n = lax.axis_size(axis_name)
        leaves, treedef = jtu.tree_flatten(grads)
        synced: list = [None] * len(leaves)

        buckets: list = []  # [dtype, [leaf indices], bytes]
        for i, g in enumerate(leaves):
            if not jnp.issubdtype(g.dtype, jnp.floating):
                synced[i] = lax.pmean(g, axis_name)
                continue
            size = g.size * g.dtype.itemsize
            if (
                buckets
                and buckets[-1][0] == g.dtype
                and buckets[-1][2] + size <= cap_bytes
            ):
                buckets[-1][1].append(i)
                buckets[-1][2] += size
            else:
                buckets.append([g.dtype, [i], size])

        for _, idxs, _ in buckets:
            flat = jnp.concatenate([leaves[i].ravel() for i in idxs])
            pad = (-flat.size) % n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            full = reduce_flat(flat, axis_name, n)
            off = 0
            for i in idxs:
                g = leaves[i]
                synced[i] = full[off : off + g.size].reshape(g.shape)
                off += g.size
        return jtu.tree_unflatten(treedef, synced)

    return hook


def make_bucketed_rs_hook(bucket_cap_mb: float = 25.0):
    """Bucketed reduce-scatter + all-gather gradient mean — the overlap-
    friendly lowering of the DP gradient sync.

    Torch's Reducer overlaps its bucketed gradient all-reduce with backward
    compute (``reducer.hpp:75,283`` — SURVEY §3.3 calls this "the entire
    DDP performance story").  On TPU the analogous scheduling decision
    belongs to XLA's latency-hiding scheduler, and the topology-AOT probe
    (``perf/overlap_aot_probe.py``) shows it leaves ``all-reduce``
    SYNCHRONOUS in the scheduled module while demonstrably making the
    all-gather / reduce-scatter / collective-permute class async (36
    start/done pairs, 12 with compute inside, in the fsdp probe).  This
    hook therefore expresses the same mean as ``psum_scatter`` +
    ``all_gather`` per bucket: identical wire bytes (ring all-reduce IS
    rs+ag), but in the op class the scheduler overlaps.

    Buckets (default 25 MB — torch's ``bucket_cap_mb`` default,
    ``nn/parallel/distributed.py:31``) partition the gradients so each
    bucket's reduce-scatter depends only on its own leaves: the scheduler
    can issue bucket k's collective while backward is still producing
    bucket k+1's grads, and bucket k's all-gather while bucket k+1's
    reduce-scatter is in flight — the Reducer-bucket dependency structure,
    recovered declaratively.
    """
    def rs_ag(flat, axis_name, n):
        shard = lax.psum_scatter(
            flat, axis_name, scatter_dimension=0, tiled=True
        )
        return lax.all_gather(shard / n, axis_name, axis=0, tiled=True)

    return _make_bucketed_hook(int(bucket_cap_mb * 1024 * 1024), rs_ag)


#: default-capacity bucketed rs+ag sync (``comm_hook="reduce_scatter"``)
reduce_scatter_hook = make_bucketed_rs_hook()


def make_ring_allreduce_hook(bucket_cap_mb: float = 4.0):
    """Bucketed gradient mean as a HAND-ROLLED ring all-reduce over
    ``lax.ppermute`` — the scaling-book "write the ring yourself"
    pattern, and the one lowering on the asyncifiable op class.

    Why this exists (the VERDICT r4 #1 endgame): the AOT census over the
    v5e-8 topology (perf/dp_overlap_sweep.json, perf/overlap_aot_probe)
    shows this TPU compiler schedules ``collective-permute`` async — 36
    start/done pairs, 12 with compute inside, in the fsdp probe — while
    ``all-reduce``, ``all-gather``, and its fused ``all-reduce-scatter``
    kernels ALL stay synchronous under every accepted flag
    (latency_hiding / async_collective_fusion family /
    data_parallel_all_reduce_opt / xla_enable_async_all_reduce), and an
    explicit ``psum_scatter`` is rewritten back into all-reduce +
    dynamic-slice. A ring all-reduce IS reduce-scatter + all-gather at
    identical wire volume, but expressed as 2(N-1) neighbor
    ``ppermute`` hops it stays in the op class the scheduler overlaps;
    with several buckets, one bucket's hops interleave with other
    buckets' hops and with backward compute — torch Reducer-bucket
    overlap, recovered on the TPU's own terms.

    Default bucket is smaller than torch's 25 MB: each bucket's ring is
    a serial 2(N-1)-hop chain, so cross-bucket parallelism (the overlap
    source) wants more, smaller buckets.

    The hop loop is PYTHON-unrolled (static N) on purpose: a
    ``fori_loop`` would wall the hops inside one sequential HLO op and
    the scheduler could not interleave them.
    """
    def ring_allreduce(flat, axis_name: str, n: int):
        """[n * chunk] summed across the axis, via 2(n-1) ppermute hops."""
        perm = [(i, (i + 1) % n) for i in range(n)]
        idx = lax.axis_index(axis_name)
        chunk = flat.size // n
        chunks = flat.reshape(n, chunk)
        # reduce-scatter phase: after n-1 hops, this rank holds the fully
        # reduced chunk (idx + 1) % n
        buf = lax.dynamic_index_in_dim(
            chunks, (idx - 0) % n, axis=0, keepdims=False
        )
        for s in range(n - 1):
            buf = lax.ppermute(buf, axis_name, perm)
            recv_ix = (idx - s - 1) % n
            buf = buf + lax.dynamic_index_in_dim(
                chunks, recv_ix, axis=0, keepdims=False
            )
        # all-gather phase: circulate the reduced chunks n-1 hops
        own_ix = (idx + 1) % n
        out = jnp.zeros_like(chunks)
        out = lax.dynamic_update_index_in_dim(out, buf, own_ix, axis=0)
        for s in range(n - 1):
            buf = lax.ppermute(buf, axis_name, perm)
            src_ix = (idx - s) % n  # chunk owned by rank (idx - s - 1)
            out = lax.dynamic_update_index_in_dim(out, buf, src_ix, axis=0)
        return out.reshape(flat.shape)

    def ring_mean(flat, axis_name, n):
        if n == 1:
            return flat
        return ring_allreduce(flat, axis_name, n) / n

    return _make_bucketed_hook(
        int(bucket_cap_mb * 1024 * 1024), ring_mean
    )


#: default ring-all-reduce sync (``comm_hook="ring_allreduce"``)
ring_allreduce_hook = make_ring_allreduce_hook()

_REGISTRY = {
    "allreduce": allreduce_hook,
    "bf16_compress": bf16_compress,
    "fp16_compress": fp16_compress,
    "reduce_scatter": reduce_scatter_hook,
    "ring_allreduce": ring_allreduce_hook,
}


def get_comm_hook(hook):
    """Resolve a hook name or callable to ``hook(grads, axis_name)``."""
    if callable(hook):
        return hook
    try:
        return _REGISTRY[hook]
    except KeyError:
        raise ValueError(
            f"unknown comm hook {hook!r} (have {sorted(_REGISTRY)})"
        ) from None
