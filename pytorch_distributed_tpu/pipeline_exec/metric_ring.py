"""On-device metric ring buffer.

The per-step metric scalars (loss, grad_norm, accuracy, ...) never leave
the device on the hot path: the jitted step writes them into a fixed-size
ring carried through the step like the rest of the train state (donated,
so the write is in-place), and the host drains whole windows with
non-blocking readback. ``float(metrics["loss"])`` per step — a host
round trip that stalls dispatch behind the device — becomes one async
transfer of ``size`` scalars per window.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
from flax import struct

__all__ = ["MetricRing"]


class MetricRing(struct.PyTreeNode):
    """Fixed-size ring of per-step scalar metrics, resident on device.

    Fields:
      idx: total steps pushed so far (i32 scalar); the write slot of the
        next push is ``idx % size``.
      buf: ``{metric name: f32[size]}`` — one lane per metric.
    """

    idx: jax.Array
    buf: Dict[str, jax.Array]

    @property
    def size(self) -> int:
        return next(iter(self.buf.values())).shape[0]

    @property
    def names(self) -> Sequence[str]:
        return tuple(sorted(self.buf))

    @classmethod
    def create(cls, names: Sequence[str], size: int) -> "MetricRing":
        if size < 1:
            raise ValueError(f"ring size must be >= 1, got {size}")
        if not names:
            raise ValueError("metric ring needs at least one metric name")
        return cls(
            idx=jnp.int32(0),
            buf={n: jnp.zeros((size,), jnp.float32) for n in sorted(names)},
        )

    def push(self, metrics: Dict[str, Any]) -> "MetricRing":
        """Write one step's metrics at the current slot (traced code).
        Bools (``all_finite``) are stored as 0.0/1.0."""
        slot = jax.lax.rem(self.idx, jnp.int32(self.size))
        buf = {
            k: self.buf[k].at[slot].set(
                jnp.asarray(metrics[k]).astype(jnp.float32).reshape(())
            )
            for k in self.buf
        }
        return MetricRing(idx=self.idx + 1, buf=buf)

    def stacked(self) -> jax.Array:
        """``[n_metrics, size]`` snapshot in sorted-name order. ``stack``
        materializes a FRESH buffer — it can never alias the donated ring
        lanes, which is what makes the snapshot safe to hold on the host
        while the ring itself is donated into the next step."""
        return jnp.stack([self.buf[k] for k in self.names])
