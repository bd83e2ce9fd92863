"""Paged KV cache subsystem: page pool pytree + allocator + radix cache.

Three pieces, one discipline:

* :class:`PagedKVCache` (device) — ``[L, n_pages, page_size, H, D]`` K/V
  pools + per-slot block tables, donated through the jitted serving steps
  exactly like the slotted cache; :class:`PagedLatentCache` is the same
  pool of a latent-attention model's 640-wide rows.
* :class:`PageAllocator` (host) — free list, refcounted copy-on-write
  pages, worst-case admission reservations so an admitted sequence can
  always grow.
* :class:`RadixTree` (host) — token-hash prefix index mapping shared
  prompt prefixes to live page chains; a hit admits by reference and
  skips prefill for the shared span.

Selected via ``InferenceEngine(cache_kind="paged")``; the scheduler wires
the three together (serving.scheduler).
"""

from pytorch_distributed_tpu.serving.paging.allocator import (  # noqa: F401
    CapacityError,
    PageAllocator,
)
from pytorch_distributed_tpu.serving.paging.kv_cache import (  # noqa: F401
    TRASH_PAGE,
    PagedKVCache,
    fork_pages,
)
from pytorch_distributed_tpu.serving.paging.latent_cache import (  # noqa: F401
    PagedLatentCache,
)
from pytorch_distributed_tpu.serving.paging.radix import (  # noqa: F401
    RadixTree,
)

__all__ = [
    "CapacityError",
    "PageAllocator",
    "PagedKVCache",
    "PagedLatentCache",
    "RadixTree",
    "TRASH_PAGE",
    "fork_pages",
]
