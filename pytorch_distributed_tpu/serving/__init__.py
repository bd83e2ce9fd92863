"""Serving engine — KV-cached decode, continuous batching, TP inference.

The inference face of the framework, reusing the training stack end to end:

  * :mod:`kv_cache`  — preallocated slotted KV cache, a donated jit pytree
    with multi-token append + rejection rollback
  * :mod:`state_cache` — the hybrid stack's: a fixed-size recurrent state a
    slot for the KDA layers beside a latent cache's rows for the MLA layers
  * :mod:`paging`    — the paged alternative: fixed-size K/V pages + block
    tables (:class:`PagedKVCache`), a refcounted COW allocator, and a radix
    tree that maps shared prompt prefixes to live page chains so repeat
    prompts skip their prefill (``cache_kind="paged"``)
  * :mod:`engine`    — compiled prefill (bucketed prompt lengths) + decode
    + speculative draft/verify steps with sampling (greedy / temperature /
    top-k / top-p) over the cache-aware GPT-2 forward (``models.gpt2`` +
    ``ops.decode_attention``)
  * :mod:`speculative` — the spec-decode math: draft filters, exact-match
    greedy acceptance, leftover/rejection sampling
  * :mod:`scheduler` — continuous batching: FIFO admission, iteration-level
    join/evict, slot reuse, 1..k+1-token speculative span consumption,
    latency/throughput/accept-rate counters into ``observability``
  * :mod:`sharding`  — train→serve glue: params-only reshard-on-load from
    training checkpoints onto a ``(dp, tp)`` serving mesh via the same
    Megatron plan the trainer uses (draft model included)

Import contract: this package loads neither orbax nor the Pallas toolchain
at module import (checkpoint IO is function-local; decode attention is the
dense op) — control planes and CPU tests import it for free.
"""

from pytorch_distributed_tpu.serving.engine import (
    InferenceEngine,
    SamplingParams,
    sample_tokens,
)
from pytorch_distributed_tpu.serving.kv_cache import KVCache, LatentCache
from pytorch_distributed_tpu.serving.window_cache import WindowedKVCache
from pytorch_distributed_tpu.serving.state_cache import HybridStateCache
from pytorch_distributed_tpu.serving.paging import (
    CapacityError,
    PageAllocator,
    PagedKVCache,
    RadixTree,
)
from pytorch_distributed_tpu.serving.scheduler import (
    FinishedRequest,
    Request,
    Scheduler,
)
from pytorch_distributed_tpu.serving.sharding import (
    draft_param_shardings,
    gpt2_param_shardings,
    gpt2_params_template,
    kv_cache_sharding,
    load_gpt2_params,
    paged_kv_cache_sharding,
    reshard_gpt2_params,
    serving_mesh,
)
from pytorch_distributed_tpu.serving.multihost import HostWorker, Router
from pytorch_distributed_tpu.serving.speculative import (
    DraftConfig,
    filter_logits,
    filtered_probs,
    greedy_accept,
    rejection_accept,
)

__all__ = [
    "KVCache",
    "LatentCache",
    "WindowedKVCache",
    "HybridStateCache",
    "PagedKVCache",
    "PageAllocator",
    "RadixTree",
    "CapacityError",
    "InferenceEngine",
    "SamplingParams",
    "sample_tokens",
    "DraftConfig",
    "filter_logits",
    "filtered_probs",
    "greedy_accept",
    "rejection_accept",
    "Request",
    "FinishedRequest",
    "Scheduler",
    "Router",
    "HostWorker",
    "serving_mesh",
    "gpt2_params_template",
    "gpt2_param_shardings",
    "draft_param_shardings",
    "kv_cache_sharding",
    "paged_kv_cache_sharding",
    "load_gpt2_params",
    "reshard_gpt2_params",
]
