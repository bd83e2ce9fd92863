"""Inference engine: jitted prefill + decode + speculative steps over a model
with the cache-aware forward contract (``models.gpt2``, ``models.xing4``).

Compiled programs serve the whole session (the prefill/decode split of
every production LLM server — Orca, vLLM, TGI):

  * ``prefill`` — one request's padded prompt ``[1, bucket]`` runs through
    the cache-aware forward as a FRESH sequence (no position offset: its
    tokens attend each other, O(bucket^2), and nothing of the resident
    cache is read), its K/V rows land in ONE slot of the shared cache as
    one ``[L, 1, bucket, H*D]`` block, and the first generated token is
    sampled from the last real prompt position's logits. Prompts pad to
    the smallest LENGTH BUCKET (powers of two up to ``prefill_len``): short
    prompts stop paying full-length compute; one program per bucket.
  * ``decode``  — ``[n_slots, 1]``: every slot advances one token per call
    and only ACTIVE slots' lengths advance (free slots ride as padding:
    the batch shape never changes, the program compiles once). The step
    scatters its new rows into the donated slotted cache where they lie
    and reads the earlier ones as stored (``serving.kv_cache``;
    ``tests/test_chip_compile.py`` holds the compiled program to it).
  * ``spec``    — speculative decoding (``spec_k > 0``): a cheap draft
    proposes k tokens per slot into scratch cache positions past each
    slot's length, then ONE target forward over the ``[S, k+1]`` window
    verifies all of them and a per-slot prefix is accepted (exact argmax
    match when greedy, leftover/rejection sampling otherwise — see
    :mod:`serving.speculative`). ``lengths`` advances by ``accepts + 1``
    per slot; rejected positions keep their speculative K/V bytes (masked,
    overwritten next step). Target forwards per generated token drops from
    1.0 to ``1 / (1 + E[accepts])``. Both the draft and verify programs
    compile once — no realloc, no shape churn.

All step programs donate the cache pytree, and the model's forward threads
the whole cache through its layers, each writing its own rows through
``cache.attend``: in-place HBM writes. How a sequence's state is stored is
the cache classes' business (``serving.kv_cache`` states the protocol).

Sampling (greedy / temperature / top-k / nucleus top-p) happens inside the
jitted step — only sampled token ids cross the host boundary each step,
which is what the continuous-batching scheduler needs to detect EOS and
join/evict slots.

Parity anchor: with ``SamplingParams(temperature=0)`` the engine emits
exactly ``argmax`` of the full uncached forward at every step — INCLUDING
the speculative path, whose greedy accept rule makes the emitted stream
identical to the non-speculative one regardless of draft quality
(tests/test_serving.py, tests/test_spec_decode.py teacher-forcing oracles).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.observability import (
    register_program,
    shapes_of,
    span,
)
from pytorch_distributed_tpu.redistribute import plan_tree, redistribute_tree
from pytorch_distributed_tpu.serving.kv_cache import KVCache
from pytorch_distributed_tpu.serving.paging import PagedKVCache
from pytorch_distributed_tpu.serving.speculative import (
    DraftConfig,
    filter_logits,
    filtered_probs,
    greedy_accept,
    rejection_accept,
)

__all__ = ["SamplingParams", "InferenceEngine", "sample_tokens"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static sampling configuration (baked into the compiled step).

    ``temperature <= 0`` means greedy (argmax); ``top_k=0`` and
    ``top_p=1.0`` disable their filters.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def validate(self) -> None:
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def sample_tokens(
    logits: jax.Array, rng: jax.Array, sp: SamplingParams
) -> jax.Array:
    """Sample one token per row of ``logits [N, V]`` -> ``[N]`` int32.

    Filter order matches the HF/vLLM convention: temperature, then top-k
    (exactly k survivors — ties with the k-th value break toward lower
    token ids), then top-p over the already-filtered distribution.
    """
    logits = logits.astype(jnp.float32)
    if sp.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filtered = filter_logits(
        logits, temperature=sp.temperature, top_k=sp.top_k, top_p=sp.top_p
    )
    return jax.random.categorical(rng, filtered).astype(jnp.int32)


def _default_buckets(prefill_len: int) -> Tuple[int, ...]:
    """Powers of two from 8 up to ``prefill_len`` (inclusive cap)."""
    buckets = []
    b = 8
    while b < prefill_len:
        buckets.append(b)
        b *= 2
    buckets.append(prefill_len)
    return tuple(buckets)


def _step_key(rng):
    """A step's key from its program's last argument, the pair
    ``InferenceEngine._next_rng`` makes on the host: (the engine's base key,
    the step's counter). Folded HERE, inside the compiled program, so that
    no program of its own runs for it between two steps; the base key stays
    an argument, so a seed is no literal in the program's text. A caller
    that lowers a program with a lone key of its own (``chipbench/tests/
    test_serve_chat_fits.py``) has it taken as the step's key."""
    return jax.random.fold_in(*rng) if isinstance(rng, tuple) else rng


def _slot_prefill(apply_fn, params, cache, tokens, slot, prompt_len):
    """Run ``tokens [1, bucket]`` through ``apply_fn`` into one slot of
    ``cache``: ``(logits, cache)`` with ``lengths[slot] = prompt_len``. The
    forward runs on a fresh one-slot cache of ``bucket`` positions with no
    position offset (nothing resident is read; O(bucket^2)); its rows land
    as one block, in place. The one-slot cache carries ``prompt_len``: a
    model may give the last real position's logits alone, ``[1, 1, V]``."""
    logits, block = apply_fn(
        params, tokens, deterministic=True,
        kv_cache=cache.one_slot(tokens.shape[1], prompt_len),
        position_offset=None,
    )
    return logits, cache.write_slot(slot, block, prompt_len)


class InferenceEngine:
    """Compiled prefill/decode over a flax GPT-2 and a slotted KVCache.

    Args:
      model: a ``models.GPT2`` (dense) or a model that names the class of
        its own slotted cache (``model.cache_class``: ``models.Xing4``).
      params: the model's param pytree — host numpy, device arrays, or
        TP-sharded arrays from ``serving.sharding.load_gpt2_params``.
      n_slots: decode batch width (concurrent sequences).
      max_len: per-slot capacity (prompt + generated); defaults to the
        model's ``n_positions``.
      prefill_len: maximum prompt length; prompts longer than this are
        rejected.
      prefill_buckets: pad-to lengths for the prefill program (compiled
        once per bucket). Defaults to powers of two up to ``prefill_len``.
      sampling: default SamplingParams for both phases.
      cache_dtype: KV dtype (defaults to the model compute dtype).
      cache_sharding: optional NamedSharding for the K/V arrays (the TP
        serving layout from ``serving.sharding.kv_cache_sharding``, or
        ``paged_kv_cache_sharding`` for ``cache_kind="paged"`` — heads on
        tp in both: decode keeps training's Megatron collective pattern).
      seed: RNG seed for stochastic sampling.
      spec_k: speculative-decoding draft depth; 0 disables speculation.
      draft_layers: self-drafting — run the first N target layers (plus
        ``ln_f`` + tied head) as the draft, sharing params AND cache.
      draft_model / draft_params: a separately supplied small GPT-2 draft
        sharing the vocab, with its own cache
        (:meth:`init_draft_cache`) that the scheduler threads beside the
        target cache. TP placement for it comes from
        ``serving.sharding.draft_param_shardings``.
      cache_kind: ``"slotted"`` (per-slot ``max_len`` reservation) or
        ``"paged"`` (``serving.paging`` page pool + block tables; the
        scheduler drives the allocator/radix control plane). The decode
        and speculative programs are cache-kind agnostic (the model
        reaches either through ``cache.attend``); only prefill differs.
        A separate draft model keeps a slotted cache either way (its
        scratch K/V has no sharing story and costs k small layers).
      page_size / n_pages: paged-cache geometry. ``n_pages`` defaults to
        slotted-equivalent capacity + the trash page; a smaller pool
        oversubscribes slots (admission backpressures on free pages).
      tail_len: paged only (``_PagedPrefill``): the longest bucket of the
        program that prefills a prompt's tail behind its chain's pages.
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 8,
        max_len: Optional[int] = None,
        prefill_len: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        sampling: SamplingParams = SamplingParams(),
        cache_dtype: Any = None,
        cache_sharding=None,
        seed: int = 0,
        spec_k: int = 0,
        draft_layers: Optional[int] = None,
        draft_model=None,
        draft_params=None,
        cache_kind: str = "slotted",
        page_size: int = 16,
        n_pages: Optional[int] = None,
        tail_len: int = 1024,
    ):
        cfg = model.cfg
        if getattr(cfg, "moe_experts", 0) > 0:
            raise ValueError("serving supports dense GPT-2 only (MoE "
                             "blocks have no KV-cache story yet)")
        slotted = _slotted_cache_class(
            model, cache_kind, cache_sharding, spec_k)
        sampling.validate()
        self.model = model
        self.cfg = cfg
        self.params = params
        self.n_slots = int(n_slots)
        self.max_len = int(max_len or cfg.n_positions)
        self.prefill_len = int(prefill_len or self.max_len)
        if not (0 < self.prefill_len <= self.max_len):
            raise ValueError(
                f"prefill_len {self.prefill_len} must be in "
                f"(0, max_len={self.max_len}]"
            )
        if prefill_buckets is None:
            self.prefill_buckets = _default_buckets(self.prefill_len)
        else:
            buckets = sorted({int(b) for b in prefill_buckets})
            if not buckets or buckets[0] < 1:
                raise ValueError("prefill_buckets must be positive")
            if buckets[-1] > self.prefill_len:
                raise ValueError(
                    f"prefill bucket {buckets[-1]} exceeds prefill_len "
                    f"{self.prefill_len}"
                )
            if buckets[-1] < self.prefill_len:
                buckets.append(self.prefill_len)
            self.prefill_buckets = tuple(buckets)
        self.sampling = sampling
        self.cache_dtype = cache_dtype
        self.cache_sharding = cache_sharding
        self._rng = jax.random.key(seed)
        self._rng_calls = 0

        # -- cache layout --------------------------------------------------
        if cache_kind not in ("slotted", "paged"):
            raise ValueError(
                f"cache_kind must be 'slotted' or 'paged', got {cache_kind!r}"
            )
        self.cache_kind = cache_kind
        paged = cache_kind == "paged"
        self.page_size = int(page_size)
        self.max_pages = -(-self.max_len // self.page_size)
        if n_pages is None and paged:
            n_pages = self.n_slots * self.max_pages + 1  # + trash page
        self.n_pages = int(n_pages) if n_pages is not None else 0
        # the resident cache's constructor: the kind's class and geometry
        served = _paged_twin(slotted) if paged else slotted
        self._create_cache = functools.partial(
            served.create, page_size=self.page_size, n_pages=self.n_pages,
        ) if paged else slotted.create
        self._step_stats = tuple(getattr(served, "STEP_STATS", ()))

        # -- speculative configuration -------------------------------------
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.draft_layers = draft_layers
        if self.spec_k > 0:
            draft_cfg = DraftConfig(
                k=self.spec_k,
                draft_layers=draft_layers,
                use_draft_model=draft_model is not None,
            )
            draft_cfg.validate(cfg.n_layer)
            if draft_model is not None:
                if draft_params is None:
                    raise ValueError("draft_model requires draft_params")
                if draft_model.cfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {draft_model.cfg.vocab_size} != "
                        f"target vocab {cfg.vocab_size} — the draft must "
                        f"share the tokenizer"
                    )
                if draft_model.cfg.moe_experts > 0:
                    raise ValueError("draft model must be dense")
            if self.spec_k + 1 >= self.max_len:
                raise ValueError(
                    f"spec_k {self.spec_k} leaves no room in max_len "
                    f"{self.max_len}"
                )
        elif draft_layers is not None or draft_model is not None:
            raise ValueError("draft_layers/draft_model require spec_k >= 1")

        model_apply = model.apply
        draft_apply = draft_model.apply if draft_model is not None else None
        sp = sampling
        greedy = sp.temperature <= 0.0

        def _fprobs(logits):
            return filtered_probs(
                logits, temperature=sp.temperature,
                top_k=sp.top_k, top_p=sp.top_p,
            )

        def _dsample(logits, rng):
            """One draft proposal: argmax when greedy, else a sample plus
            the filtered distribution it was drawn from."""
            if greedy:
                tok = jnp.argmax(
                    logits.astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return tok, None
            filtered = filter_logits(
                logits, temperature=sp.temperature,
                top_k=sp.top_k, top_p=sp.top_p,
            )
            tok = jax.random.categorical(rng, filtered).astype(jnp.int32)
            return tok, jax.nn.softmax(filtered, axis=-1)

        def prefill_fn(params, cache, tokens, slot, prompt_len, rng):
            rng = _step_key(rng)
            logits, cache = _slot_prefill(
                model_apply, params, cache, tokens, slot, prompt_len
            )
            whole = logits.shape[1] == tokens.shape[1]
            last = logits[0, prompt_len - 1] if whole else logits[0, 0]
            tok = sample_tokens(last[None], rng, sp)[0]
            return cache, tok

        def paged_prefill_fn(params, cache, tokens, slot, start, n_real,
                             rng, cold=False):
            """Prefill ``tokens [1, bucket]`` (the UNCACHED tail of a
            prompt) into one slot's page chain at global positions
            ``start..``: a radix prefix hit sets ``start = cached_len`` and
            skips the shared span's compute entirely — the chain's shared
            pages supply its K/V through the block table. The view is ONE
            table row over the whole pool (``cache.one_chain``) and carries
            ``n_real``: a model may give the last real position's logits
            alone. ``cold`` (static; ``_PagedPrefill`` says which buckets):
            a prompt from position 0 with nothing cached runs as a FRESH
            sequence, no position offset, and reads nothing of the pool."""
            rng = _step_key(rng)
            offset = None if cold else jnp.full((1,), start, jnp.int32)
            logits, view = model_apply(
                params, tokens, deterministic=True,
                kv_cache=cache.one_chain(slot, n_real),
                position_offset=offset,
            )
            cache = cache.write_chain(slot, view, start + n_real)
            whole = logits.shape[1] == tokens.shape[1]
            last = logits[0, n_real - 1] if whole else logits[0, 0]
            tok = sample_tokens(last[None], rng, sp)[0]
            return cache, tok





        def decode_fn(params, cache, last_tokens, active, rng):
            rng = _step_key(rng)
            logits, new_cache = model_apply(
                params, last_tokens[:, None], deterministic=True,
                kv_cache=cache, position_offset=cache.lengths,
            )
            next_tok = sample_tokens(logits[:, 0, :], rng, sp)
            if self._step_stats:
                next_tok = jnp.concatenate([next_tok, new_cache.step_stats])
            # only active slots advance; free slots ride as padding and
            # their (masked, overwritten-on-admit) cache rows don't move
            return new_cache.advance(1, active), next_tok

        self._prefill = _PagedPrefill(
            paged_prefill_fn, self.prefill_bucket, int(tail_len),
        ) if paged else jax.jit(prefill_fn, donate_argnums=(1,))
        self._decode = jax.jit(decode_fn, donate_argnums=(1,))

        # -- speculative programs ------------------------------------------
        k = self.spec_k

        def _verify_and_commit(params, cache, base, last_tokens, draft,
                               d_probs, active, rng):
            """One target forward over [S, k+1], prefix acceptance, length
            commit. Shared by both draft flavors."""
            window = jnp.concatenate([last_tokens[:, None], draft], axis=1)
            logits, cache = model_apply(
                params, window, deterministic=True,
                kv_cache=cache, position_offset=base,
            )
            if greedy:
                accepts, emitted = greedy_accept(logits, draft)
            else:
                accepts, emitted = rejection_accept(
                    _fprobs(logits), jnp.stack(d_probs, axis=1), draft,
                    jax.random.fold_in(rng, k + 1),
                )
            n_emit = jnp.where(active, accepts + 1, 0).astype(jnp.int32)
            # commit: lengths += accepts+1; rejected tail keeps its
            # speculative K/V bytes — masked out, overwritten next step
            cache = cache.rollback(base).advance(n_emit)
            # token now at position lengths-1 (the separate-draft catch-up
            # refeed wants it): last accepted proposal, or the old last
            ai = jnp.maximum(accepts - 1, 0)
            prev = jnp.take_along_axis(draft, ai[:, None], axis=1)[:, 0]
            prev_next = jnp.where(accepts > 0, prev, last_tokens)
            return cache, emitted, n_emit, prev_next

        def spec_self_fn(params, cache, last_tokens, active, rng):
            """Self-drafting: k truncated-layer forwards into the SAME
            cache's scratch positions, then one full verify that rewrites
            every drafted position for all layers."""
            rng = _step_key(rng)
            base = cache.lengths
            tok = last_tokens
            draft, d_probs = [], []
            for i in range(k):
                logits, cache = model_apply(
                    params, tok[:, None], deterministic=True,
                    kv_cache=cache, position_offset=base + i,
                    n_layers=draft_layers,
                )
                tok, probs = _dsample(
                    logits[:, 0, :], jax.random.fold_in(rng, i)
                )
                draft.append(tok)
                d_probs.append(probs)
            return _verify_and_commit(
                params, cache, base, last_tokens, jnp.stack(draft, axis=1),
                d_probs, active, rng,
            )

        def spec_draft_fn(params, dparams, cache, dcache, last_tokens,
                          prev_tokens, active, rng):
            """Separate draft model: k draft forwards against the draft's
            own cache. The first is a [S, 2] catch-up refeed of
            [prev, last] at positions len-1, len — rewriting an
            already-cached position is idempotent, and after a full accept
            it fills the one position the draft never processed."""
            rng = _step_key(rng)
            base = cache.lengths
            refeed = jnp.stack([prev_tokens, last_tokens], axis=1)
            dlogits, dcache = draft_apply(
                dparams, refeed, deterministic=True,
                kv_cache=dcache, position_offset=jnp.maximum(base - 1, 0),
            )
            tok, probs = _dsample(
                dlogits[:, 1, :], jax.random.fold_in(rng, 0)
            )
            draft, d_probs = [tok], [probs]
            for i in range(1, k):
                dlogits, dcache = draft_apply(
                    dparams, tok[:, None], deterministic=True,
                    kv_cache=dcache, position_offset=base + i,
                )
                tok, probs = _dsample(
                    dlogits[:, 0, :], jax.random.fold_in(rng, i)
                )
                draft.append(tok)
                d_probs.append(probs)
            cache, emitted, n_emit, prev_next = _verify_and_commit(
                params, cache, base, last_tokens, jnp.stack(draft, axis=1),
                d_probs, active, rng,
            )
            # draft cache is valid through the same accepted prefix
            dcache = dcache.rollback(cache.lengths)
            return cache, dcache, emitted, n_emit, prev_next

        def draft_prefill_fn(dparams, dcache, tokens, slot, prompt_len):
            _, dcache = _slot_prefill(
                draft_apply, dparams, dcache, tokens, slot, prompt_len
            )
            return dcache

        if self.spec_k > 0:
            if draft_model is None:
                self._spec = jax.jit(spec_self_fn, donate_argnums=(1,))
                self._draft_prefill = None
            else:
                self._spec = jax.jit(spec_draft_fn, donate_argnums=(2, 3))
                self._draft_prefill = jax.jit(
                    draft_prefill_fn, donate_argnums=(1,)
                )
        else:
            self._spec = None
            self._draft_prefill = None
        self._register_programs()

    def _register_programs(self) -> None:
        """The lazy way to the compiled decode and prefill programs
        (``observability.programs()``): thunks over saved shapes, so they
        hold no array and nothing is lowered or compiled until somebody
        asks."""
        decode, prefill = self._decode, self._prefill
        cache = jax.eval_shape(self.init_cache)
        if self.cache_sharding is not None:
            def placed(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=self.cache_sharding)

            cache = cache.replace(k=placed(cache.k), v=placed(cache.v))
        params = shapes_of(self.params)
        rng = (shapes_of(self._rng), jax.ShapeDtypeStruct((), jnp.uint32))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        slots = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
        active = jax.ShapeDtypeStruct((self.n_slots,), jnp.bool_)
        register_program("decode", lambda: decode.lower(
            params, cache, slots, active, rng).compile())
        # slot, (start,) n_real
        scalars = (i32,) * (3 if self.cache_kind == "paged" else 2)
        for bucket in self.prefill_buckets:
            tokens = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            register_program(
                f"prefill/{bucket}",
                lambda tokens=tokens: prefill.lower(
                    params, cache, tokens, *scalars, rng).compile())

    # -- state -------------------------------------------------------------
    def init_cache(self):
        """Fresh resident cache of the configured kind (``KVCache`` or
        ``PagedKVCache`` — the step programs take either; the scheduler
        owns the paged kind's allocator/radix control plane)."""
        cache = self._create_cache(
            self.cfg, n_slots=self.n_slots, max_len=self.max_len,
            dtype=self.cache_dtype,
        )
        if self.cache_sharding is not None:
            cache = cache.placed(self.cache_sharding)
        return cache

    def init_draft_cache(self) -> Optional[KVCache]:
        """Slotted cache for the separate draft model (None when
        self-drafting or speculation is off — self-drafting shares the
        target cache)."""
        if self.draft_model is None:
            return None
        cache = KVCache.create(
            self.draft_model.cfg, n_slots=self.n_slots,
            max_len=self.max_len, dtype=self.cache_dtype,
        )
        if self.cache_sharding is not None:
            cache = cache.placed(self.cache_sharding)
        return cache

    def _place_like(self, current, new, max_staging_bytes):
        """Redistribute ``new`` onto ``current``'s exact placement."""
        cur_leaves, cur_def = jax.tree_util.tree_flatten(current)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if cur_def != new_def:
            raise ValueError("swap_params: tree structure mismatch")
        for c, n in zip(cur_leaves, new_leaves):
            if tuple(c.shape) != tuple(n.shape) or \
                    np.dtype(c.dtype) != np.dtype(n.dtype):
                raise ValueError(
                    f"swap_params: leaf mismatch — have "
                    f"{tuple(c.shape)}/{np.dtype(c.dtype)}, got "
                    f"{tuple(n.shape)}/{np.dtype(n.dtype)}"
                )
        shardings = jax.tree_util.tree_unflatten(cur_def, [
            c.sharding if isinstance(c, jax.Array) else None
            for c in cur_leaves
        ])
        plan = plan_tree(new, shardings, max_staging_bytes=max_staging_bytes)
        placed = redistribute_tree(new, shardings, plan=plan)
        # leaves the engine held on host stay host-resident, so every
        # compiled program's (shape, dtype, sharding) signature is unchanged
        placed_leaves = jax.tree_util.tree_flatten(placed)[0]
        out = [
            p if isinstance(c, jax.Array) else np.asarray(jax.device_get(p))
            for c, p in zip(cur_leaves, placed_leaves)
        ]
        return jax.tree_util.tree_unflatten(cur_def, out), plan.cost

    def swap_params(self, params, *, draft_params=None,
                    max_staging_bytes: Optional[int] = None):
        """Reshard-while-serving: install new weights between steps.

        ``params`` may live on any mesh/layout — or be host numpy — as
        long as tree structure, shapes, and dtypes match the current
        weights. Each leaf is redistributed (``redistribute/`` planner)
        onto the CURRENT leaf's placement, so the compiled prefill/decode/
        spec programs see an identical (shape, dtype, sharding) signature:
        no recompile, and since redistribution is pure data movement the
        swap is bit-exact — a greedy stream continues token-identically
        when the new values equal the old. Safe whenever no step call is
        in flight (the scheduler calls this between steps).

        Returns the planner's :class:`TransferCost` for the move.
        """
        placed, cost = self._place_like(self.params, params,
                                        max_staging_bytes)
        self.params = placed
        if draft_params is not None:
            if self.draft_params is None:
                raise ValueError(
                    "swap_params: draft_params given but engine has no "
                    "separate draft model"
                )
            placed_d, cost_d = self._place_like(
                self.draft_params, draft_params, max_staging_bytes
            )
            self.draft_params = placed_d
            cost = cost + cost_d
        return cost

    def _next_rng(self) -> Tuple[jax.Array, np.uint32]:
        """The last argument of the next step's program: the engine's base
        key and the step's counter, which the program folds into it
        (``_step_key``). A host value: nothing runs on the device here."""
        self._rng_calls += 1
        return self._rng, np.uint32(self._rng_calls)

    # -- steps -------------------------------------------------------------
    def prefill_bucket(self, n: int) -> int:
        """Smallest compiled prompt bucket holding ``n`` tokens."""
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"prompt length {n} exceeds prefill_len {self.prefill_len}"
        )

    def _pad_prompt(self, prompt: np.ndarray) -> Tuple[np.ndarray, int]:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = prompt.shape[0]
        if n == 0:
            raise ValueError("empty prompt")
        if n > self.prefill_len:
            raise ValueError(
                f"prompt length {n} exceeds prefill_len {self.prefill_len}"
            )
        if n >= self.max_len:
            raise ValueError(
                f"prompt length {n} leaves no room to generate "
                f"(max_len {self.max_len})"
            )
        padded = np.zeros((1, self.prefill_bucket(n)), np.int32)
        padded[0, :n] = prompt
        return padded, n

    def prefill(
        self, cache, slot: int, prompt: np.ndarray, *, cached_len: int = 0,
        request_id: Optional[int] = None,
    ) -> Tuple[Any, int]:
        """Admit ``prompt`` (1-D int tokens) into ``slot``; returns the
        updated cache and the FIRST generated token.

        ``cached_len`` (paged cache only) marks a radix prefix hit: the
        first ``cached_len`` positions are already resident in the slot's
        attached page chain, so only the tail ``prompt[cached_len:]`` runs
        through the prefill program (padded to ITS bucket: a hit on a long
        prompt prefills through a much smaller compiled one, the cached-
        prefix TTFT win). ``request_id`` labels ``pdt.engine.prefill``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = prompt.shape[0]
        cached_len = int(cached_len)
        if cached_len:
            if self.cache_kind != "paged":
                raise ValueError("cached_len requires cache_kind='paged'")
            if not (0 <= cached_len < n):
                raise ValueError(
                    f"cached_len {cached_len} must be in [0, {n})"
                )
            if n > self.prefill_len:
                raise ValueError(
                    f"prompt length {n} exceeds prefill_len "
                    f"{self.prefill_len}"
                )
            if n >= self.max_len:
                raise ValueError(
                    f"prompt length {n} leaves no room to generate "
                    f"(max_len {self.max_len})"
                )
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} out of range")
        stats = {} if request_id is None else {"request_id": request_id}
        with span("engine.prefill", **stats) as whole:
            with span("engine.prefill.dispatch") as dispatch:
                with span("engine.prefill.dispatch.inputs"):
                    padded, n_real = self._pad_prompt(prompt[cached_len:])
                    whole.set_metadata(
                        **self._counts(padded.shape[1], n_real, cached_len))
                    # typed on the host: a weak-typed Python int would be
                    # a second executable beside the described one
                    scalars = [np.int32(i) for i in (
                        (slot, n_real) if self.cache_kind != "paged"
                        else (slot, cached_len, n_real))]
                    rng = self._next_rng()
                with span("engine.prefill.dispatch.call"):
                    cache, tok = self._prefill(
                        self.params, cache, padded, *scalars, rng)
                dispatch.set_metadata(executables=self._prefill._cache_size())
            with span("engine.prefill.read"):
                tok = int(tok)  # waits for the device
        return cache, tok

    def prefill_draft(self, draft_cache: KVCache, slot: int,
                      prompt: np.ndarray) -> KVCache:
        """``prompt`` into the draft model's cache (same bucket; no token)."""
        if self._draft_prefill is None:
            raise RuntimeError("no separate draft model configured")
        padded, n = self._pad_prompt(prompt)
        return self._draft_prefill(
            self.draft_params, draft_cache, padded,
            np.int32(slot), np.int32(n),
        )

    def decode(self, cache: KVCache, last_tokens: np.ndarray,
               active: np.ndarray) -> Tuple[KVCache, np.ndarray]:
        """One decode step for the whole slot batch: ``last_tokens [S]`` is
        each active slot's newest token, ``active [S]`` bool. Returns the
        cache and the sampled tokens ``[S]`` (garbage at inactive slots)."""
        with span("engine.decode") as whole:
            with span("engine.decode.dispatch") as dispatch:
                with span("engine.decode.dispatch.inputs"):
                    last = np.asarray(last_tokens, np.int32)
                    act = np.asarray(active, bool)
                    rng = self._next_rng()
                with span("engine.decode.dispatch.call"):
                    cache, toks = self._decode(
                        self.params, cache, last, act, rng)
                dispatch.set_metadata(executables=self._decode._cache_size())
            with span("engine.decode.read"):
                toks = np.asarray(toks)  # waits for the device, copies back
            if self._step_stats:
                whole.set_metadata(**{
                    name: int(n) for name, n in zip(
                        self._step_stats, toks[self.n_slots:])})
        return cache, toks[:self.n_slots]

    def spec_decode(
        self,
        cache: KVCache,
        draft_cache: Optional[KVCache],
        last_tokens: np.ndarray,
        prev_tokens: np.ndarray,
        active: np.ndarray,
    ) -> Tuple[KVCache, Optional[KVCache], np.ndarray, np.ndarray,
               np.ndarray]:
        """One speculative step: draft k, verify once, accept a prefix.

        Returns ``(cache, draft_cache, emitted [S, k+1], counts [S],
        prev_tokens [S])``. Each active slot emitted ``counts[slot]``
        tokens (1..k+1): read ``emitted[slot, :counts[slot]]``; entries
        past the count are garbage. ``counts - 1`` is the per-slot accepted
        draft count. ``prev_tokens`` is the token now at ``lengths - 1``
        (thread it back into the next call; only the separate-draft
        catch-up consumes it)."""
        if self._spec is None:
            raise RuntimeError("spec_k=0 — speculative decoding disabled")
        with span("engine.decode"):
            with span("engine.decode.dispatch") as dispatch:
                with span("engine.decode.dispatch.inputs"):
                    last = np.asarray(last_tokens, np.int32)
                    prev = np.asarray(prev_tokens, np.int32)
                    act = np.asarray(active, bool)
                    rng = self._next_rng()
                with span("engine.decode.dispatch.call"):
                    if self.draft_model is None:
                        cache, emitted, counts, prev_next = self._spec(
                            self.params, cache, last, act, rng
                        )
                        dcache = draft_cache
                    else:
                        step = self._spec(
                            self.params, self.draft_params, cache,
                            draft_cache, last, prev, act, rng,
                        )
                        cache, dcache, emitted, counts, prev_next = step
                dispatch.set_metadata(executables=self._spec._cache_size())
            with span("engine.decode.read"):
                out = (np.asarray(emitted), np.asarray(counts),
                       np.asarray(prev_next))
        return (cache, dcache, *out)

    def _counts(self, bucket: int, n_real: int,
                cached_len: int = 0) -> dict:
        """What the ``engine.prefill`` span says of a prompt of ``n_real``
        tokens padded to ``bucket``: both, and ``n_computed``, the
        positions the model's tokenwise loops run for it. A model whose
        loops end with the prompt's last real token says how far they go
        (``model.prefill_computed``, the expression that bounds the loop
        itself: ``models.exaone_moe.computed_tokens``); any other computes
        the bucket. A paged engine adds ``cached_len`` (the positions
        before these that were served from shared pages) and ``cold``,
        which program ran (``_PagedPrefill``). (Down here, below every
        function that a compiled kernel's recorded call stack passes
        through: a line added above them moves every serving program's
        compile-cache key.)"""
        computed = getattr(self.model, "prefill_computed", None)
        counts = dict(bucket=bucket, n_real=n_real, n_computed=int(
            computed(bucket, n_real)) if computed else bucket)
        if self.cache_kind == "paged":
            counts.update(cached_len=cached_len, cold=int(
                self._prefill.is_cold(bucket, cached_len)))
        return counts


def _slotted_cache_class(model, cache_kind, cache_sharding, spec_k):
    """The class of the slotted cache a model is served from: ``KVCache``
    unless the model names its own (``model.cache_class``; ``models.xing4``
    names ``LatentCache``, ``models.exaone_moe`` ``WindowedKVCache``,
    ``models.kimi_linear`` ``HybridStateCache`` or, with no recurrent
    layer, ``LatentCache``). What such a cache does not support raises
    here, at construction, with a sentence (the class's
    ``UNSUPPORTED_BECAUSE``); ``cache_kind='paged'`` is supported where the
    class names a paged twin (``_paged_twin``). A class's ``STEP_STATS``
    name the counts its model leaves in ``cache.step_stats`` each step: the
    decode program sends them to the host behind the step's tokens, in the
    one read the step makes anyway, onto the ``pdt.engine.decode`` span."""
    slotted = getattr(model, "cache_class", KVCache)
    if slotted is not KVCache:
        unsupported = [what for what, asked in (
            ("cache_kind='paged'",
             cache_kind == "paged" and not hasattr(slotted, "paged_class")),
            ("cache_sharding", cache_sharding is not None),
            ("spec_k > 0", spec_k > 0)) if asked]
        if unsupported:
            raise ValueError(
                f"{type(model).__name__} is served from a "
                f"{slotted.__name__} only, whole on one device and one "
                f"token a step: {', '.join(unsupported)} not supported "
                f"({getattr(slotted, 'UNSUPPORTED_BECAUSE', _LATENT_LEFT)})")
    return slotted


_LATENT_LEFT = ("a tensor-parallel plan and drafting for a latent cache, "
                "pages for rings, are ROADMAP items")


def _paged_twin(slotted):
    """The class that holds in pages what ``slotted`` holds in slots: the
    one a cache class names itself (``LatentCache.paged_class``), and
    ``PagedKVCache`` for ``KVCache``."""
    return PagedKVCache if slotted is KVCache else slotted.paged_class()


class _PagedPrefill:
    """The two prefill programs of a paged engine, standing where the
    slotted engine's one ``jax.jit`` stands (``__call__``, ``lower`` and
    ``_cache_size`` are what the engine and its tests use of it), and the
    choice between them, made on the host where ``start`` (the cached
    length) is known:

      * a bucket up to ``tail_len`` runs the TAIL program at ``start``:
        the new rows behind whatever the chain's pages hold, nothing or a
        shared prefix;
      * a longer bucket at ``start == 0`` runs the COLD program: a fresh
        sequence that reads nothing of the pool;
      * a longer tail behind a prefix (a document whose last pages were
        reclaimed) goes through the tail program ``tail_len`` tokens at a
        time; the last piece gives the token.

    So a bucket is one program, and ``prefill/<bucket>`` names it."""

    def __init__(self, fn, bucket_of, tail_len: int):
        self.tail = jax.jit(fn, donate_argnums=(1,))
        # under the function's own name: a trace finds a prefill's runs by it
        self.cold = jax.jit(
            functools.wraps(fn)(functools.partial(fn, cold=True)),
            donate_argnums=(1,))
        self.bucket_of, self.tail_len = bucket_of, tail_len

    def is_cold(self, bucket: int, start: int) -> bool:
        return bucket > self.tail_len and start == 0

    def _cache_size(self) -> int:
        return self.tail._cache_size() + self.cold._cache_size()

    def lower(self, params, cache, tokens, *rest):
        program = self.cold if tokens.shape[1] > self.tail_len else self.tail
        return program.lower(params, cache, tokens, *rest)

    def __call__(self, params, cache, tokens, slot, start, n_real, rng):
        whole = self.tail if tokens.shape[1] <= self.tail_len else \
            self.cold if start == 0 else None
        if whole is not None:
            return whole(params, cache, tokens, slot, start, n_real, rng)
        tok = None
        for at in range(0, int(n_real), self.tail_len):
            n = min(self.tail_len, int(n_real) - at)
            piece = np.zeros((1, self.bucket_of(n)), np.int32)
            piece[0, :n] = tokens[0, at:at + n]
            cache, tok = self.tail(params, cache, piece, slot,
                                   np.int32(start + at), np.int32(n), rng)
        return cache, tok
