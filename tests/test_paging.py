"""Paged KV cache: allocator/radix units, op parity, serving parity.

Correctness is anchored the same way as the slotted serving tests —
against the already-oracled slotted path: the paged scheduler must emit
bit-identical token streams for every request (greedy decode leaves no
tolerance), including radix prefix hits, whole-prompt COW forks,
page-recycling eviction churn, and speculative rollback. On top of that
sit the paging-only invariants: the allocator's reservation ledger must
balance, recycled pages must never leak stale bytes into a new owner, and
the paged cache must admit strictly more concurrent sequences than the
slotted cache at the same page budget.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.ops import (
    cached_attention,
    paged_cached_attention,
    paged_decode_attention,
)
from pytorch_distributed_tpu.serving import (
    InferenceEngine,
    KVCache,
    Request,
    Scheduler,
)
from pytorch_distributed_tpu.serving.paging import (
    CapacityError,
    PageAllocator,
    PagedKVCache,
    RadixTree,
    TRASH_PAGE,
)

pytestmark = pytest.mark.paging


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=97, n_positions=48, n_embd=48, n_layer=2,
                     n_head=4, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables


def run_requests(model, variables, reqs, *, cache_kind, n_slots=2,
                 max_len=32, prefill_len=8, page_size=4, n_pages=None,
                 spec_k=0, draft_layers=0):
    """Run requests through a scheduler; returns (token streams by id,
    scheduler)."""
    kw = {}
    if cache_kind == "paged":
        kw = {"page_size": page_size, "n_pages": n_pages}
    if spec_k:
        kw.update(spec_k=spec_k, draft_layers=draft_layers)
    eng = InferenceEngine(
        model, variables, n_slots=n_slots, max_len=max_len,
        prefill_len=prefill_len, cache_kind=cache_kind, **kw,
    )
    sched = Scheduler(eng, emit_events=False)
    for prompt, n_new in reqs:
        sched.submit(Request(prompt=prompt, max_new_tokens=n_new))
    finished = sched.run()
    return {f.request_id: f.tokens for f in finished}, sched


# -- PagedKVCache pytree ---------------------------------------------------
def test_paged_cache_shapes_and_trash_eviction(tiny):
    model, _ = tiny
    cache = PagedKVCache.create(model.cfg, n_slots=3, max_len=16,
                                page_size=4)
    assert cache.k.shape == (2, 3 * 4 + 1, 4, 4, 12)
    assert cache.v.shape == cache.k.shape
    assert cache.block_tables.shape == (3, 4)
    assert (cache.n_pages, cache.page_size, cache.max_pages) == (13, 4, 4)
    assert cache.max_len == 16
    assert cache.bytes_per_page() == 2 * 2 * 4 * 4 * 12 * 4  # fp32
    cache = cache.replace(
        lengths=cache.lengths.at[1].set(9),
        block_tables=cache.block_tables.at[1].set(
            jnp.array([5, 6, 7, 8], jnp.int32)
        ),
    )
    cache = cache.evict(1)
    assert int(cache.lengths[1]) == 0
    # the table row is zeroed: the evicted slot's padding-lane writes and
    # gathers land in the trash page, never a live page
    assert (np.asarray(cache.block_tables[1]) == TRASH_PAGE).all()


def test_paged_cache_rejects_bad_shapes(tiny):
    model, _ = tiny
    with pytest.raises(ValueError, match="n_positions"):
        PagedKVCache.create(model.cfg, n_slots=2, max_len=4096)
    with pytest.raises(ValueError, match="n_pages"):
        PagedKVCache.create(model.cfg, n_slots=1, max_len=8, page_size=4,
                            n_pages=1)


# -- PageAllocator ---------------------------------------------------------
def test_allocator_reservation_ledger():
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=2, max_pages=8)
    assert alloc.free_pages == 8 and alloc.available_pages == 8
    # admit reserves the worst-case span up front...
    assert alloc.admit(0, [], 3)
    assert alloc.free_pages == 8 and alloc.available_pages == 5
    # ...so growth draws credit, never new pool capacity
    for _ in range(3):
        alloc.alloc(0)
    assert alloc.reserved[0] == 0 and alloc.available_pages == 5
    assert len(alloc.chain(0)) == 3
    # a newcomer needing more than the uncommitted remainder is refused
    assert not alloc.admit(1, [], 6)
    assert alloc.admit(1, [], 5)
    alloc.check()
    # eviction returns both the pages and the (voided) reservation
    alloc.free_slot(1)
    alloc.free_slot(0)
    assert alloc.available_pages == 8
    assert (alloc.tables == TRASH_PAGE).all()
    alloc.check()


def test_allocator_exhaustion_raises():
    alloc = PageAllocator(n_pages=3, page_size=4, n_slots=1, max_pages=4)
    assert alloc.admit(0, [], 2)
    alloc.alloc(0)
    alloc.alloc(0)
    with pytest.raises(CapacityError):
        alloc.alloc(0)


def test_allocator_release_tail_refunds_credit():
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=1, max_pages=4)
    assert alloc.admit(0, [], 4)
    alloc.ensure(0, 16)
    assert alloc.reserved[0] == 0 and len(alloc.chain(0)) == 4
    # rollback to 6 positions: position 6 is the next write, its page
    # (entry 1) stays; entries 2 and 3 go back with their credit
    dropped = alloc.release_tail(0, 6)
    assert len(dropped) == 2
    assert len(alloc.chain(0)) == 2
    assert alloc.reserved[0] == 2
    # the refunded credit re-acquires the pages without touching the pool
    alloc.ensure(0, 16)
    assert alloc.reserved[0] == 0
    alloc.check()


def test_allocator_cow_preserves_shared_page():
    alloc = PageAllocator(n_pages=6, page_size=4, n_slots=2, max_pages=4)
    assert alloc.admit(0, [], 1)
    page = alloc.alloc(0)
    alloc.pin(page)        # the radix tree keeps the prompt page alive
    alloc.free_slot(0)
    assert alloc.refcount[page] == 1  # pinned: survived eviction
    # a second sequence admits the page by reference, then must fork it
    # before its own write can land there
    assert alloc.admit(1, [page], 2, cow_last=True)
    assert alloc.refcount[page] == 2
    pair = alloc.cow(1, 0)
    assert pair is not None and pair[0] == page
    assert alloc.refcount[page] == 1       # the pin remains
    assert alloc.chain(1)[0] == pair[1]    # slot re-pointed at the copy
    assert alloc.cow(1, 0) is None         # already exclusive
    alloc.check()


# -- RadixTree -------------------------------------------------------------
def test_radix_insert_match_and_stats():
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=1, max_pages=4)
    assert alloc.admit(0, [], 3)
    alloc.ensure(0, 12)
    pages = alloc.chain(0)
    tree = RadixTree(page_size=4)
    prompt = list(range(10))  # 2 full pages + a 2-token tail
    assert tree.insert(prompt, pages, alloc) == 2
    assert tree.n_nodes == 2
    # probe (touch=False) must not skew hit/miss stats
    assert tree.match(prompt, touch=False) == pages[:2]
    assert tree.hits == 0 and tree.misses == 0
    assert tree.match(prompt) == pages[:2]
    assert tree.hits == 1 and tree.cached_tokens == 8
    # a diverging prompt matches only the shared page-chunks
    assert tree.match(prompt[:4] + [96] * 6) == pages[:1]
    assert tree.match([42] * 8) == []
    assert tree.misses == 1


def test_radix_reclaim_drops_only_unshared_lru_leaves():
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=1, max_pages=4)
    assert alloc.admit(0, [], 3)
    alloc.ensure(0, 12)
    pages = alloc.chain(0)
    tree = RadixTree(page_size=4)
    tree.insert(list(range(12)), pages, alloc)
    # every page is shared with the live slot: nothing reclaimable
    assert tree.reclaim(alloc, 3) == 0
    alloc.free_slot(0)
    free_before = alloc.free_pages
    # now only the deepest leaf is a refcount-1 leaf; reclaim walks up
    assert tree.reclaim(alloc, 2) == 2
    assert alloc.free_pages == free_before + 2
    assert tree.n_nodes == 1
    tree.clear(alloc)
    assert alloc.free_pages == 8
    alloc.check()


# -- op parity -------------------------------------------------------------
def test_paged_prefill_op_matches_slotted():
    """Same math, different storage: the paged op gathering its chain must
    reproduce the dense slotted op (prefill T=5 then decode T=1) to
    float32 rounding — the slotted op contracts against its folded rows in
    another order — and hold the very same rows."""
    rng = np.random.default_rng(0)
    B, H, D, page, M = 2, 2, 4, 4, 3
    S = page * M
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    kp = jnp.zeros((8, page, H, D), jnp.float32)
    vp = jnp.zeros((8, page, H, D), jnp.float32)
    kc = jnp.zeros((1, B, S, H * D), jnp.float32)
    vc = jnp.zeros((1, B, S, H * D), jnp.float32)

    def rand(t):
        return jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)

    off = jnp.zeros((B,), jnp.int32)
    q, kn, vn = rand(5), rand(5), rand(5)
    out_p, kp, vp = paged_cached_attention(q, kn, vn, kp, vp, tables, off)
    out_s, kc, vc = cached_attention(q, kn, vn, kc, vc, 0, None)
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_s), rtol=1e-5, atol=1e-6)

    off = jnp.full((B,), 5, jnp.int32)
    q, kn, vn = rand(1), rand(1), rand(1)
    out_p, kp, vp = paged_cached_attention(q, kn, vn, kp, vp, tables, off)
    out_s, kc, vc = cached_attention(q, kn, vn, kc, vc, 0, off)
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_s), rtol=1e-5, atol=1e-6)
    # the pool holds exactly the dense cache's rows, page by page
    np.testing.assert_array_equal(
        np.asarray(kp[tables].reshape(B, S, H * D)), np.asarray(kc[0])
    )


def test_paged_decode_kernel_matches_reference():
    """The Pallas kernel (interpret mode off-TPU) must match the jnp
    reference for ragged lengths — including a chain whose tail entries
    are still the trash page."""
    rng = np.random.default_rng(1)
    B, H, D, page = 2, 2, 4, 4
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)  # seq1: 1 page + trash
    kp = jnp.zeros((6, page, H, D), jnp.float32)
    vp = jnp.zeros((6, page, H, D), jnp.float32)

    def rand(t):
        return jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)

    # prefill positions 0..5 (seq0) / 0..2 (seq1) via the reference op
    kn, vn = rand(6), rand(6)
    _, kp, vp = paged_cached_attention(rand(6), kn, vn, kp, vp, tables,
                                       jnp.zeros((B,), jnp.int32))
    lengths = jnp.asarray([6, 3], jnp.int32)  # the decode query positions
    q, kn, vn = rand(1), rand(1), rand(1)
    want, kp, vp = paged_cached_attention(q, kn, vn, kp, vp, tables, lengths)
    got = paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# -- the cache protocol: one method, the same on both kinds ----------------
KINDS = ("slotted", "paged")


def _seam_cache(kind, cfg, rng=None):
    """A two-slot cache of ``kind`` for ``cfg`` (12 positions a slot, each
    paged slot owning three pages of four); with ``rng`` its K and V hold
    noise, so that a write nobody asked for shows."""
    if kind == "slotted":
        cache = KVCache.create(cfg, n_slots=2, max_len=12)
    else:
        cache = PagedKVCache.create(cfg, n_slots=2, max_len=12, page_size=4)
        cache = cache.set_table_row(0, [1, 2, 3]).set_table_row(1, [4, 5, 6])
    if rng is not None:
        cache = cache.replace(
            k=jnp.asarray(rng.standard_normal(cache.k.shape), cache.k.dtype),
            v=jnp.asarray(rng.standard_normal(cache.v.shape), cache.v.dtype),
        )
    return cache


def _raw_op(kind, cache, layer, q, k_new, v_new, offset):
    """What the op under ``cache.attend`` returns when called on the raw
    arrays: ``(y, layer's K, layer's V)``."""
    if kind == "slotted":
        y, k, v = cached_attention(q, k_new, v_new, cache.k, cache.v, layer,
                                   offset)
        return y, k[layer], v[layer]
    return paged_cached_attention(q, k_new, v_new, cache.k[layer],
                                  cache.v[layer], cache.block_tables, offset)


def _qkv(rng, cfg, T):
    H, D = cfg.n_head, cfg.n_embd // cfg.n_head
    return [jnp.asarray(rng.standard_normal((2, T, H, D)), jnp.float32)
            for _ in range(3)]


@pytest.mark.parametrize("kind", KINDS)
def test_attend_writes_its_layer_only_and_returns_the_ops_result(tiny, kind):
    """``cache.attend(layer, ...)`` is the raw op on that layer and
    nothing else: the other layer, the lengths and (paged) the block
    tables come back bit-identical."""
    cfg = tiny[0].cfg
    rng = np.random.default_rng(11)
    cache = _seam_cache(kind, cfg, rng)
    q, k_new, v_new = _qkv(rng, cfg, 3)
    offset = jnp.asarray([2, 5], jnp.int32)
    y, new = cache.attend(1, q, k_new, v_new, offset)
    want_y, want_k, want_v = _raw_op(kind, cache, 1, q, k_new, v_new, offset)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(new.k[1]), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(new.v[1]), np.asarray(want_v))
    assert not np.array_equal(np.asarray(new.k[1]), np.asarray(cache.k[1]))
    assert type(new) is type(cache)
    old_rest = jax.tree.leaves(cache.replace(k=cache.k[0], v=cache.v[0]))
    new_rest = jax.tree.leaves(new.replace(k=new.k[0], v=new.v[0]))
    for a, b in zip(old_rest, new_rest):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", KINDS)
def test_truncated_forward_leaves_later_layers_bit_identical(tiny, kind):
    """``n_layers=1`` (the self-drafting draft) writes layer 0 and returns
    layer 1 of the cache as it came."""
    model, variables = tiny
    rng = np.random.default_rng(12)
    cache = _seam_cache(kind, model.cfg, rng)
    tokens = jnp.asarray(rng.integers(0, 97, (2, 1)), jnp.int32)
    _, new = model.apply(variables, tokens, kv_cache=cache,
                         position_offset=jnp.asarray([3, 7], jnp.int32),
                         n_layers=1)
    for a, b in ((cache.k, new.k), (cache.v, new.v)):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


@pytest.mark.parametrize("kind", KINDS)
def test_no_offset_means_every_sequence_fresh_from_position_zero(tiny, kind):
    """``position_offset=None`` through ``attend``: rows land at positions
    ``0..T-1`` and ``y`` is what an explicit zero offset gives on an empty
    cache (to float32 rounding: the slotted op's fresh path contracts
    T x T, its offset path against the folded rows)."""
    cfg = tiny[0].cfg
    rng = np.random.default_rng(13)
    cache = _seam_cache(kind, cfg)
    q, k_new, v_new = _qkv(rng, cfg, 5)
    y_none, c_none = cache.attend(0, q, k_new, v_new, None)
    y_zero, c_zero = cache.attend(0, q, k_new, v_new,
                                  jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(y_none), np.asarray(y_zero),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(c_none), jax.tree.leaves(c_zero)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(c_none.k[0]).any() and not np.asarray(c_none.k[1]).any()


# -- serving parity against the slotted oracle ------------------------------
def test_paged_scheduler_matches_slotted_with_shared_prefixes(tiny):
    """Mixed churn with repeated prefixes: the paged path (radix hits,
    COW fork on the whole-prompt repeat, page recycling across evictions)
    must emit the slotted scheduler's exact token streams."""
    model, variables = tiny
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 97, 8).astype(np.int32)  # 2 full pages
    reqs = [
        (rng.integers(0, 97, 5).astype(np.int32), 6),
        (np.concatenate([shared, rng.integers(0, 97, 3)]).astype(np.int32), 5),
        (np.concatenate([shared, rng.integers(0, 97, 2)]).astype(np.int32), 4),
        (shared.copy(), 6),  # whole prompt cached -> COW fork path
        (rng.integers(0, 97, 7).astype(np.int32), 3),
    ]
    want, _ = run_requests(model, variables, reqs, cache_kind="slotted",
                           prefill_len=16)
    got, sched = run_requests(model, variables, reqs, cache_kind="paged",
                              prefill_len=16)
    assert got == want
    s = sched.stats()
    assert s["cache_kind"] == "paged"
    assert sched.radix.hits >= 2  # requests 2 and 3 reuse request 1's pages
    assert sched.prefill_tokens_cached > 0
    sched.allocator.check()
    assert sched.allocator.reserved.sum() == 0  # every credit drained


def test_paged_slot_reuse_does_not_leak(tiny):
    """One slot, two unrelated prompts: the second request decodes over
    pages recycled from the first (LIFO free list) and must match a fresh
    slotted generation — masking + page ownership, not zeroing, is the
    isolation boundary."""
    model, variables = tiny
    reqs = [
        (np.array([60, 61, 62, 63], np.int32), 10),
        (np.array([7, 1], np.int32), 8),
    ]
    want, _ = run_requests(model, variables, reqs, cache_kind="slotted",
                           n_slots=1)
    got, sched = run_requests(model, variables, reqs, cache_kind="paged",
                              n_slots=1)
    assert got == want
    sched.allocator.check()


def test_cow_fork_then_evict_recycled_page_isolation(tiny):
    """The eviction-isolation oracle through the COW path: admit a prompt
    twice (second admission COW-forks the shared last page), evict both,
    drop the radix pins so every page recycles, then admit an unrelated
    prompt over the recycled pool — its stream must match a fresh slotted
    generation (no stale bytes reachable)."""
    model, variables = tiny
    prompt = np.arange(10, 18, dtype=np.int32)  # exactly 2 full pages
    fresh = np.array([90, 91, 92], np.int32)
    want, _ = run_requests(model, variables, [(fresh, 9)],
                           cache_kind="slotted", n_slots=1)

    eng = InferenceEngine(model, variables, n_slots=1, max_len=32,
                          prefill_len=8, cache_kind="paged", page_size=4)
    sched = Scheduler(eng, emit_events=False)
    sched.submit(Request(prompt=prompt, max_new_tokens=4))
    sched.submit(Request(prompt=prompt.copy(), max_new_tokens=4))
    sched.run()
    assert sched.radix.hits == 1  # the repeat fully hit -> COW fork ran
    sched.radix.clear(sched.allocator)
    assert sched.allocator.free_pages == sched.allocator.n_pages - 1
    sched.submit(Request(prompt=fresh, max_new_tokens=9))
    finished = sched.run()
    assert {f.request_id: f.tokens for f in finished} == {2: want[0]}
    sched.allocator.check()


def test_spec_decode_paged_parity_and_page_release(tiny):
    """Speculative decode over the paged cache: streams identical to the
    slotted spec path, and the page-granular rollback returns every
    rejected-span page (ledger drains to zero, pool restored)."""
    model, variables = tiny
    rng = np.random.default_rng(5)
    reqs = [
        (rng.integers(0, 97, int(rng.integers(2, 8))).astype(np.int32),
         int(rng.integers(3, 9)))
        for _ in range(5)
    ]
    want, _ = run_requests(model, variables, reqs, cache_kind="slotted",
                           spec_k=3, draft_layers=1)
    got, sched = run_requests(model, variables, reqs, cache_kind="paged",
                              spec_k=3, draft_layers=1)
    assert got == want
    alloc = sched.allocator
    alloc.check()
    assert alloc.reserved.sum() == 0
    # all non-radix pages returned to the pool after the drain
    pinned = (alloc.refcount[1:] > 0).sum()
    assert alloc.free_pages == alloc.n_pages - 1 - pinned


def _capacity_peak(model, variables, *, cache_kind, budget_pages, page_size,
                   max_len, n_requests):
    max_pages = -(-max_len // page_size)
    if cache_kind == "slotted":
        eng = InferenceEngine(model, variables,
                              n_slots=max(1, budget_pages // max_pages),
                              max_len=max_len, prefill_len=8)
    else:
        eng = InferenceEngine(model, variables, n_slots=n_requests,
                              max_len=max_len, prefill_len=8,
                              cache_kind="paged", page_size=page_size,
                              n_pages=budget_pages + 1)
    sched = Scheduler(eng, emit_events=False)
    rng = np.random.default_rng(7)
    for i in range(n_requests):
        sched.submit(Request(prompt=rng.integers(0, 97, 2 + 2 * (i % 3)),
                             max_new_tokens=4))
    peak = 0
    while sched.has_work:
        sched.step()
        peak = max(peak, sched.n_active)
    return peak


def test_paged_capacity_beats_slotted_at_same_budget(tiny):
    """The tentpole capacity claim, small: at one fixed page budget the
    paged cache's span reservations admit strictly more concurrent
    mixed-length sequences than whole-max_len slot reservations."""
    model, variables = tiny
    kw = dict(budget_pages=12, page_size=4, max_len=16, n_requests=8)
    slotted = _capacity_peak(model, variables, cache_kind="slotted", **kw)
    paged = _capacity_peak(model, variables, cache_kind="paged", **kw)
    assert paged > slotted, (paged, slotted)


@pytest.mark.slow
@pytest.mark.parametrize("budget_pages", [8, 12, 16])
def test_paged_capacity_sweep(tiny, budget_pages):
    """Capacity holds across budgets (and degenerates gracefully: the
    paged peak can never be worse than the slotted one)."""
    model, variables = tiny
    kw = dict(budget_pages=budget_pages, page_size=4, max_len=16,
              n_requests=8)
    slotted = _capacity_peak(model, variables, cache_kind="slotted", **kw)
    paged = _capacity_peak(model, variables, cache_kind="paged", **kw)
    assert paged >= slotted
    assert paged > slotted or budget_pages < 12


def test_paged_backpressure_is_deterministic(tiny):
    """A pool too small for two worst-case spans serializes admissions
    (FIFO head blocks; no head-of-line skip) and still completes every
    request with the slotted streams."""
    model, variables = tiny
    reqs = [(np.arange(4, dtype=np.int32) + i, 6) for i in range(3)]
    want, _ = run_requests(model, variables, reqs, cache_kind="slotted",
                           n_slots=2, max_len=16)
    got, sched = run_requests(model, variables, reqs, cache_kind="paged",
                              n_slots=2, max_len=16, n_pages=4)
    assert got == want
    sched.allocator.check()
