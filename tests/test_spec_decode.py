"""Speculative decoding: draft/verify correctness over the slotted cache.

The anchor is the same teacher-forcing oracle as test_serving.py: GREEDY
speculative decode must emit exactly the argmax stream of the full
uncached forward, token for token, REGARDLESS of draft quality — the
accept rule guarantees it (an accepted draft token IS the target argmax;
the first mismatch position emits the target argmax instead). Any bug in
the scratch-position drafting, the [S, k+1] verify, the rollback/commit
arithmetic, or the scheduler's span consumption breaks the equality.
"""

import functools
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.observability import recent_events
from pytorch_distributed_tpu.serving import (
    DraftConfig,
    InferenceEngine,
    Request,
    SamplingParams,
    Scheduler,
    greedy_accept,
    rejection_accept,
)
from pytorch_distributed_tpu.serving.kv_cache import KVCache

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=97, n_positions=96, n_embd=48, n_layer=2,
                     n_head=4, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables


@pytest.fixture(scope="module")
def tiny_draft():
    cfg = GPT2Config(vocab_size=97, n_positions=96, n_embd=24, n_layer=1,
                     n_head=2, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))
    return model, variables


@functools.lru_cache(maxsize=None)
def _oracle_fwd(model):
    return jax.jit(model.apply)


def greedy_oracle(model, variables, prompt, n_tokens):
    """Teacher forcing, zero-padded to ``n_positions`` and jitted once per
    model — causal attention makes the padded tail invisible to the
    position being read."""
    fwd = _oracle_fwd(model)
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(n_tokens):
        buf = np.zeros((1, model.cfg.n_positions), np.int32)
        buf[0, : len(seq)] = seq
        logits = fwd(variables, jnp.asarray(buf))
        nxt = int(jnp.argmax(logits[0, len(seq) - 1].astype(jnp.float32)))
        out.append(nxt)
        seq.append(nxt)
    return out


def spec_generate(engine, prompt, n_tokens, slot=0):
    """Generate via prefill + spec_decode rounds, only `slot` active."""
    cache = engine.init_cache()
    dcache = engine.init_draft_cache()
    if dcache is not None:
        dcache = engine.prefill_draft(dcache, slot, prompt)
    cache, tok = engine.prefill(cache, slot, prompt)
    got = [tok]
    last = np.zeros(engine.n_slots, np.int32)
    prev = np.zeros(engine.n_slots, np.int32)
    active = np.zeros(engine.n_slots, bool)
    last[slot], prev[slot], active[slot] = tok, int(prompt[-1]), True
    while len(got) < n_tokens:
        cache, dcache, emitted, counts, prev_next = engine.spec_decode(
            cache, dcache, last, prev, active
        )
        n = int(counts[slot])
        got.extend(int(t) for t in emitted[slot, :n])
        last[slot] = emitted[slot, n - 1]
        prev[slot] = prev_next[slot]
    return got[:n_tokens]


# -- acceptance math -------------------------------------------------------
def test_greedy_accept_counts_matching_prefix():
    V = 11
    # target argmax per position: [3, 5, 7, 2]
    logits = np.full((1, 4, V), -5.0, np.float32)
    for i, t in enumerate([3, 5, 7, 2]):
        logits[0, i, t] = 5.0
    # draft [3, 5, 9]: first two match, third doesn't -> accepts = 2
    accepts, emitted = greedy_accept(
        jnp.asarray(logits), jnp.asarray([[3, 5, 9]], jnp.int32)
    )
    assert int(accepts[0]) == 2
    np.testing.assert_array_equal(np.asarray(emitted), [[3, 5, 7, 2]])
    # consuming accepts+1 = 3 tokens yields [3, 5, 7] — the greedy stream


def test_rejection_accept_full_accept_when_draft_equals_target():
    """p_d == p_t makes the accept test u * p < p always true, so every
    proposal survives and position k emits the bonus from p_t[k]."""
    rng = np.random.default_rng(0)
    S, k, V = 3, 4, 13
    pt = rng.dirichlet(np.ones(V), (S, k + 1)).astype(np.float32)
    pd = pt[:, :k]
    draft = rng.integers(0, V, (S, k)).astype(np.int32)
    accepts, emitted = rejection_accept(
        jnp.asarray(pt), jnp.asarray(pd), jnp.asarray(draft),
        jax.random.key(0),
    )
    np.testing.assert_array_equal(np.asarray(accepts), [k] * S)
    np.testing.assert_array_equal(np.asarray(emitted)[:, :k], draft)
    assert all(0 <= int(t) < V for t in np.asarray(emitted)[:, k])


def test_rejection_accept_rejects_impossible_tokens():
    """A draft token with zero target probability must be rejected and the
    replacement drawn from the target's support."""
    S, k, V = 1, 2, 8
    pt = np.zeros((S, k + 1, V), np.float32)
    pt[..., 0] = 1.0          # target is a point mass on token 0
    pd = np.zeros((S, k, V), np.float32)
    pd[..., 5] = 1.0          # draft always proposes token 5
    draft = np.full((S, k), 5, np.int32)
    accepts, emitted = rejection_accept(
        jnp.asarray(pt), jnp.asarray(pd), jnp.asarray(draft),
        jax.random.key(1),
    )
    assert int(accepts[0]) == 0
    assert int(np.asarray(emitted)[0, 0]) == 0  # leftover == target


def test_draft_config_validation():
    DraftConfig(k=2, draft_layers=1).validate(2)
    with pytest.raises(ValueError, match="spec_k"):
        DraftConfig(k=0, draft_layers=1).validate(2)
    with pytest.raises(ValueError, match="exactly one draft source"):
        DraftConfig(k=2).validate(2)
    with pytest.raises(ValueError, match="exactly one draft source"):
        DraftConfig(k=2, draft_layers=1, use_draft_model=True).validate(2)
    with pytest.raises(ValueError, match="draft_layers"):
        DraftConfig(k=2, draft_layers=3).validate(2)


def test_engine_spec_validation(tiny, tiny_draft):
    model, variables = tiny
    dmodel, dvars = tiny_draft
    with pytest.raises(ValueError, match="require spec_k"):
        InferenceEngine(model, variables, draft_layers=1)
    with pytest.raises(ValueError, match="draft_params"):
        InferenceEngine(model, variables, spec_k=2, draft_model=dmodel)
    with pytest.raises(ValueError, match="no room"):
        InferenceEngine(model, variables, max_len=3, prefill_len=2,
                        spec_k=2, draft_layers=1)
    bad_cfg = GPT2Config(vocab_size=96, n_positions=96, n_embd=24,
                         n_layer=1, n_head=2)
    with pytest.raises(ValueError, match="vocab"):
        InferenceEngine(model, variables, spec_k=2,
                        draft_model=GPT2(bad_cfg), draft_params=dvars)


# -- the greedy parity oracle ----------------------------------------------
@pytest.mark.parametrize("spec_k,draft_layers", [(1, 1), (2, 1), (3, 2)])
def test_self_draft_greedy_matches_oracle(tiny, spec_k, draft_layers):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=64,
                             prefill_len=8, spec_k=spec_k,
                             draft_layers=draft_layers)
    prompt = np.array([5, 17, 3, 9, 44], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 14)
    assert spec_generate(engine, prompt, 14, slot=1) == oracle


def test_separate_draft_greedy_matches_oracle(tiny, tiny_draft):
    model, variables = tiny
    dmodel, dvars = tiny_draft
    engine = InferenceEngine(model, variables, n_slots=2, max_len=64,
                             prefill_len=8, spec_k=2,
                             draft_model=dmodel, draft_params=dvars)
    prompt = np.array([7, 1, 60, 2], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 14)
    assert spec_generate(engine, prompt, 14) == oracle


def test_full_layer_self_draft_accepts_everything(tiny):
    """draft_layers == n_layer makes the draft the target itself: every
    greedy proposal is the target argmax, so every round accepts all k."""
    model, variables = tiny
    k = 3
    engine = InferenceEngine(model, variables, n_slots=1, max_len=64,
                             prefill_len=8, spec_k=k,
                             draft_layers=model.cfg.n_layer)
    cache = engine.init_cache()
    prompt = np.array([5, 17, 3], np.int32)
    cache, tok = engine.prefill(cache, 0, prompt)
    last = np.array([tok], np.int32)
    prev = np.array([int(prompt[-1])], np.int32)
    active = np.array([True])
    oracle = greedy_oracle(model, variables, prompt, 1 + 3 * (k + 1))
    got = [tok]
    for _ in range(3):
        cache, _, emitted, counts, prev_next = engine.spec_decode(
            cache, None, last, prev, active
        )
        assert int(counts[0]) == k + 1, "full-layer draft must fully accept"
        got.extend(int(t) for t in emitted[0, : k + 1])
        last[0] = emitted[0, k]
        prev[0] = prev_next[0]
    assert got == oracle


# -- rollback / cache state ------------------------------------------------
def test_spec_rollback_commits_only_accepted_span(tiny):
    """lengths must advance by exactly counts per round, and inactive
    slots must not move at all."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=3, max_len=64,
                             prefill_len=8, spec_k=2, draft_layers=1)
    cache = engine.init_cache()
    cache, tok = engine.prefill(cache, 1, np.array([4, 8, 15], np.int32))
    last = np.zeros(3, np.int32)
    prev = np.zeros(3, np.int32)
    active = np.zeros(3, bool)
    last[1], prev[1], active[1] = tok, 15, True
    len_before = int(np.asarray(cache.lengths)[1])
    cache, _, emitted, counts, _ = engine.spec_decode(
        cache, None, last, prev, active
    )
    lengths = np.asarray(cache.lengths)
    assert lengths[1] == len_before + int(counts[1])
    assert lengths[0] == 0 and lengths[2] == 0
    assert 1 <= int(counts[1]) <= 3


def test_kv_cache_advance_and_rollback(tiny):
    model, _ = tiny
    cache = KVCache.create(model.cfg, n_slots=3, max_len=16)
    cache = cache.replace(lengths=jnp.asarray([4, 7, 0], jnp.int32))
    adv = cache.advance(jnp.asarray([2, 3, 1], jnp.int32),
                        jnp.asarray([True, False, True]))
    np.testing.assert_array_equal(np.asarray(adv.lengths), [6, 7, 1])
    back = adv.rollback(cache.lengths)
    np.testing.assert_array_equal(np.asarray(back.lengths), [4, 7, 0])


# -- temperature > 0 -------------------------------------------------------
def test_stochastic_spec_decode_smoke(tiny):
    """Rejection-sampling path: correct span sizes, tokens in vocab, and
    lengths consistent after several rounds."""
    model, variables = tiny
    k = 2
    engine = InferenceEngine(
        model, variables, n_slots=2, max_len=64, prefill_len=8,
        sampling=SamplingParams(temperature=0.8, top_k=20, top_p=0.95),
        spec_k=k, draft_layers=1, seed=3,
    )
    cache = engine.init_cache()
    cache, tok = engine.prefill(cache, 0, np.array([3, 1, 4], np.int32))
    last = np.array([tok, 0], np.int32)
    prev = np.array([4, 0], np.int32)
    active = np.array([True, False])
    total = 0
    for _ in range(4):
        cache, _, emitted, counts, prev_next = engine.spec_decode(
            cache, None, last, prev, active
        )
        n = int(counts[0])
        assert 1 <= n <= k + 1
        assert all(0 <= int(t) < 97 for t in emitted[0, :n])
        total += n
        last[0] = emitted[0, n - 1]
        prev[0] = prev_next[0]
    # cache invariant: positions 0..lengths-1 are cached and the CURRENT
    # last token (position lengths) is not yet — so after consuming
    # `total` tokens past the prefill, lengths = prompt_len + total
    assert int(np.asarray(cache.lengths)[0]) == 3 + total


@pytest.mark.parametrize("separate_draft", [False, True])
def test_sampled_spec_tokens_are_those_of_keys_folded_on_the_host(
        tiny, tiny_draft, separate_draft, keys_folded_on_the_host,
        served_tokens):
    """The speculative programs fold the step's key from (base key, counter)
    themselves, as decode and prefill do: drafts, acceptances and bonus
    tokens under ``temperature > 0`` are those of the eagerly folded stream,
    and the programs' inputs are host values (the only ``jax.Array`` among
    the arguments are weights, caches and the base key)."""
    model, variables = tiny
    draft = dict(draft_model=tiny_draft[0], draft_params=tiny_draft[1]) \
        if separate_draft else dict(draft_layers=1)

    def make(seed):
        return InferenceEngine(
            model, variables, n_slots=2, max_len=48, prefill_len=8,
            sampling=SamplingParams(temperature=0.9, top_k=20, top_p=0.95),
            spec_k=2, seed=seed, **draft)

    def churn(engine):
        return served_tokens(engine, 5, 7)

    engine = make(5)
    got = churn(engine)
    assert got == churn(keys_folded_on_the_host(make(5))) and len(got) == 5
    assert got != churn(make(6))
    assert engine._spec._cache_size() == 1

    engine._spec = mock.Mock(wraps=engine._spec)
    if separate_draft:
        engine._draft_prefill = mock.Mock(wraps=engine._draft_prefill)
    churn(engine)
    n_device = 4 if separate_draft else 2       # weights and caches
    *host, (base, counter) = engine._spec.call_args.args[n_device:]
    assert base is engine._rng and type(counter) is np.uint32
    assert [(type(a), a.dtype) for a in host] == [
        (np.ndarray, np.int32)] * (len(host) - 1) + [(np.ndarray, np.bool_)]
    assert len(host) == (3 if separate_draft else 2)    # last, (prev,) active
    if separate_draft:
        tokens, slot, n = engine._draft_prefill.call_args.args[2:]
        assert (type(tokens), tokens.dtype) == (np.ndarray, np.int32)
        assert (type(slot), type(n)) == (np.int32, np.int32)


# -- scheduler integration -------------------------------------------------
def test_scheduler_spec_churn_matches_solo_generation(tiny):
    """Continuous batching + speculation: 7 requests through 2 slots with
    join/evict churn — every request's stream must equal its solo oracle
    generation, exactly as the non-speculative scheduler guarantees."""
    model, variables = tiny
    rng = np.random.default_rng(3)
    reqs = [
        (rng.integers(0, 97, int(rng.integers(2, 8))).astype(np.int32),
         int(rng.integers(2, 9)))
        for _ in range(7)
    ]
    solo = {
        i: greedy_oracle(model, variables, prompt, n_new)
        for i, (prompt, n_new) in enumerate(reqs)
    }
    engine = InferenceEngine(model, variables, n_slots=2, max_len=48,
                             prefill_len=8, spec_k=2, draft_layers=1)
    sched = Scheduler(engine, emit_events=False)
    for prompt, n_new in reqs:
        sched.submit(Request(prompt=prompt, max_new_tokens=n_new))
    finished = sched.run()
    assert sorted(f.request_id for f in finished) == list(range(7))
    for f in finished:
        assert f.tokens == solo[f.request_id], (
            f"request {f.request_id} diverged under speculative batching"
        )
    s = sched.stats()
    assert s["spec_k"] == 2.0
    assert 0.0 <= s["accept_rate"] <= 1.0
    assert s["tokens_per_target_forward"] > 0


def test_scheduler_spec_draft_model_churn(tiny, tiny_draft):
    """Same churn oracle through the separate-draft-model path (draft
    cache prefill + catch-up refeed under slot reuse)."""
    model, variables = tiny
    dmodel, dvars = tiny_draft
    rng = np.random.default_rng(5)
    reqs = [
        (rng.integers(0, 97, int(rng.integers(2, 8))).astype(np.int32),
         int(rng.integers(2, 8)))
        for _ in range(5)
    ]
    solo = {
        i: greedy_oracle(model, variables, prompt, n_new)
        for i, (prompt, n_new) in enumerate(reqs)
    }
    engine = InferenceEngine(model, variables, n_slots=2, max_len=48,
                             prefill_len=8, spec_k=2,
                             draft_model=dmodel, draft_params=dvars)
    sched = Scheduler(engine, emit_events=False)
    assert sched.draft_cache is not None
    for prompt, n_new in reqs:
        sched.submit(Request(prompt=prompt, max_new_tokens=n_new))
    finished = sched.run()
    for f in finished:
        assert f.tokens == solo[f.request_id]


def test_scheduler_spec_step_events_trace_accept_counts(tiny):
    """The structured serving.spec_step events must reconcile with the
    scheduler's accept/token accounting: per step, accepted <= proposed,
    every consumed span is within [1, k+1], and the event totals equal
    the RatioTracker numerators."""
    model, variables = tiny
    k = 2
    engine = InferenceEngine(model, variables, n_slots=2, max_len=48,
                             prefill_len=8, spec_k=k, draft_layers=1)
    sched = Scheduler(engine)  # emit_events=True
    for i in range(3):
        sched.submit(Request(prompt=[1 + i, 2, 3], max_new_tokens=6))
    sched.run()
    evs = [e for e in recent_events(500) if e.name == "serving.spec_step"]
    assert evs, "speculative steps must emit serving.spec_step events"
    tot_proposed = tot_accepted = 0
    for e in evs:
        md = e.metadata
        assert 0 <= md["accepted"] <= md["proposed"]
        assert md["proposed"] % k == 0
        for consumed in md["consumed"].values():
            assert 1 <= consumed <= k + 1
        tot_proposed += md["proposed"]
        tot_accepted += md["accepted"]
    assert tot_proposed == sched.accept_rate.den
    assert tot_accepted == sched.accept_rate.num
    # every request ran to its 6-token budget through spec spans
    assert sched.tokens_generated == 3 * 6
