"""``sarvam_mla``'s block (``models/kimi_linear.py`` with no KDA layer,
rotation under YaRN; every layer MLA, a share of the routed experts) served
from a PAGED latent pool with prefix reuse, against its plain reference
``chipbench/references/sarvam_mla.py``: a tiny size that only these tests
choose, on the CPU, on seeded random weights, float32 on both sides.

Tolerances. The two differ only in the ORDER of float32 sums (absorbed
against expanded attention, blocks under a running softmax against one
softmax, sorted-and-grouped against masked experts): a few ulps of values of
order one, held to ``TOL = 1e-4`` absolute on logits whose range is about
one. A missing rotation, plain frequencies, a page of another's rows, a read
a page short and an expert fewer each move the logits by more than ``20 *
TOL`` at this size (``test_every_degraded_reference...``), so ``TOL``
refuses each. Greedy tokens through the engine and the scheduler are
compared exactly with the reference's argmax wherever its best two logits
lie more than ``TOL`` apart.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import sarvam_mla as family
from chipbench.references import sarvam_mla as reference
from pytorch_distributed_tpu.models.kimi_linear import ExpertShare
from pytorch_distributed_tpu.ops import latent_paged_attention as paged
from pytorch_distributed_tpu.serving import (
    InferenceEngine,
    LatentCache,
    Request,
    Scheduler,
)
from pytorch_distributed_tpu.serving.paging import PagedLatentCache

TOL = 1e-4
PAGE = 8
MAX_LEN = 128

#: the configuration file's keys at a tiny size: the first layer dense,
#: experts 4..7 of 16 held, the source's YaRN block with a short original
#: length, so that the ramp between the two corrections lies inside 8 pairs
CONFIG = dict(
    vocab_size=256, max_position_embeddings=4096, num_hidden_layers=3,
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=16, q_head_dim=32, v_head_dim=16,
    rope_theta=10000,
    rope_scaling=dict(type="deepseek_yarn", factor=40, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=64),
    intermediate_size=96, first_k_dense_replace=1, moe_intermediate_size=32,
    num_experts=4, router_width=16, held_experts_first=4,
    num_experts_per_tok=4, num_shared_experts=1, routed_scaling_factor=2.5,
    rms_norm_eps=1e-6,
    assumed=dict(compute_dtype="float32", param_dtype="float32",
                 initializer_range=0.02),
)


@pytest.fixture(scope="module")
def served():
    model = family.build_model(CONFIG)
    variables = model.init(jax.random.key(54), jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def reference_logits(variables, tokens, **knobs):
    with jax.default_matmul_precision("highest"):
        logits, _ = reference.forward(variables["params"],
                                      jnp.asarray(tokens), CONFIG, **knobs)
    return np.asarray(logits)


def apply(model, variables, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return model.apply(variables, jnp.asarray(tokens), **kw)


def pool(model, chains, n_pages=64):
    """A pool whose slots' tables are ``chains`` (lists of page ids)."""
    cache = PagedLatentCache.create(
        model.cfg, n_slots=len(chains), max_len=MAX_LEN, page_size=PAGE,
        n_pages=n_pages)
    for slot, chain in enumerate(chains):
        row = np.zeros((cache.max_pages,), np.int32)
        row[:len(chain)] = chain
        cache = cache.set_table_row(slot, row)
    return cache


def prompt_into(model, variables, cache, slot, tokens, start=0, bucket=None,
                cold=None):
    """What the engine's paged prefill program does with ``tokens`` at
    ``start``: ``(the last real position's logits, cache)``."""
    n = len(tokens)
    bucket = bucket or -(-n // PAGE) * PAGE
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens
    cold = start == 0 if cold is None else cold
    logits, view = apply(
        model, variables, padded, kv_cache=cache.one_chain(slot, n),
        position_offset=None if cold else jnp.full((1,), start, jnp.int32))
    assert logits.shape[1] == 1          # the last real position alone
    return np.asarray(logits[0, 0]), cache.write_chain(slot, view, start + n)


# -- (1) the block without a cache ------------------------------------------

def test_the_forward_without_a_cache_is_the_reference(served):
    model, variables = served
    tokens = _tokens(1, 100)
    got = np.asarray(apply(model, variables, tokens[None]))[0]
    want = reference_logits(variables, tokens)
    assert np.abs(got - want).max() < TOL
    assert np.ptp(want) > 0.5


def test_every_degraded_reference_moves_the_logits_by_far_more_than_the_tolerance(
        served):
    _, variables = served
    tokens = _tokens(1, 100)
    want = reference_logits(variables, tokens)
    for knobs in (dict(no_rotation=True), dict(no_yarn=True),
                  dict(swap_page=(32, 0, PAGE)), dict(drop_page=(32, PAGE)),
                  dict(experts_per_token=3)):
        moved = np.abs(reference_logits(variables, tokens, **knobs)
                       - want).max()
        assert moved > 20 * TOL, (knobs, moved)


def test_the_yarn_frequencies_are_the_program_s(served):
    model, _ = served
    from pytorch_distributed_tpu.ops import latent_attention as mla

    cfg = model.cfg
    theirs = reference.inv_freq(dict(
        qk_rope_head_dim=16, rope_theta=10000, rope_factor=40,
        rope_original=64, rope_beta_fast=32, rope_beta_slow=1))
    ours = mla.yarn_inv_freq(16, cfg.rope_theta, cfg.rope_factor, 64, 32, 1)
    np.testing.assert_allclose(ours, theirs, rtol=1e-6)
    assert not np.allclose(ours, reference.inv_freq(dict(
        qk_rope_head_dim=16, rope_theta=10000), plain=True))


# -- (2) a cold prompt through pages, then decode steps ---------------------

def test_a_cold_prompt_and_decode_steps_over_scattered_pages_are_the_reference(
        served):
    model, variables = served
    tokens = _tokens(2, 60)
    n = 37
    # two chains over pages that are neither in order nor adjacent
    chains = [[9, 3, 41, 17, 5, 30, 2, 11], [7, 40, 1, 33, 12, 6, 21, 4]]
    cache = pool(model, chains)
    want = reference_logits(variables, tokens)
    first, cache = prompt_into(model, variables, cache, 1, tokens[:n],
                               bucket=40)
    assert np.abs(first - want[n - 1]).max() < TOL
    assert list(np.asarray(cache.lengths)) == [0, n]
    for t in range(n, 50):
        step = np.zeros((2, 1), np.int32)
        step[1, 0] = tokens[t]
        logits, cache = apply(model, variables, step, kv_cache=cache,
                              position_offset=cache.lengths)
        cache = cache.advance(1, jnp.asarray([False, True]))
        assert np.abs(np.asarray(logits[1, 0]) - want[t]).max() < TOL, t
    # slot 0 rode as padding: its rows went to the trash page, and the pages
    # of its table that slot 1 does not own were never written
    rows = np.asarray(cache.rows)
    assert not rows[:, chains[0][1:]].any()
    assert rows[:, chains[1][:6]].any()


# -- (3) a warm ask: a tail behind shared pages ------------------------------

@pytest.mark.parametrize("question", [5, 11, 1])
def test_a_tail_behind_shared_pages_is_its_own_cold_prompt_and_the_slotted_cache(
        served, question):
    """A second prompt that shares four whole pages with a first: the tail
    alone is computed, through the SAME pages by reference."""
    model, variables = served
    document = _tokens(3, 4 * PAGE + 3)
    prompt = np.concatenate([document, _tokens(question, question)])
    shared = [13, 2, 29, 8]
    cache = pool(model, [shared + [20, 21, 22], shared + [40, 41, 42]])
    # slot 0 prefills the document cold; slot 1 attaches its whole pages
    _, cache = prompt_into(model, variables, cache, 0, document)
    before = np.asarray(cache.rows[:, shared])
    warm, cache = prompt_into(model, variables, cache, 1, prompt[4 * PAGE:],
                              start=4 * PAGE, bucket=16)
    np.testing.assert_array_equal(np.asarray(cache.rows[:, shared]), before)
    assert int(cache.lengths[1]) == len(prompt)
    alone = pool(model, [[3, 4, 5, 6, 7, 9, 10]])
    cold, _ = prompt_into(model, variables, alone, 0, prompt)
    slotted = LatentCache.create(model.cfg, n_slots=1, max_len=MAX_LEN)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :len(prompt)] = prompt
    in_slots, _ = apply(model, variables, padded,
                        kv_cache=slotted.one_slot(64, len(prompt)))
    want = reference_logits(variables, prompt)[-1]
    for got in (warm, cold, np.asarray(in_slots[0, 0])):
        assert np.abs(got - want).max() < TOL


def test_a_prompt_cached_whole_forks_its_last_page_and_shares_the_others(
        served):
    """Through the scheduler: the second ask of a prompt of whole pages
    finds all of it cached, computes its last token alone
    (``cached_len = prompt_len - 1``) into a COPY of the last page, and
    gives the tokens of the first ask; every other page is held once."""
    model, variables = served
    engine = InferenceEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                             prefill_len=64, cache_kind="paged",
                             page_size=PAGE, tail_len=16)
    sched = Scheduler(engine, emit_events=False)
    prompt = _tokens(4, 5 * PAGE)
    sched.submit(Request(prompt=prompt, max_new_tokens=6))
    first, = sched.run()
    held = sched.allocator.n_pages - 1 - sched.allocator.free_pages
    assert held == 5                      # the prompt's pages, pinned
    sched.submit(Request(prompt=prompt, max_new_tokens=6))
    sched.step()
    assert sched.prefill_tokens_cached == len(prompt) - 1
    chain = sched.allocator.chain(0)
    assert chain[:4] == sched.radix.match(prompt, touch=False)[:4]
    assert chain[4] != sched.radix.match(prompt, touch=False)[4]   # the copy
    second, = sched.run()
    assert second.tokens == first.tokens
    sched.allocator.check()
    want = reference_logits(
        variables, np.concatenate([prompt, first.tokens[:-1]]))
    assert list(want[len(prompt) - 1:].argmax(-1)) == first.tokens


# -- (4) the paged read kernel ----------------------------------------------

@pytest.mark.parametrize("lengths", [(32, 47), (33, 48), (63, 32)])
def test_the_paged_read_kernel_is_the_dense_read_over_shared_pages(lengths):
    """``latent_paged_read`` in the Pallas interpreter: two slots whose
    chains share their first two pages (a step writes behind them, in pages
    of its own), lengths at, one under and one over a page's edge (pages of
    16: a bf16 tile's sublanes)."""
    H, d_c, d_n, d_r, d_v, page, W = 4, 128, 32, 64, 32, 16, 256
    key = jax.random.split(jax.random.key(7), 5)
    rows = jax.random.normal(key[0], (2, 12, page, W), jnp.float32)
    rows = rows.at[..., d_c + d_r:].set(0.0)
    tables = jnp.asarray([[5, 2, 9, 0], [5, 2, 7, 3]], jnp.int32)
    q = jax.random.normal(key[1], (2, 1, H, d_n + d_r), jnp.float32)
    latent = jax.random.normal(key[2], (2, 1, d_c + d_r), jnp.float32)
    kv_b = jax.random.normal(key[3], (d_c, H, d_n + d_v), jnp.float32) * 0.1
    offset = jnp.asarray(lengths, jnp.int32)
    kw = dict(d_c=d_c, d_n=d_n, scale=0.1)
    dense, rows_dense = paged.paged_read(q, latent, kv_b, rows, tables, 1,
                                         offset, **kw)
    kernel, rows_kernel = paged.paged_read(q, latent, kv_b, rows, tables, 1,
                                           offset, kernel=True,
                                           interpret=True, **kw)
    np.testing.assert_allclose(kernel, dense, atol=2e-5)
    np.testing.assert_array_equal(rows_kernel, rows_dense)
    assert np.abs(np.asarray(dense)).max() > 0.1


# -- (5) the four shares ------------------------------------------------------

def test_four_holders_shares_add_up_to_the_uncut_layer(served):
    """The parts the four holders of 4 of 16 experts give, the shared expert
    counted once, add up to the layer that holds all 16, in the program
    (``ExpertShare``) and in the reference alike."""
    model, _ = served
    import dataclasses

    whole_cfg = dataclasses.replace(model.cfg, held_experts=(0, 16))
    x = jax.random.normal(jax.random.key(5), (24, 64), jnp.float32)
    whole = ExpertShare(whole_cfg)
    variables = whole.init(jax.random.key(6), x)
    p = variables["params"]
    with jax.default_matmul_precision("highest"):
        uncut, _ = whole.apply(variables, x)
        shared = reference.ffn(x, p["shared"], functools.partial(
            reference._mm, round_to=None))
        sizes = dict(CONFIG, num_experts=16, held_experts_first=0)
        want, _ = reference.experts(p, x, sizes, round_to=None,
                                    experts_per_token=None)
        parts = []
        for first in (0, 4, 8, 12):
            part_cfg = dataclasses.replace(model.cfg,
                                           held_experts=(first, 4))
            held = dict(p, **{name: p[name][first:first + 4] for name in (
                "experts_gate", "experts_up", "experts_down")})
            y, _ = ExpertShare(part_cfg).apply({"params": held}, x)
            ref_part, _ = reference.experts(
                held, x, dict(CONFIG, held_experts_first=first),
                round_to=None, experts_per_token=None)
            assert np.abs(np.asarray(y - ref_part)).max() < TOL
            parts.append(y - shared)
    assert np.abs(np.asarray(uncut - want)).max() < TOL
    assert np.abs(np.asarray(sum(parts) + shared - uncut)).max() < TOL
    assert np.abs(np.asarray(parts[0])).max() > 1e-3


# -- (6), (7) through the scheduler -----------------------------------------

def _engine(model, variables, **kw):
    return InferenceEngine(model, variables, n_slots=2, max_len=MAX_LEN,
                           prefill_len=96, cache_kind="paged",
                           page_size=PAGE, tail_len=16, **kw)


def _ask(sched, prompt, n=4):
    sched.submit(Request(prompt=prompt, max_new_tokens=n))
    done, = sched.run()
    return done.tokens


def _is_the_reference(variables, prompt, tokens):
    logits = reference_logits(
        variables, np.concatenate([prompt, tokens[:-1]]))[len(prompt) - 1:]
    best = np.sort(logits, -1)
    clear = best[:, -1] - best[:, -2] > TOL
    return list(logits.argmax(-1)[clear]) == list(np.asarray(tokens)[clear])


def test_two_documents_asked_three_times_count_as_by_hand(served):
    """``prefill_tokens_cached``, the radix tree's hits and the counters of
    ``pdt.sched.step`` and ``pdt.engine.prefill`` against a hand count."""
    model, variables = served
    engine = _engine(model, variables)
    sched = Scheduler(engine, emit_events=False)
    docs = [_tokens(10, 4 * PAGE + 5), _tokens(11, 6 * PAGE)]
    questions = [_tokens(20 + k, 3 + k) for k in range(3)]
    cached = total = 0
    for k in range(3):
        for d, doc in enumerate(docs):
            prompt = np.concatenate([doc, questions[k]])
            tokens = _ask(sched, prompt)
            assert _is_the_reference(variables, prompt, tokens), (k, d)
            total += len(prompt)
            if k:
                cached += len(doc) // PAGE * PAGE
    stats = sched.stats()
    assert stats["prefill_tokens_total"] == total
    assert stats["prefill_tokens_cached"] == cached == 2 * (32 + 48)
    assert (stats["radix_hits"], stats["radix_misses"]) == (4, 2)
    assert stats["pages_reclaimed"] == 0
    counts = sched._step_counts()
    # the documents' whole pages and each first question's page stay pinned
    assert counts["pages"] == engine.n_pages - 1
    assert counts["pages_held"] == counts["pages"] - counts["pages_free"]
    assert counts["pages_held"] >= 4 + 6
    assert (counts["radix_hits"], counts["radix_misses"]) == (4, 2)
    sched.allocator.check()
    assert engine._counts(96, 40)["cold"] == 1
    assert engine._counts(96, 40, cached_len=8) == dict(
        bucket=96, n_real=40, n_computed=96, cached_len=8, cold=0)
    assert engine._counts(16, 9)["cold"] == 0


def test_a_document_evicted_under_pressure_is_asked_again_cold_and_right(
        served):
    """A pool too small for three documents: admitting the third reclaims
    the first's pages, a later ask of the first is a cold prefill again,
    with the reference's tokens, and no page is held twice."""
    model, variables = served
    engine = _engine(model, variables, n_pages=2 * 12 + 1)
    sched = Scheduler(engine, emit_events=False)
    docs = [_tokens(30 + d, 8 * PAGE) for d in range(3)]
    question = _tokens(40, 4)
    for doc in docs:
        prompt = np.concatenate([doc, question])
        assert _is_the_reference(variables, prompt, _ask(sched, prompt))
        sched.allocator.check()
    assert sched.pages_reclaimed > 0
    # every page given back was on some step's span; none since the last
    assert sched._reclaimed_seen == sched.pages_reclaimed
    assert sched._step_counts()["pages_reclaimed"] == 0
    before = sched.prefill_tokens_cached
    prompt = np.concatenate([docs[0], _tokens(41, 6)])
    tokens = _ask(sched, prompt)
    found = sched.prefill_tokens_cached - before
    assert found < 8 * PAGE               # some of it, or none, was gone
    assert _is_the_reference(variables, prompt, tokens)
    sched.allocator.check()
    pages = [p for slot in range(2) for p in sched.allocator.chain(slot)]
    assert len(pages) == len(set(pages))


def test_a_long_tail_behind_a_prefix_goes_through_the_tail_program_in_pieces(
        served):
    """A prompt whose cached prefix is short and whose tail is longer than
    ``tail_len``: the tail program runs piece by piece, no other program is
    compiled for it, and the tokens are the reference's."""
    model, variables = served
    engine = _engine(model, variables)
    sched = Scheduler(engine, emit_events=False)
    doc = _tokens(50, 2 * PAGE)
    _ask(sched, np.concatenate([doc, _tokens(51, 3)]))
    cold_before = engine._prefill.cold._cache_size()
    prompt = np.concatenate([doc, _tokens(52, 41)])
    tokens = _ask(sched, prompt)
    assert sched.prefill_tokens_cached == 2 * PAGE
    assert engine._prefill.cold._cache_size() == cold_before
    assert _is_the_reference(variables, prompt, tokens)


def test_slotted_and_paged_engines_refuse_and_accept_as_their_caches_say(
        served):
    model, variables = served
    assert model.cache_class is LatentCache
    assert LatentCache.paged_class() is PagedLatentCache
    with pytest.raises(ValueError, match="cache_sharding"):
        InferenceEngine(model, variables, n_slots=1, max_len=32,
                        cache_kind="paged", cache_sharding=object())
    slotted = InferenceEngine(model, variables, n_slots=1, max_len=64)
    assert isinstance(slotted.init_cache(), LatentCache)
