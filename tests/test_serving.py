"""Serving engine: KV-cached decode, continuous batching, TP inference.

Correctness is anchored by the teacher-forcing oracle: greedy KV-cached
decode must emit exactly the argmax tokens of the full uncached forward,
token for token — any cache-write, masking, position-offset, or slot-reuse
bug breaks the equality. The scheduler's churn trace extends the oracle to
continuous batching: every request's batched tokens must equal its solo
generation regardless of which slot it landed in or who used it before.
"""

import os
import subprocess
import sys
from pathlib import Path

import functools
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.serving import (
    InferenceEngine,
    KVCache,
    Request,
    SamplingParams,
    Scheduler,
    gpt2_param_shardings,
    kv_cache_sharding,
    sample_tokens,
)

pytestmark = pytest.mark.serving

REPO = Path(__file__).parent.parent


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=97, n_positions=48, n_embd=48, n_layer=2,
                     n_head=4, dtype=jnp.float32)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables


@functools.lru_cache(maxsize=None)
def _oracle_fwd(model):
    return jax.jit(model.apply)


def greedy_oracle(model, variables, prompt, n_tokens):
    """Teacher forcing on the uncached forward: argmax continuation.

    The input is zero-padded to ``n_positions`` so the jitted forward
    compiles once per model — causal attention makes the padded tail
    invisible to the position being read.
    """
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


def engine_greedy(engine, cache, slot, prompt, n_tokens):
    """Generate via prefill + decode steps, only `slot` active."""
    cache, tok = engine.prefill(cache, slot, prompt)
    got = [tok]
    last = np.zeros(engine.n_slots, np.int32)
    active = np.zeros(engine.n_slots, bool)
    last[slot], active[slot] = tok, True
    for _ in range(n_tokens - 1):
        cache, toks = engine.decode(cache, last, active)
        got.append(int(toks[slot]))
        last[slot] = toks[slot]
    return cache, got


# -- KV cache pytree -------------------------------------------------------
def test_kv_cache_shapes_and_evict(tiny):
    model, _ = tiny
    cache = KVCache.create(model.cfg, n_slots=3, max_len=16)
    # heads folded into the minor dim: one 48-wide row a token and layer
    assert cache.k.shape == (2, 3, 16, 4 * 12)
    assert cache.v.shape == cache.k.shape
    assert cache.lengths.shape == (3,)
    assert cache.n_layers == 2 and cache.n_slots == 3 and cache.max_len == 16
    assert cache.bytes_per_slot() == 2 * 2 * 16 * 4 * 12 * 4  # fp32
    cache = cache.replace(lengths=cache.lengths.at[1].set(9))
    cache = cache.evict(1)
    assert int(cache.lengths[1]) == 0


def test_kv_cache_rejects_bad_shapes(tiny):
    model, _ = tiny
    with pytest.raises(ValueError, match="n_positions"):
        KVCache.create(model.cfg, n_slots=2, max_len=4096)
    with pytest.raises(ValueError, match="n_slots"):
        KVCache.create(model.cfg, n_slots=0, max_len=8)


# -- prefill parity --------------------------------------------------------
def test_cached_prefill_logits_match_uncached(tiny):
    """The cache-aware forward on a full prompt must reproduce the plain
    forward's logits at every prompt position (same params, same math)."""
    model, variables = tiny
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 97, (1, 8)), jnp.int32
    )
    ref = model.apply(variables, tokens)
    cache = KVCache.create(model.cfg, n_slots=1, max_len=16)
    out, new_cache = model.apply(
        variables, tokens, kv_cache=cache,
        position_offset=jnp.zeros((1,), jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert new_cache.k.shape == cache.k.shape


def test_training_path_signature_unchanged(tiny):
    """No kv_cache kwarg -> plain logits, exactly as trainers call it."""
    model, variables = tiny
    tokens = jnp.zeros((2, 4), jnp.int32)
    out = model.apply(variables, tokens)
    assert out.shape == (2, 4, 97)


# -- the greedy parity oracle ----------------------------------------------
@pytest.mark.parametrize("slot", [0, 2])
def test_greedy_decode_matches_uncached_argmax(tiny, slot):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=3, max_len=32,
                             prefill_len=8)
    prompt = np.array([5, 17, 3, 9, 44], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 12)
    _, got = engine_greedy(engine, engine.init_cache(), slot, prompt, 12)
    assert got == oracle


def test_paged_cache_greedy_matches_uncached_argmax(tiny):
    """The same teacher-forcing oracle on the paged cache: block-table
    scatter/gather attention must emit the identical argmax continuation.
    (The paged path's own unit/isolation/COW oracles live in
    tests/test_paging.py — this anchors it to THE serving oracle.)"""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8, cache_kind="paged", page_size=4)
    sched = Scheduler(engine, emit_events=False)
    prompt = np.array([5, 17, 3, 9, 44], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 12)
    sched.submit(Request(prompt=prompt, max_new_tokens=12))
    (fin,) = sched.run()
    assert fin.tokens == oracle


def test_slot_reuse_does_not_leak(tiny):
    """Generate in a slot, evict, admit a different prompt into the SAME
    slot: its tokens must match a fresh-cache generation (masking, not
    zeroing, is the isolation boundary)."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8)
    cache = engine.init_cache()
    cache, _ = engine_greedy(engine, cache, 1,
                             np.array([60, 61, 62, 63], np.int32), 10)
    cache = cache.evict(1)
    p2 = np.array([7, 1], np.int32)
    _, reused = engine_greedy(engine, cache, 1, p2, 8)
    _, fresh = engine_greedy(engine, engine.init_cache(), 1, p2, 8)
    assert reused == fresh


def test_engine_validation(tiny):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=16,
                             prefill_len=8)
    cache = engine.init_cache()
    with pytest.raises(ValueError, match="empty"):
        engine.prefill(cache, 0, np.array([], np.int32))
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        engine.prefill(cache, 0, np.arange(9, dtype=np.int32))
    with pytest.raises(ValueError, match="slot"):
        engine.prefill(cache, 5, np.array([1], np.int32))
    with pytest.raises(ValueError, match="prefill_len"):
        InferenceEngine(model, variables, n_slots=2, max_len=8,
                        prefill_len=9)
    moe_cfg = GPT2Config(vocab_size=97, n_positions=16, n_embd=48,
                         n_layer=1, n_head=4, moe_experts=2)
    with pytest.raises(ValueError, match="dense"):
        InferenceEngine(GPT2(moe_cfg), variables)


# -- sampling --------------------------------------------------------------
def test_sample_greedy_is_argmax():
    logits = jnp.asarray(
        np.random.default_rng(1).standard_normal((5, 33)), jnp.float32
    )
    toks = sample_tokens(logits, jax.random.key(0), SamplingParams())
    np.testing.assert_array_equal(
        np.asarray(toks), np.argmax(np.asarray(logits), -1)
    )


def test_sample_top_k_restricts_support():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((4, 50)), jnp.float32)
    sp = SamplingParams(temperature=1.0, top_k=5)
    top5 = np.argsort(np.asarray(logits), -1)[:, -5:]
    for i in range(20):
        toks = np.asarray(
            sample_tokens(logits, jax.random.key(i), sp)
        )
        for row in range(4):
            assert toks[row] in top5[row]


def test_sample_top_p_keeps_best_token_when_peaked():
    # one dominant logit -> nucleus of size 1 -> sampling is deterministic
    logits = np.full((3, 20), -5.0, np.float32)
    best = [4, 11, 0]
    for r, b in enumerate(best):
        logits[r, b] = 10.0
    sp = SamplingParams(temperature=1.0, top_p=0.5)
    for i in range(5):
        toks = np.asarray(
            sample_tokens(jnp.asarray(logits), jax.random.key(i), sp)
        )
        np.testing.assert_array_equal(toks, best)


def test_sample_top_k_exact_k_with_ties():
    """Regression: the old filter kept every logit TIED with the k-th
    value (`logits < kth` keeps ties), silently widening the support
    beyond k. With a row of [1, 1, 1, 0, ...] and top_k=2 the support
    must be exactly the 2 lowest-id tied tokens, never the third."""
    logits = np.full((2, 16), -10.0, np.float32)
    logits[0, [3, 7, 11]] = 2.0          # three-way tie, top_k=2
    logits[1, [0, 1, 2, 3]] = 5.0        # four-way tie, top_k=2
    sp = SamplingParams(temperature=1.0, top_k=2)
    seen = [set(), set()]
    for i in range(40):
        toks = np.asarray(
            sample_tokens(jnp.asarray(logits), jax.random.key(i), sp)
        )
        for row in range(2):
            seen[row].add(int(toks[row]))
    # ties break toward lower token ids (lax.top_k order)
    assert seen[0] <= {3, 7}, seen[0]
    assert seen[1] <= {0, 1}, seen[1]


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1).validate()


def test_stochastic_sampling_stays_in_vocab(tiny):
    model, variables = tiny
    engine = InferenceEngine(
        model, variables, n_slots=2, max_len=24, prefill_len=8,
        sampling=SamplingParams(temperature=0.8, top_k=10, top_p=0.9),
        seed=7,
    )
    _, got = engine_greedy(engine, engine.init_cache(), 0,
                           np.array([3, 1, 4], np.int32), 8)
    assert all(0 <= t < 97 for t in got)


# -- prefill length buckets ------------------------------------------------
def test_prefill_bucket_selection(tiny):
    """Buckets default to powers of two up to prefill_len; each prompt
    pads to the smallest bucket that holds it (one compiled program per
    bucket, short prompts stop paying full-length prefill compute)."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=48,
                             prefill_len=24)
    assert engine.prefill_buckets == (8, 16, 24)
    assert engine.prefill_bucket(1) == 8
    assert engine.prefill_bucket(8) == 8
    assert engine.prefill_bucket(9) == 16
    assert engine.prefill_bucket(24) == 24
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        engine.prefill_bucket(25)
    padded, n = engine._pad_prompt(np.arange(1, 11, dtype=np.int32))
    assert padded.shape == (1, 16) and n == 10

    custom = InferenceEngine(model, variables, n_slots=2, max_len=48,
                             prefill_len=24, prefill_buckets=(4, 12))
    assert custom.prefill_buckets == (4, 12, 24)  # cap auto-appended
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        InferenceEngine(model, variables, n_slots=2, max_len=48,
                        prefill_len=8, prefill_buckets=(16,))


def test_prefill_bucket_parity(tiny):
    """The same prompt must generate identical greedy tokens no matter
    which bucket it pads to — padding is invisible to the cache."""
    model, variables = tiny
    prompt = np.array([5, 17, 3, 9, 44], np.int32)
    oracle = greedy_oracle(model, variables, prompt, 8)
    for buckets in [(8,), (16,), (5, 7)]:
        engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                                 prefill_len=16, prefill_buckets=buckets)
        _, got = engine_greedy(engine, engine.init_cache(), 0, prompt, 8)
        assert got == oracle, f"buckets {buckets} diverged"


# -- a step's inputs are host values, its key is folded in the program -----
SAMPLED = SamplingParams(temperature=0.9, top_k=20, top_p=0.95)


@pytest.mark.parametrize("cache_kind", ["slotted", "paged"])
def test_sampled_tokens_are_those_of_keys_folded_on_the_host(
        tiny, cache_kind, keys_folded_on_the_host, served_tokens):
    """Prefills and decode steps share one counter, and the key a program
    folds from (base key, counter) is the key ``fold_in`` gives eagerly:
    with ``temperature > 0`` every token of every request is the one
    sampled under the eagerly folded stream, and another seed's are not."""
    model, variables = tiny

    def make(seed):
        return InferenceEngine(
            model, variables, n_slots=2, max_len=32, prefill_len=16,
            sampling=SAMPLED, seed=seed, cache_kind=cache_kind, page_size=4)

    engine = make(11)
    got = served_tokens(engine, 7, 14)
    want = served_tokens(keys_folded_on_the_host(make(11)), 7, 14)
    assert got == want and len(got) == 7
    assert engine._rng_calls > 7        # prefills AND decode steps counted
    assert got != served_tokens(make(12), 7, 14)    # they were sampled


def test_the_seed_is_no_literal_of_a_program(tiny):
    """The base key is an ARGUMENT of the decode and prefill programs: two
    engines that differ in ``seed`` only lower the same text, so a new seed
    is no cold compile."""
    model, variables = tiny

    def texts(seed):
        engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                                 prefill_len=8, sampling=SAMPLED, seed=seed)
        cache = jax.eval_shape(engine.init_cache)
        decode = engine._decode.lower(
            engine.params, cache, np.zeros(2, np.int32), np.ones(2, bool),
            engine._next_rng())
        prefill = engine._prefill.lower(
            engine.params, cache, np.zeros((1, 8), np.int32), np.int32(0),
            np.int32(3), engine._next_rng())
        return decode.as_text(), prefill.as_text()

    assert texts(1) == texts(2)


def test_steps_add_no_executable_after_the_warm_up(tiny):
    """One executable for decode and one a prefill bucket, whatever the
    slot, the prompt's length or the types the caller holds (Python ints,
    lists, int64 arrays): the engine types them on the host, so no weak
    type traces a second program. These are the counts the ``executables``
    stat of ``pdt.engine.decode.dispatch`` / ``.prefill.dispatch`` reports."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=3, max_len=32,
                             prefill_len=16, sampling=SAMPLED)
    assert engine.prefill_buckets == (8, 16)
    cache = engine.init_cache()
    for slot, n in ((0, 3), (1, 12)):           # the warm-up: each bucket
        cache, _ = engine.prefill(cache, slot, np.arange(1, n + 1))
    cache, toks = engine.decode(cache, np.zeros(3, np.int32),
                                np.array([True, True, False]))
    assert engine._decode._cache_size() == 1
    assert engine._prefill._cache_size() == 2
    for slot, n in ((2, 8), (0, 9), (1, 1), (2, 16), (np.int64(1), 5)):
        cache, tok = engine.prefill(cache, slot, list(range(1, n + 1)))
        active = np.arange(3) != slot
        cache, toks = engine.decode(
            cache, [int(t) for t in toks], list(map(bool, active)))
        cache, toks = engine.decode(cache, toks.astype(np.int64), active)
    assert engine._decode._cache_size() == 1
    assert engine._prefill._cache_size() == 2


@pytest.mark.parametrize("cache_kind", ["slotted", "paged"])
def test_a_warm_step_launches_its_program_and_nothing_else(tiny, cache_kind):
    """Between two steps no device program runs for a step's inputs: the
    only ``jax.Array`` among the arguments of the one compiled call are the
    weights, the cache and the engine's base key; tokens, mask, scalars and
    the step's counter are typed NumPy values that the call's own argument
    path copies (an eager ``jnp.asarray`` / ``jnp.int32`` / ``fold_in`` would
    each have been a program or a transfer of its own)."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8, sampling=SAMPLED,
                             cache_kind=cache_kind, page_size=4)
    key, counter = engine._next_rng()
    assert key is engine._rng and type(counter) is np.uint32
    sched = Scheduler(engine, emit_events=False)
    sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=4))
    sched.step()                                    # warm: both compiled
    engine._prefill = mock.Mock(wraps=engine._prefill)
    engine._decode = mock.Mock(wraps=engine._decode)
    sched.submit(Request(prompt=[9, 44], max_new_tokens=3))
    sched.run()
    *_, tokens, active, (base, counter) = engine._decode.call_args.args
    assert base is engine._rng and type(counter) is np.uint32
    assert (type(tokens), tokens.dtype) == (np.ndarray, np.int32)
    assert (type(active), active.dtype) == (np.ndarray, np.bool_)
    _, _, tokens, *scalars, (base, counter) = engine._prefill.call_args.args
    assert base is engine._rng and type(counter) is np.uint32
    assert (type(tokens), tokens.dtype) == (np.ndarray, np.int32)
    # slot, (start,) n_real
    assert [type(i) for i in scalars] == [np.int32] * (
        3 if cache_kind == "paged" else 2)


# -- scheduler: continuous batching ----------------------------------------
def test_scheduler_fifo_admission_order(tiny):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8)
    sched = Scheduler(engine, emit_events=False)
    ids = [sched.submit(Request(prompt=[1 + i], max_new_tokens=5))
           for i in range(4)]
    assert ids == [0, 1, 2, 3]
    sched.step()
    # first two requests occupy slots in index order; later ones wait
    assert sched.slots[0].request.request_id == 0
    assert sched.slots[1].request.request_id == 1
    assert [r.request_id for r in sched.queue] == [2, 3]


def test_scheduler_churn_matches_solo_generation(tiny):
    """The continuous-batching oracle: 7 requests through 2 slots (constant
    join/evict churn, every slot reused multiple times) — each request's
    token stream must equal its solo single-slot generation."""
    model, variables = tiny
    rng = np.random.default_rng(3)
    reqs = [
        (rng.integers(0, 97, int(rng.integers(2, 8))).astype(np.int32),
         int(rng.integers(2, 9)))
        for _ in range(7)
    ]

    solo = {}
    for i, (prompt, n_new) in enumerate(reqs):
        solo[i] = greedy_oracle(model, variables, prompt, n_new)

    engine = InferenceEngine(model, variables, n_slots=2, max_len=32,
                             prefill_len=8)
    sched = Scheduler(engine, emit_events=False)
    for prompt, n_new in reqs:
        sched.submit(Request(prompt=prompt, max_new_tokens=n_new))
    finished = sched.run()

    assert sorted(f.request_id for f in finished) == list(range(7))
    for f in finished:
        assert f.tokens == solo[f.request_id], (
            f"request {f.request_id} diverged under batching"
        )
        assert f.reason == "length"
        assert f.ttft_s > 0 and f.total_s >= f.ttft_s
    assert not sched.has_work
    assert sched.n_active == 0


def test_scheduler_eos_eviction_frees_slot(tiny):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=1, max_len=32,
                             prefill_len=8)
    prompt = np.array([5, 17, 3, 9], np.int32)
    # pick the 3rd greedy token as EOS: request must stop there
    stream = greedy_oracle(model, variables, prompt, 8)
    eos = stream[2]
    sched = Scheduler(engine, emit_events=False)
    sched.submit(Request(prompt=prompt, max_new_tokens=20, eos_token=eos))
    sched.submit(Request(prompt=prompt, max_new_tokens=2))
    finished = sched.run()
    by_id = {f.request_id: f for f in finished}
    assert by_id[0].reason == "eos"
    assert by_id[0].tokens == stream[:3]  # includes the EOS token
    # slot was reused by request 1 after the eviction
    assert by_id[1].reason == "length" and len(by_id[1].tokens) == 2


def test_scheduler_capacity_eviction(tiny):
    """A request whose budget exceeds the slot capacity is cut off when
    the cache fills, not wedged."""
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=1, max_len=12,
                             prefill_len=8)
    sched = Scheduler(engine, emit_events=False)
    sched.submit(Request(prompt=np.arange(6, dtype=np.int32),
                         max_new_tokens=100))
    (fin,) = sched.run()
    assert fin.reason == "length"
    # prompt 6 + tokens t: next write position 6 + t - 1 must stay < 12
    assert len(fin.tokens) == 12 - 6 + 1
    assert not sched.has_work


def test_scheduler_stats_track_latency(tiny):
    model, variables = tiny
    engine = InferenceEngine(model, variables, n_slots=2, max_len=24,
                             prefill_len=8)
    sched = Scheduler(engine, emit_events=False)
    for i in range(3):
        sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    sched.run()
    s = sched.stats()
    assert s["tokens_generated"] == 12.0
    assert s["decode_steps"] > 0
    assert s["decode_step_p99_s"] >= s["decode_step_p50_s"] > 0
    assert s["ttft_p99_s"] >= s["ttft_p50_s"] > 0


# -- TP serving ------------------------------------------------------------
def test_tp_sharded_serving_parity(tiny, mesh24):
    """Params TP-sharded on the (2,4) mesh + head-sharded cache must emit
    exactly the host engine's greedy tokens."""
    model, variables = tiny
    shardings = gpt2_param_shardings(variables["params"], mesh24)
    sharded = {
        "params": jax.tree_util.tree_map(
            jax.device_put, variables["params"], shardings
        )
    }
    kern = sharded["params"]["h_0"]["attn"]["c_attn"]["kernel"]
    assert "tp" in str(kern.sharding.spec), kern.sharding

    prompt = np.array([5, 17, 3, 9], np.int32)
    host_eng = InferenceEngine(model, variables, n_slots=4, max_len=24,
                               prefill_len=8)
    _, want = engine_greedy(host_eng, host_eng.init_cache(), 0, prompt, 8)

    tp_eng = InferenceEngine(
        model, sharded, n_slots=4, max_len=24, prefill_len=8,
        cache_sharding=kv_cache_sharding(mesh24),
    )
    cache = tp_eng.init_cache()
    assert "tp" in str(cache.k.sharding.spec)
    _, got = engine_greedy(tp_eng, cache, 0, prompt, 8)
    assert got == want


# -- subprocess: import weight + train->serve ------------------------------
def _env(n_dev=2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_serving_import_stays_dependency_light():
    """import pytorch_distributed_tpu.serving must not drag in orbax or
    the Pallas toolchain (control planes / CPU tools import it freely);
    checkpoint IO loads lazily inside load_gpt2_params only."""
    code = (
        "import sys; import pytorch_distributed_tpu.serving; "
        "import pytorch_distributed_tpu.serving.speculative; "
        "heavy = [m for m in sys.modules if 'orbax' in m "
        "or 'flash_attention' in m or '.pallas' in m]; "
        "assert not heavy, heavy; print('LIGHT')"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=180,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LIGHT" in r.stdout


def test_train_then_serve_end_to_end(tmp_path):
    """The full train->serve bridge as a user runs it: train config #4 for
    a few steps with checkpoints, then serve the checkpoint TP=2 with the
    serving example."""
    ck = tmp_path / "ck"
    r = subprocess.run(
        [sys.executable, "examples/train_gpt2_fsdp.py",
         "--layers", "2", "--embd", "64", "--heads", "4", "--vocab", "256",
         "--seq-len", "32", "--global-batch", "4", "--steps", "3",
         "--dataset-size", "16", "--log-every", "1",
         "--ckpt-every", "2", "--ckpt-dir", str(ck)],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert ck.exists()

    r = subprocess.run(
        [sys.executable, "examples/serve_gpt2.py",
         "--ckpt-dir", str(ck),
         "--layers", "2", "--embd", "64", "--heads", "4", "--vocab", "256",
         "--seq-len", "32", "--tp", "2", "--slots", "2",
         "--prefill-len", "8", "--requests", "3", "--max-new-tokens", "4"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "loaded params from" in r.stdout
    assert "served 3 requests" in r.stdout
    assert "tok/s" in r.stdout
