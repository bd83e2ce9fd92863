"""The ``kimi_linear`` decoder (a hybrid stack: KDA layers that keep a
fixed-size recurrent state, MLA layers over latent rows with no positional
encoding, a share of the routed experts) against its plain reference
``chipbench/references/kimi_linear.py``, at a tiny size on the CPU, on
seeded random weights; one period of the stack (K K K M: eager forwards of
eight layers take the suite's time and show nothing that four do not). The
chunked form's chunk is the program's own, ``ops.kda.CHUNK`` = 64 tokens
(there is no knob for it), so whatever is to carry a state BETWEEN chunks
here has a prompt of more than 64 tokens in a bucket of 128 or 256.

Tolerances. Everything here runs in float32 on both sides, so the two differ
only in the ORDER of float32 sums (the chunked form's triangular solve and
state carry against one rank-one update a token, blocked against whole
softmax, sorted-and-grouped against masked experts): a few ulps of values
of order one, held to ``TOL = 1e-4`` absolute on logits whose range is about
one. A state rounded to bfloat16 after every token (8 bits: 4e-3 of its
values) and a dropped decay are arithmetic of another kind and move the
logits by more than ``10 * TOL`` (``test_a_degraded_reference...``: 1.3e-3
and 0.31 at this size), so ``TOL`` fails both. Greedy tokens through the engine and the scheduler are
compared exactly against the reference's argmax wherever its best two logits
lie more than ``TOL`` apart. ``STATE_TOL = 1e-5`` holds the chunked form's
final state to the recurrent form's, both float32, on states of order one.
"""

import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import kimi_linear as family
from chipbench.references import kimi_linear as reference
from pytorch_distributed_tpu.models import KimiLinearConfig
from pytorch_distributed_tpu.ops import kda
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    held_share,
    route_sigmoid_topk,
)
from pytorch_distributed_tpu.serving import (
    HybridStateCache,
    InferenceEngine,
    Request,
    Scheduler,
)
from tests import _real_chunks

TOL = 1e-4
STATE_TOL = 1e-5
MAX_LEN = 256

#: the configuration file's keys at a tiny size (``families/kimi_linear.py``
#: maps them onto the model's config): layers K K K M, one period of the
#: cell's two, the first dense, experts 4..7 of 16 held
CONFIG = dict(
    vocab_size=256, model_max_length=4096, num_hidden_layers=4,
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    rope_scaling=None, rope_theta=10000, mla_use_nope=True,
    intermediate_size=96, first_k_dense_replace=1, moe_intermediate_size=32,
    num_experts=4, router_width=16, held_experts_first=4,
    num_experts_per_token=4, num_shared_experts=1,
    routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    linear_attn_config=dict(kda_layers=[1, 2, 3],
                            full_attn_layers=[4], num_heads=4, head_dim=16,
                            short_conv_kernel_size=4),
    assumed=dict(compute_dtype="float32", param_dtype="float32",
                 initializer_range=0.02, kda_chunk=kda.CHUNK,
                 state_dtype="float32"),
)
N_KDA = 3


@pytest.fixture(scope="module")
def served():
    model = family.build_model(CONFIG)
    variables = jax.jit(model.init)(jax.random.key(0),
                                    jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         CONFIG["vocab_size"]), np.int32)


def _reference(variables, tokens, **knobs):
    return reference.forward(variables["params"], jnp.asarray(tokens), CONFIG,
                             **knobs)[0]


@functools.lru_cache(maxsize=None)
def _programs(model):
    """The two forwards through a cache, jitted (an eager forward compiles
    every operation of every new shape by itself)."""
    def prefill(variables, tokens, block):
        return model.apply(variables, tokens, kv_cache=block,
                           position_offset=None)

    def decode(variables, last, cache):
        return model.apply(variables, last, kv_cache=cache,
                           position_offset=cache.lengths)

    return jax.jit(prefill), jax.jit(decode)


def _prefilled(model, variables, cache, slot, prompt, bucket):
    """``(last position's logits, cache)`` after ``prompt`` went into
    ``slot`` as the engine puts it there."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, block = _programs(model)[0](
        variables, jnp.asarray(padded), cache.one_slot(bucket, len(prompt)))
    assert logits.shape == (1, 1, CONFIG["vocab_size"])
    return logits[0, 0], cache.write_slot(slot, block, len(prompt))


def _decoded(model, variables, cache, last, active):
    logits, cache = _programs(model)[1](
        variables, jnp.asarray(last, jnp.int32)[:, None], cache)
    return logits[:, 0], cache.advance(1, jnp.asarray(active))


def test_forward_without_a_cache_is_the_reference(served):
    """150 tokens: two whole chunks and 22 tokens of a third."""
    model, variables = served
    tokens = _tokens(1, 150)
    logits = jax.jit(model.apply)(variables, tokens[None])[0]
    ref, margin = reference.forward(variables["params"], jnp.asarray(tokens),
                                    CONFIG)
    assert float(jnp.abs(logits - ref).max()) < TOL
    assert margin.shape == (150,) and float(margin.min()) > 0


@pytest.mark.parametrize("knobs", [
    dict(state_dtype="bfloat16"), dict(no_decay=True), dict(beta_one=True),
    dict(conv_taps=3), dict(rotate_mla=True), dict(experts_per_token=3),
    dict(round_to="float8_e4m3fn")], ids=lambda k: next(iter(k)))
def test_a_degraded_reference_is_another_function(served, knobs):
    """Each knob the cell's limits are read with moves the logits by far
    more than the program lies from the reference: ``TOL`` fails a bfloat16
    state and a dropped decay, among the others."""
    _, variables = served
    tokens = _tokens(1, 40)
    moved = jnp.abs(_reference(variables, tokens, **knobs)
                    - _reference(variables, tokens)).max()
    assert float(moved) > 10 * TOL


# -- (a) prefill, then decode, through the cache ------------------------------

@pytest.mark.parametrize("n_prompt,total,bucket", [
    (5, 12, 8),         # shorter than a chunk and a bucket
    (8, 16, 8),         # a bucket exactly, part of a chunk
    (64, 72, 64),       # a chunk and a bucket exactly
    (150, 160, 256),    # two whole chunks, 22 tokens of a third, one all pad
    (128, 136, 128),    # two whole chunks, no pad
], ids=["shorter", "a_bucket", "a_chunk", "chunks_and_pad", "whole_chunks"])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        served, n_prompt, total, bucket):
    """Through ``HybridStateCache.attend``, as the engine's prefill program
    goes: the state carried from chunk to chunk under the lengths' mask, the
    tail taken at the last real position, then decode steps from both."""
    model, variables = served
    tokens = _tokens(2, total)
    ref = _reference(variables, tokens)
    cache = HybridStateCache.create(model.cfg, n_slots=1, max_len=MAX_LEN)
    logits, cache = _prefilled(model, variables, cache, 0, tokens[:n_prompt],
                               bucket)
    assert float(jnp.abs(logits - ref[n_prompt - 1]).max()) < TOL
    for t in range(n_prompt, total):
        logits, cache = _decoded(model, variables, cache, tokens[t:t + 1],
                                 [True])
        # the MLA layer holds t + 1 rows; one live slot's states
        assert cache.step_stats.tolist()[-3:] == [
            t + 1, 1, cache.slot_state_bytes() // 1024]
        assert float(jnp.abs(logits[0] - ref[t]).max()) < TOL, t
    assert int(cache.lengths[0]) == total


def test_slots_of_mixed_lengths_decode_in_one_batch(served):
    """Three slots, one short, one idle, one long: every decode step of the
    batch against each sequence's own reference."""
    model, variables = served
    seqs = {0: (_tokens(3, 12), 4), 2: (_tokens(4, 30), 19)}
    cache = HybridStateCache.create(model.cfg, n_slots=3, max_len=64)
    for slot, (tokens, n_prompt) in seqs.items():
        _, cache = _prefilled(model, variables, cache, slot,
                              tokens[:n_prompt], 32)
    refs = {slot: _reference(variables, tokens)
            for slot, (tokens, _) in seqs.items()}
    for step in range(8):
        last = np.zeros((3,), np.int32)
        for slot, (tokens, n_prompt) in seqs.items():
            last[slot] = tokens[n_prompt + step]
        logits, cache = _decoded(model, variables, cache, last,
                                 [True, False, True])
        assert cache.step_stats.tolist()[-2] == 2          # live slots
        for slot, (tokens, n_prompt) in seqs.items():
            want = refs[slot][n_prompt + step]
            assert float(jnp.abs(logits[slot] - want).max()) < TOL
    assert cache.lengths.tolist() == [12, 0, 27]


# -- (b) the two forms of the op ----------------------------------------------

def _rule_operands(seed, T, lo, hi, B=2, H=3, d_k=16, d_v=8):
    ks = jax.random.split(jax.random.key(seed), 6)
    a = jax.random.uniform(ks[3], (B, T, H, d_k), minval=lo, maxval=hi)
    return (jax.random.normal(ks[0], (B, T, H, d_k)) * d_k ** -0.5,
            kda._unit(jax.random.normal(ks[1], (B, T, H, d_k))),
            jax.random.normal(ks[2], (B, T, H, d_v)), jnp.log(a),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))),
            jax.random.normal(ks[5], (B, H, d_k, d_v)))


@pytest.mark.parametrize("lo,hi", [(0.9, 0.9999), (1e-4, 1e-2), (1e-3, 1.0)],
                         ids=["a_near_1", "a_near_0", "a_mixed"])
@pytest.mark.parametrize("T", [8, 64, 200], ids=["part_of_a_chunk",
                                                 "a_chunk", "chunks_and_pad"])
def test_the_chunked_form_is_the_recurrent_form(T, lo, hi):
    """Values and final state, from a state that is not zero; chunks of 64
    in sub-blocks of 16. With ``a`` near 0 a chunk's decay is e^-590: no
    exponent may be taken apart from its partner's."""
    operands = _rule_operands(T, T, lo, hi)
    o, state = kda.gated_delta_rule(*operands)
    o_chunked, state_chunked = jax.jit(
        lambda *x: kda.gated_delta_rule(*x, chunk=64))(*operands)
    assert bool(jnp.isfinite(o_chunked).all())
    assert float(jnp.abs(o - o_chunked).max()) < STATE_TOL
    assert float(jnp.abs(state - state_chunked).max()) < STATE_TOL


def test_the_recurrent_form_is_the_equation():
    """One token: ``S = (I - beta k k^T) diag(a) S + beta k v^T``, ``o = S^T
    q``, a head, written out with matrices."""
    q, k, v, log_a, beta, state = _rule_operands(7, 1, 0.2, 0.9, B=1, H=1)
    o, new = kda.gated_delta_rule(q, k, v, log_a, beta, state)
    kk, aa = k[0, 0, 0], jnp.exp(log_a[0, 0, 0])
    want = (jnp.eye(16) - beta[0, 0, 0] * jnp.outer(kk, kk)) @ (
        aa[:, None] * state[0, 0]) + beta[0, 0, 0] * jnp.outer(kk, v[0, 0, 0])
    assert float(jnp.abs(new[0, 0] - want).max()) < STATE_TOL
    assert float(jnp.abs(o[0, 0, 0] - want.T @ q[0, 0, 0]).max()) < STATE_TOL


def test_a_position_that_is_not_valid_leaves_the_state():
    q, k, v, log_a, beta, state = _rule_operands(8, 24, 0.5, 0.99)
    valid = jnp.arange(24)[None] < jnp.asarray([[13], [24]])
    _, masked = kda.gated_delta_rule(q, k, v, log_a, beta, state, chunk=8,
                                     valid=valid)
    _, short = kda.gated_delta_rule(q[:1, :13], k[:1, :13], v[:1, :13],
                                    log_a[:1, :13], beta[:1, :13], state[:1])
    _, whole = kda.gated_delta_rule(q[1:], k[1:], v[1:], log_a[1:], beta[1:],
                                    state[1:])
    assert float(jnp.abs(masked[0] - short[0]).max()) < STATE_TOL
    assert float(jnp.abs(masked[1] - whole[0]).max()) < STATE_TOL


def test_the_convolution_continues_from_its_tail():
    x = jax.random.normal(jax.random.key(0), (2, 11, 12))
    w = jax.random.normal(jax.random.key(1), (4, 12))
    whole, window = kda.short_conv(x, w)
    want = sum(w[i] * jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, i:i + 11]
               for i in range(4))
    assert float(jnp.abs(whole - want).max()) < 1e-6
    first, window = kda.short_conv(x[:, :7], w)
    rest, _ = kda.short_conv(x[:, 7:], w, window[:, 7:])
    assert float(jnp.abs(jnp.concatenate([first, rest], 1) - whole).max()
                 ) < 1e-6


# -- (c) PAD ------------------------------------------------------------------

def test_a_prompt_in_two_buckets_leaves_the_same_state(served):
    """The pad positions of a bucket touch neither the state nor the tail
    (taken at the last REAL position), whatever tokens they hold: 70 tokens
    as a chunk and 6 of a second, and as that before two chunks all pad."""
    model, variables = served
    prompt = _tokens(9, 70)
    cache = HybridStateCache.create(model.cfg, n_slots=2, max_len=MAX_LEN)
    small, cache = _prefilled(model, variables, cache, 0, prompt, 128)
    padded = np.full((1, 256), 7, np.int32)         # pads that are not zero
    padded[0, :70] = prompt
    large, block = _programs(model)[0](
        variables, jnp.asarray(padded), cache.one_slot(256, 70))
    cache = cache.write_slot(1, block, 70)
    assert float(jnp.abs(small - large[0, 0]).max()) < TOL
    for state, tail in zip(cache.state, cache.tail):
        assert float(jnp.abs(state[0] - state[1]).max()) < STATE_TOL
        assert float(jnp.abs(tail[0] - tail[1]).max()) < STATE_TOL
        assert float(jnp.abs(tail[0]).max()) > 100 * STATE_TOL
    # a prompt shorter than the convolution: zeros before its start
    _, cache = _prefilled(model, variables, cache, 0, prompt[:2], 8)
    assert not float(jnp.abs(cache.tail[0][0, 0]).max())
    assert float(jnp.abs(cache.tail[0][0, 1]).max())


# -- (d) SLOT REUSE -----------------------------------------------------------

def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(served):
    """``evict`` resets a length and zeroes nothing: the state, the tail
    and the rows a long request left (made huge here) are overwritten whole
    by the next occupant's, never added to."""
    model, variables = served
    cache = HybridStateCache.create(model.cfg, n_slots=2, max_len=64)
    _, cache = _prefilled(model, variables, cache, 1, _tokens(5, 30), 32)
    cache = cache.replace(
        state=tuple(s * 1e4 for s in cache.state),
        tail=tuple(t * 1e4 for t in cache.tail),
        latent=cache.latent.replace(rows=cache.latent.rows * 1e4)).evict(1)
    assert int(cache.lengths[1]) == 0
    assert float(jnp.abs(cache.state[0][1]).max()) > 10
    tokens = _tokens(6, 14)
    ref = _reference(variables, tokens)
    logits, cache = _prefilled(model, variables, cache, 1, tokens[:3], 8)
    assert float(jnp.abs(logits - ref[2]).max()) < TOL
    for t in range(3, 14):
        logits, cache = _decoded(model, variables, cache, [0, tokens[t]],
                                 [False, True])
        assert float(jnp.abs(logits[1] - ref[t]).max()) < TOL, t


def test_slot_reuse_through_the_scheduler(served):
    """One slot, a long request and then a short one: the second's tokens
    are those of an engine that never served the first."""
    model, variables = served
    long, short = _tokens(11, 30), _tokens(12, 6)

    def run(prompts):
        sched = Scheduler(InferenceEngine(model, variables, n_slots=1,
                                          max_len=64), emit_events=False)
        ids = [sched.submit(Request(prompt=p, max_new_tokens=10))
               for p in prompts]
        done = {f.request_id: f.tokens for f in sched.run()}
        return [done[i] for i in ids]

    assert run([long, short])[1] == run([short])[0]


# -- (e) NOT LIVE -------------------------------------------------------------

def test_a_decode_step_leaves_idle_slots_bit_identical(served):
    model, variables = served
    cache = HybridStateCache.create(model.cfg, n_slots=3, max_len=64)
    _, cache = _prefilled(model, variables, cache, 0, _tokens(13, 9), 16)
    _, cache = _prefilled(model, variables, cache, 2, _tokens(14, 20), 32)
    cache = cache.evict(2)          # evicted: its state stays where it was
    before = cache
    _, cache = _decoded(model, variables, cache, [3, 4, 5],
                        [True, False, False])
    for old, new in zip(before.state + before.tail, cache.state + cache.tail):
        assert (old[1:] == new[1:]).all()            # to the bit
        assert not (old[0] == new[0]).all()
    assert float(jnp.abs(before.state[0][2]).max()) > 0
    assert cache.lengths.tolist() == [10, 0, 0]


# -- (f) THE SHARE ------------------------------------------------------------

def _expert_layer(seed=0, n=24, d=64, E=16, F=32):
    ks = jax.random.split(jax.random.key(seed), 8)
    p = {"router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
         "router_bias": jnp.zeros((E,)),
         "experts_gate": jax.random.normal(ks[1], (E, d, F)) * d ** -0.5,
         "experts_up": jax.random.normal(ks[2], (E, d, F)) * d ** -0.5,
         "experts_down": jax.random.normal(ks[3], (E, F, d)) * F ** -0.5,
         "shared": {"gate": jax.random.normal(ks[4], (d, F)) * d ** -0.5,
                    "up": jax.random.normal(ks[5], (d, F)) * d ** -0.5,
                    "down": jax.random.normal(ks[6], (F, d)) * F ** -0.5}}
    return p, jax.random.normal(ks[7], (n, d))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four holders of four experts each (the cell's 4-way expert
    parallel), the shared expert counted once, against the reference's
    layer with all sixteen held."""
    p, x = _expert_layer()
    sizes = reference._Sizes(num_experts_per_token=4, num_experts=16,
                             held_experts_first=0,
                             routed_scaling_factor=2.446)

    def share(first, count):
        experts, gates = held_share(*route_sigmoid_topk(
            x, p["router"], p["router_bias"], 4, 2.446), first, count)
        held = slice(first, first + count)
        return dropless_experts(
            x, experts, gates, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], num_experts=16)

    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(p, x, sizes, round_to=None,
                                    experts_per_token=None)
        shared = reference.ffn(x, p["shared"], jnp.matmul)
        parts = [share(first, 4) for first in range(0, 16, 4)]
    total = sum(y for y, _ in parts) + shared
    assert float(jnp.abs(total - want).max()) < TOL
    experts, _ = route_sigmoid_topk(x, p["router"], p["router_bias"], 4,
                                    2.446)
    assert sum(int(hit) for _, hit in parts) == len(np.unique(experts))


# -- (g) no rotation ----------------------------------------------------------

def test_an_mla_layer_takes_no_positions(served):
    """Positions shifted by a constant, the mask kept (the same tokens
    attend each other causally): ``models.xing4`` rotates by ``position_offset
    + arange(T)`` and would move; here nothing reads a position but the
    cache's mask. On the layer alone and on the whole model; that a rotation
    WOULD be seen is ``rotate_mla`` among the degraded references above."""
    from pytorch_distributed_tpu.models.kimi_linear import LatentAttention

    model, variables = served
    layer = 3                                        # the first MLA layer
    params = {"params": variables["params"][f"layer_{layer}_attn"]}
    x = jax.random.normal(jax.random.key(3), (1, 12, CONFIG["hidden_size"]))
    mixer = LatentAttention(model.cfg)
    plain, _ = mixer.apply(params, x, None, layer, None)
    shifted, _ = mixer.apply(params, x, None, layer,
                             jnp.asarray([1000], jnp.int32))
    assert (plain == shifted).all() and float(jnp.abs(plain).max()) > 0
    tokens = _tokens(15, 12)[None]
    assert (model.apply(variables, tokens) == model.apply(
        variables, tokens, position_offset=jnp.asarray([1000], jnp.int32))
    ).all()
    # and the layer is the reference's, which has no positions to take
    sizes = reference._Sizes({k: v for k, v in CONFIG.items()
                              if isinstance(v, (int, float))
                              and not isinstance(v, bool)})
    with jax.default_matmul_precision("highest"):
        want = reference.latent_attention(
            variables["params"][f"layer_{layer}_attn"], x[0], sizes,
            round_to=None, rotate_mla=False)
    assert float(jnp.abs(plain[0] - want).max()) < TOL


@_real_chunks.CASES
def test_a_prefill_ends_at_the_last_real_token(served, n_real):
    """``tests/_real_chunks.py``: the dense MLP's and the experts' chunks
    past the last real token are not run; every layer's state after the
    last REAL token, the convolutions' tails and the latent rows below it
    are what they are when every chunk runs."""
    _real_chunks.check_a_prefill_ends_at_the_last_real_token(served[0], n_real)


# -- the engine and the scheduler ---------------------------------------------

def test_a_mixed_length_trace_through_the_scheduler_is_the_references(served):
    """Join, evict and refill: more requests than slots, through
    ``InferenceEngine`` + ``Scheduler``; every greedy token the reference's
    argmax (where its best two lie apart)."""
    model, variables = served
    engine = InferenceEngine(model, variables, n_slots=3, max_len=MAX_LEN)
    assert type(engine.init_cache()) is HybridStateCache
    sched = Scheduler(engine, emit_events=False)
    # the 150: three chunks of a 256 bucket through the engine's own prefill
    prompts = [_tokens(20 + i, n) for i, n in enumerate([5, 37, 9, 150, 3,
                                                         17, 70])]
    news = [6, 12, 8, 20, 7, 5, 16]
    ids = [sched.submit(Request(prompt=p, max_new_tokens=n))
           for p, n in zip(prompts, news)]
    done = {f.request_id: f.tokens for f in sched.run()}
    assert sorted(done) == sorted(ids)
    checked = 0
    for rid, prompt, n in zip(ids, prompts, news):
        assert len(done[rid]) == n
        seq = np.concatenate([prompt, done[rid][:-1]]).astype(np.int32)
        logits = np.asarray(_reference(variables, seq))[len(prompt) - 1:]
        best = np.sort(logits, axis=-1)
        clear = best[:, -1] - best[:, -2] > TOL
        assert (logits.argmax(-1) == np.asarray(done[rid]))[clear].all(), rid
        checked += int(clear.sum())
    assert checked > 0.9 * sum(news)


@pytest.mark.parametrize("kwargs,named", [
    (dict(cache_kind="paged"), "cache_kind='paged'"),
    (dict(spec_k=2, draft_layers=1), "spec_k > 0"),
    (dict(cache_sharding=object()), "cache_sharding"),
])
def test_engine_refuses_what_a_state_cannot_do(served, kwargs, named):
    model, variables = served
    with pytest.raises(ValueError, match="HybridStateCache") as e:
        InferenceEngine(model, variables, n_slots=2, max_len=32, **kwargs)
    assert named in str(e.value) and "rolled back" in str(e.value)


def test_the_cache_is_three_states_and_one_layer_of_rows(served):
    model, _ = served
    cache = HybridStateCache.create(model.cfg, n_slots=3, max_len=64)
    assert len(cache.state) == len(cache.tail) == N_KDA
    assert {s.shape for s in cache.state} == {(3, 4, 16, 16)}
    assert {s.dtype for s in cache.state} == {jnp.dtype("float32")}
    assert {t.shape for t in cache.tail} == {(3, 3, 3 * 4 * 16)}
    assert cache.latent.rows.shape == (1, 3, 64, 128)
    assert cache.n_layers == 4 and cache.n_slots == 3 and cache.max_len == 64
    assert cache.slot_state_bytes() == N_KDA * (2 * 4 * 16 * 16 * 4
                                                + 3 * 192 * 4)
    with pytest.raises(ValueError, match="one new token"):
        cache.attend(0, jnp.zeros((3, 2, 192)), jnp.zeros((4, 192)),
                     jnp.zeros((3, 2, 4, 16)), jnp.zeros((3, 2, 4)),
                     position_offset=cache.lengths)
    with pytest.raises(NotImplementedError):
        cache.placed(None)


def test_decode_span_carries_the_steps_counts(served, monkeypatch):
    """The experts' counts, the live slots and the state bytes they move
    ride the read of the step's tokens onto ``pdt.engine.decode``."""
    from pytorch_distributed_tpu.serving import engine as engine_module

    model, variables = served
    seen = {}

    class Span:
        def __init__(self, name, **stats):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **stats):
            seen.setdefault(self.name, {}).update(stats)

    monkeypatch.setattr(engine_module, "span", Span)
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32)
    cache = engine.init_cache()
    cache, tok = engine.prefill(cache, 0, _tokens(3, 11))
    cache, toks = engine.decode(cache, np.array([tok, 0], np.int32),
                                np.array([True, False]))
    assert toks.shape == (2,)
    stats = seen["engine.decode"]
    assert set(stats) == set(HybridStateCache.STEP_STATS)
    assert 0 <= stats["experts_hit"] <= 3 * 4 and stats["experts_spill"] == 0
    assert stats["live_slots"] == 1 and stats["latent_rows"] == 12
    assert stats["state_kib"] == cache.slot_state_bytes() // 1024


def test_config_file_maps_onto_the_model():
    """``chipbench/configs/kimi-linear-48b-a3b.json``: the published widths;
    the depth, the experts held and the vocabulary's rows cut, nothing
    else."""
    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "chipbench/configs/kimi-linear-48b-a3b.json").read_text())
    cfg = family.model_config(config)
    want = KimiLinearConfig(
        n_layer=8, vocab_size=40960, held_experts=(0, 64),
        kda_layers=(1, 2, 3, 5, 6, 7), full_attn_layers=(4, 8))
    none = {"dtype": None, "param_dtype": None}
    assert dataclasses.asdict(cfg) | none == dataclasses.asdict(want) | none
    assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.bfloat16
    assert cfg.layer_recurrent == (True, True, True, False) * 2
    assert sorted(config["reduced"]) == sorted(config["published"]) == sorted([
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"])
    # the widths are the source's
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.kda_num_heads, cfg.kda_head_dim, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim,
            cfg.num_attention_heads, cfg.short_conv_kernel_size,
            cfg.num_experts, cfg.num_experts_per_token,
            cfg.routed_scaling_factor) == (
        2304, 9216, 1024, 32, 128, 512, 64, 128, 128, 32, 4, 256, 8, 2.446)
    shapes = jax.eval_shape(
        lambda: family.build_model(config).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert 3.7e9 < n < 3.85e9          # the issue's 3.77 B: 7.5 GB in bfloat16
    # ``assumed.cache`` and ``assumed.state_dtype``, as the engine builds it
    cache = jax.eval_shape(lambda: HybridStateCache.create(
        cfg, n_slots=1, max_len=6144))
    assert config["assumed"]["state_dtype"] == "float32"
    assert {(s.shape, str(s.dtype)) for s in cache.state} == {
        ((1, 32, 128, 128), "float32")} and len(cache.state) == 6
    assert {(t.shape, str(t.dtype)) for t in cache.tail} == {
        ((1, 3, 12288), "bfloat16")}
    assert (cache.latent.rows.shape, str(cache.latent.rows.dtype)) == (
        (2, 1, 6144, 640), "bfloat16")


def test_bad_configs_are_refused():
    with pytest.raises(ValueError, match="kda_layers"):
        KimiLinearConfig(n_layer=3, kda_layers=(1, 2), full_attn_layers=(2,))
    with pytest.raises(ValueError, match="held_experts"):
        KimiLinearConfig(n_layer=1, kda_layers=(1,), held_experts=(250, 16))


def test_the_example_serves_the_hybrid_stack(capsys):
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "examples/serve_kimi_linear.py"
    spec = importlib.util.spec_from_file_location("serve_kimi_linear", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    assert example.main(["--requests", "3", "--slots", "2", "--periods",
                         "1", "--max-len", "64"]) == 0
    out = capsys.readouterr().out
    assert ("HybridStateCache (3 states of 4x16x16 a slot, 1 layers of 64 "
            "latent rows)") in out
    assert "every token is the uncached forward's argmax" in out
