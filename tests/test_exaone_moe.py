"""The ``exaone_moe`` decoder (window and full attention layers mixed, fewer
K/V heads than query heads, norms on the sublayers' outputs, a share of the
routed experts) against its plain reference
``chipbench/references/exaone_moe.py``, at a tiny size on the CPU, on seeded
random weights: a window of 8, so that twenty tokens wrap a ring twice.

Tolerances. Everything here runs in float32 on both sides, so the two differ
only in the ORDER of float32 sums (blocked and banded against whole softmax,
sorted-and-grouped against masked experts, a ring against rows in order): a
few ulps of values of order one, held to ``1e-4`` absolute on logits whose
range is about one. Greedy tokens through the engine and the scheduler are
compared exactly against the reference's argmax wherever its best two
logits lie more than ``1e-4`` apart.
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import exaone_moe as family
from chipbench.references import exaone_moe as reference
from pytorch_distributed_tpu.models import ExaoneMoEConfig
from pytorch_distributed_tpu.ops import gqa_attention
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    held_share,
    route_sigmoid_topk,
    share_passes,
    share_rows,
)
from pytorch_distributed_tpu.serving import (
    InferenceEngine,
    Request,
    Scheduler,
    WindowedKVCache,
)
from tests import _real_chunks

TOL = 1e-4
WINDOW = 8

#: the configuration file's keys at a tiny size (``families/exaone_moe.py``
#: maps them onto the model's config): layers S S S F S, the first dense,
#: experts 4..7 of 16 held
CONFIG = dict(
    vocab_size=256, max_position_embeddings=4096, num_hidden_layers=5,
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=4,
    router_width=16, held_experts_first=4, num_experts_per_tok=4,
    num_shared_experts=1, routed_scaling_factor=2.5, sliding_window=WINDOW,
    layer_types=["sliding_attention"] * 3 + ["full_attention",
                                             "sliding_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, rms_norm_eps=1e-5,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    assumed=dict(compute_dtype="float32", param_dtype="float32",
                 initializer_range=0.02),
)


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


def _prefilled(model, variables, cache, slot, prompt, bucket):
    """``(last position's logits, cache)`` after ``prompt`` went into
    ``slot`` as the engine puts it there."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, block = model.apply(
        variables, jnp.asarray(padded),
        kv_cache=cache.one_slot(bucket, len(prompt)), position_offset=None)
    assert logits.shape == (1, 1, CONFIG["vocab_size"])
    return logits[0, 0], cache.write_slot(slot, block, len(prompt))


def test_forward_without_a_cache_is_the_reference(served):
    model, variables = served
    tokens = _tokens(1, 40)
    logits = model.apply(variables, tokens[None])[0]
    ref, margin = reference.forward(variables["params"], jnp.asarray(tokens),
                                    CONFIG)
    assert float(jnp.abs(logits - ref).max()) < TOL
    assert margin.shape == (40,) and float(margin.min()) > 0


@pytest.mark.parametrize("knobs", [
    dict(window=WINDOW - 1), dict(window_layers_full=True),
    dict(rotate_full=True), dict(experts_per_token=3),
    dict(round_to="float8_e4m3fn")], ids=lambda k: next(iter(k)))
def test_a_degraded_reference_is_another_function(served, knobs):
    """Each knob the cell's limits are read with moves the logits by far
    more than the program lies from the reference."""
    _, variables = served
    tokens = _tokens(1, 40)
    moved = jnp.abs(_reference(variables, tokens, **knobs)
                    - _reference(variables, tokens)).max()
    assert float(moved) > 100 * TOL


@pytest.mark.parametrize("n_prompt,total,bucket", [
    (3, 7, 8),          # never leaves the first window
    (8, 20, 8),         # the prompt fills a ring exactly, decode wraps it
    (21, 40, 32),       # the prompt wraps twice, decode twice more
], ids=["inside_a_window", "a_ring_exactly", "wrapped"])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        served, n_prompt, total, bucket):
    model, variables = served
    tokens = _tokens(2, total)
    ref = _reference(variables, tokens)
    cache = WindowedKVCache.create(model.cfg, n_slots=1, max_len=64)
    logits, cache = _prefilled(model, variables, cache, 0, tokens[:n_prompt],
                               bucket)
    assert float(jnp.abs(logits - ref[n_prompt - 1]).max()) < TOL
    for t in range(n_prompt, total):
        logits, cache = model.apply(
            variables, jnp.asarray(tokens[None, t:t + 1]), kv_cache=cache,
            position_offset=cache.lengths)
        # one full layer holds t + 1 rows, four rings at most a window each
        assert cache.step_stats.tolist()[-2:] == [
            t + 1, 4 * min(t + 1, WINDOW)]
        cache = cache.advance(1)
        assert float(jnp.abs(logits[0, 0] - ref[t]).max()) < TOL, t
    assert int(cache.lengths[0]) == total


def test_slots_of_mixed_lengths_decode_in_one_batch(served):
    """Three slots, one inside its first window, one past it, one idle:
    every decode step of the batch against each sequence's own reference."""
    model, variables = served
    seqs = {0: (_tokens(3, 12), 4), 2: (_tokens(4, 30), 19)}
    cache = WindowedKVCache.create(model.cfg, n_slots=3, max_len=64)
    for slot, (tokens, n_prompt) in seqs.items():
        _, cache = _prefilled(model, variables, cache, slot,
                              tokens[:n_prompt], 32)
    refs = {slot: _reference(variables, tokens)
            for slot, (tokens, _) in seqs.items()}
    active = jnp.asarray([True, False, True])
    for step in range(8):
        last = np.zeros((3, 1), np.int32)
        for slot, (tokens, n_prompt) in seqs.items():
            last[slot, 0] = tokens[n_prompt + step]
        logits, cache = model.apply(variables, jnp.asarray(last),
                                    kv_cache=cache,
                                    position_offset=cache.lengths)
        cache = cache.advance(1, active)
        for slot, (tokens, n_prompt) in seqs.items():
            want = refs[slot][n_prompt + step]
            assert float(jnp.abs(logits[slot, 0] - want).max()) < TOL
    assert cache.lengths.tolist() == [12, 0, 27]


def test_a_reused_slot_does_not_see_its_predecessors_rows(served):
    """``evict`` resets a length and zeroes nothing. The next occupant is
    shorter than a window, so most of every ring still holds the
    predecessor's rows (made huge here, so that one visible row would
    show): its logits are those of a fresh cache."""
    model, variables = served
    cache = WindowedKVCache.create(model.cfg, n_slots=2, max_len=64)
    _, cache = _prefilled(model, variables, cache, 1, _tokens(5, 30), 32)
    cache = cache.replace(
        k_ring=cache.k_ring * 1e4, v_ring=cache.v_ring * 1e4,
        k_full=cache.k_full * 1e4, v_full=cache.v_full * 1e4).evict(1)
    assert int(cache.lengths[1]) == 0 and float(
        jnp.abs(cache.k_ring[:, 1]).max()) > 1e3
    tokens = _tokens(6, 14)
    ref = _reference(variables, tokens)
    logits, cache = _prefilled(model, variables, cache, 1, tokens[:3], 8)
    assert float(jnp.abs(logits - ref[2]).max()) < TOL
    for t in range(3, 14):          # through the ring's first wrap
        last = jnp.asarray([[0], [tokens[t]]], jnp.int32)
        logits, cache = model.apply(variables, last, kv_cache=cache,
                                    position_offset=cache.lengths)
        cache = cache.advance(1, jnp.asarray([False, True]))
        assert float(jnp.abs(logits[1, 0] - ref[t]).max()) < TOL, t


def test_the_cache_is_one_full_layer_and_four_rings(served):
    model, _ = served
    cache = WindowedKVCache.create(model.cfg, n_slots=3, max_len=64)
    width = CONFIG["num_key_value_heads"] * CONFIG["head_dim"]
    assert cache.k_full.shape == (1, 3, 64, width)
    assert cache.k_ring.shape == (4, 3, WINDOW, width)
    assert cache.n_layers == 5 and cache.n_slots == 3 and cache.max_len == 64
    assert 2 * (cache.k_full.nbytes + cache.k_ring.nbytes) == \
        2 * 4 * 3 * width * (64 + 4 * WINDOW)
    with pytest.raises(ValueError, match="one new token"):
        q = jnp.zeros((3, 2, 8, 16))
        cache.attend(0, q, q[:, :, :2], q[:, :, :2], cache.lengths)
    with pytest.raises(NotImplementedError):
        cache.placed(None)


# -- the attention ops --------------------------------------------------------

def _plain_attention(q, k, v, window):
    """The T x T masked softmax ``blockwise_attention`` never forms."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    s, p = jnp.arange(T)[None, :], jnp.arange(T)[:, None]
    seen = (s <= p) & ((s > p - window) if window else True)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


@pytest.mark.parametrize("T,window", [
    (40, None), (40, 8), (100, 128),      # one block; a window wider than T
    (600, 128), (777, 128),               # banded blocks, and a padded tail
    (2304, None), (1100, None),           # key blocks under a running softmax
])
def test_blockwise_attention_is_the_masked_softmax(T, window):
    ks = jax.random.split(jax.random.key(T), 3)
    q = jax.random.normal(ks[0], (2, T, 4, 16))
    k = jax.random.normal(ks[1], (2, T, 2, 16))
    v = jax.random.normal(ks[2], (2, T, 2, 16))
    got = gqa_attention.blockwise_attention(q, k, v, window=window)
    assert got.shape == q.shape
    assert float(jnp.abs(got - _plain_attention(q, k, v, window)).max()) < TOL


@pytest.mark.parametrize("T,window", [
    (16, None), (128, None), (512, None),        # one key block
    (2048, None), (3072, None),                  # two and three of 1,024
    (1024, 128),                                 # a band: never the kernel
])
def test_the_prefill_kernel_is_blockwise_attention(T, window):
    """``gqa_attention_prefill`` in the Pallas interpreter against the
    ``jax.numpy`` form: 4 query heads on 2 K/V heads of 128."""
    ks = jax.random.split(jax.random.key(T), 3)
    q = jax.random.normal(ks[0], (2, T, 4, 128))
    k = jax.random.normal(ks[1], (2, T, 2, 128))
    v = jax.random.normal(ks[2], (2, T, 2, 128))
    want = gqa_attention.blockwise_attention(q, k, v, window=window)
    got = gqa_attention.prefill_attention(q, k, v, window=window,
                                          kernel=True, interpret=True)
    assert float(jnp.abs(got - want).max()) < TOL
    same = gqa_attention.prefill_attention(q, k, v, window=window)
    assert float(jnp.abs(same - want).max()) == 0      # no kernel: the twin


def test_the_prefill_kernel_visits_the_causal_half():
    qi, ki, last = gqa_attention._prefill_pairs(nq=8, bq=128, bk=512)
    assert list(zip(qi, ki)) == [(i, 0) for i in range(4)] + [
        (i, j) for i in range(4, 8) for j in range(2)]
    assert last.tolist() == [1] * 4 + [0, 1] * 4
    # 32,768 tokens: 4,224 pairs of blocks of the 8,192 there are
    assert len(gqa_attention._prefill_pairs(256, 128, 1024)[0]) == 4224


@pytest.mark.parametrize("depth,n_rows", [
    (128, [128, 5, 0, 77]),               # a ring: one block
    (1024, [1024, 513, 0, 512]),          # two blocks, one, none, one exactly
    (1536, [1, 1100, 1536, 40]),
])
def test_the_read_kernel_is_the_dense_read(depth, n_rows):
    """``gqa_attention_read`` in the Pallas interpreter against the dense
    twin over the same cache: 16 query heads on 2 K/V heads of 128, layer 1
    of 2, bfloat16 as served."""
    ks = jax.random.split(jax.random.key(depth), 3)
    S, Hq, Hkv, D = len(n_rows), 16, 2, 128
    q = jax.random.normal(ks[0], (S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, S, depth, Hkv * D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, S, depth, Hkv * D), jnp.bfloat16)
    n = jnp.asarray(n_rows, jnp.int32)
    dense = gqa_attention.cached_read(q, k, v, 1, n)
    kernel = gqa_attention.cached_read(q, k, v, 1, n, kernel=True,
                                       interpret=True)
    assert kernel.shape == dense.shape == (S, Hq, D)
    # bfloat16 outputs of order one: an ulp is 2^-8
    assert float(jnp.abs(kernel.astype(jnp.float32)
                         - dense.astype(jnp.float32)).max()) < 2.0 ** -6
    for idle in (i for i, r in enumerate(n_rows) if r == 0):
        assert not float(jnp.abs(kernel[idle]).max())


def test_the_dense_read_is_attention_over_the_held_rows():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 16))
    k = jax.random.normal(ks[1], (1, 2, 24, 32))
    v = jax.random.normal(ks[2], (1, 2, 24, 32))
    got = gqa_attention.cached_read(q, k, v, 0, jnp.asarray([24, 9]))
    for s, n in enumerate([24, 9]):
        keys = jnp.repeat(k[0, s, :n].reshape(n, 2, 16), 2, axis=1)
        values = jnp.repeat(v[0, s, :n].reshape(n, 2, 16), 2, axis=1)
        probs = jax.nn.softmax(jnp.einsum("hd,rhd->hr", q[s], keys) / 4, -1)
        want = jnp.einsum("hr,rhd->hd", probs, values)
        assert float(jnp.abs(got[s] - want).max()) < TOL


def test_kernel_reads_only_on_a_tpu(monkeypatch):
    from pytorch_distributed_tpu.ops import decode_attention

    ring = jnp.zeros((4, 2, 128, 1024), jnp.bfloat16)
    assert not gqa_attention.kernel_reads(ring, 128)        # the CPU
    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    assert gqa_attention.kernel_reads(ring, 128)
    assert gqa_attention.kernel_reads(jnp.zeros((1, 2, 32768, 1024)), 128)
    assert not gqa_attention.kernel_reads(jnp.zeros((1, 2, 8, 32)), 16)
    assert not gqa_attention.kernel_reads(jnp.zeros((1, 2, 1000, 1024)), 128)
    assert gqa_attention.kernel_prefills(jnp.zeros((1, 32768, 64, 128)))
    assert gqa_attention.kernel_prefills(jnp.zeros((1, 64, 64, 128)))
    assert gqa_attention.kernel_prefills(jnp.zeros((1, 256, 64, 128)))
    assert not gqa_attention.kernel_prefills(jnp.zeros((1, 48, 64, 128)))
    assert not gqa_attention.kernel_prefills(jnp.zeros((1, 1536, 64, 128)))
    assert not gqa_attention.kernel_prefills(jnp.zeros((1, 512, 8, 16)))
    monkeypatch.undo()
    assert not gqa_attention.kernel_prefills(jnp.zeros((1, 512, 64, 128)))


# -- the expert share ---------------------------------------------------------

def _expert_layer(seed=0, n=24, d=64, E=16, F=32):
    ks = jax.random.split(jax.random.key(seed), 8)
    p = {"router": jax.random.normal(ks[0], (d, E)) * 0.5,
         "router_bias": jnp.zeros((E,)),
         "experts_gate": jax.random.normal(ks[1], (E, d, F)) * d ** -0.5,
         "experts_up": jax.random.normal(ks[2], (E, d, F)) * d ** -0.5,
         "experts_down": jax.random.normal(ks[3], (E, F, d)) * F ** -0.5,
         "shared": {"gate": jax.random.normal(ks[4], (d, F)) * d ** -0.5,
                    "up": jax.random.normal(ks[5], (d, F)) * d ** -0.5,
                    "down": jax.random.normal(ks[6], (F, d)) * F ** -0.5}}
    return p, jax.random.normal(ks[7], (n, d))


def _share(p, x, first, count, k=4, scaling=2.5):
    experts, gates = held_share(*route_sigmoid_topk(
        x, p["router"], p["router_bias"], k, scaling), first, count)
    held = slice(first, first + count)
    return dropless_experts(x, experts, gates, p["experts_gate"][held],
                            p["experts_up"][held], p["experts_down"][held])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight holders of two experts each, the shared expert counted once,
    against the reference's layer with all sixteen held."""
    p, x = _expert_layer()
    sizes = reference._Sizes(num_experts_per_tok=4, num_experts=16,
                             held_experts_first=0, routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(p, x, sizes, round_to=None,
                                    experts_per_token=None)
        shared = reference.ffn(x, p["shared"], jnp.matmul)
        parts = [_share(p, x, first, 2) for first in range(0, 16, 2)]
    total = sum(y for y, _ in parts) + shared
    assert float(jnp.abs(total - want).max()) < TOL
    # every expert some token chose is hit on exactly one holder
    experts, _ = route_sigmoid_topk(x, p["router"], p["router_bias"], 4, 2.5)
    assert sum(int(hit) for _, hit in parts) == len(np.unique(experts))


def test_one_share_is_the_references_share():
    p, x = _expert_layer(seed=1)
    sizes = reference._Sizes(num_experts_per_tok=4, num_experts=4,
                             held_experts_first=8, routed_scaling_factor=2.5)
    held = {k: v[8:12] if k.startswith("experts_") else v
            for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(held, x, sizes, round_to=None,
                                    experts_per_token=None)
        got = _share(p, x, 8, 4)[0] + reference.ffn(x, p["shared"],
                                                    jnp.matmul)
    assert float(jnp.abs(got - want).max()) < TOL


def test_a_holder_of_every_expert_gets_back_what_it_gave():
    """``models.xing4``'s layer: its share is 64 of 64, and the pairs it
    hands ``dropless_experts`` are those ``held_share`` would hand it."""
    p, x = _expert_layer(seed=2)
    experts, gates = route_sigmoid_topk(x, p["router"], p["router_bias"], 4,
                                        2.5)
    own, kept = held_share(experts, gates, 0, 16)
    assert (own == experts).all() and (kept == gates).all()
    none, dropped = held_share(experts, gates, 16, 4)
    assert (none == 4).all() and not float(jnp.abs(dropped).max())
    y, hit = dropless_experts(x, none, dropped, p["experts_gate"][:4],
                              p["experts_up"][:4], p["experts_down"][:4])
    assert not float(jnp.abs(y).max()) and int(hit) == 0


@pytest.mark.parametrize("n,E,favoured,cap,passes", [
    (96, 128, None, 256, 1),            # an eighth of 768 pairs, more or less
    (96, 128, (0, 16), 256, 3),         # every pair is this holder's
    (96, 128, (16, 32), 256, 0),        # none is
    (96, 16, None, 768, 1),             # a holder of every expert
    (32, 128, None, 128, 1),            # a decode step of 32 slots
], ids=["typical", "every_pair_held", "none_held", "all_experts_held",
        "decode_step"])
def test_the_compacted_share_is_the_whole_sort_share(n, E, favoured, cap,
                                                     passes):
    """A holder of 16 of ``E`` experts sorts, gathers and multiplies a
    buffer of ``share_rows`` pairs at a time, and goes round again for a
    routing that overfills it: the sum is that of all ``n * k`` pairs sorted
    at once (no ``num_experts``), and ``hit`` is the same."""
    p, x = _expert_layer(seed=3, n=n, E=E)
    bias = jnp.zeros((E,))
    if favoured:
        bias = bias.at[slice(*favoured)].set(10.0)
    experts, gates = held_share(*route_sigmoid_topk(
        x, p["router"], bias, 8, 2.5), 0, 16)
    weights = [p[name][:16] for name in ("experts_gate", "experts_up",
                                         "experts_down")]
    assert share_rows(n * 8, 16, E) == cap
    held, made = share_passes(experts, 16, E)
    assert int(made) == passes and int(held) == int((experts < 16).sum())
    if favoured:
        assert int(held) == (n * 8 if favoured[0] == 0 else 0)
    want, want_hit = dropless_experts(x, experts, gates, *weights)
    got, hit = jax.jit(dropless_experts, static_argnames="num_experts")(
        x, experts, gates, *weights, num_experts=E)
    assert float(jnp.abs(got - want).max()) < TOL
    assert int(hit) == int(want_hit) == len(np.unique(experts[experts < 16]))
    assert float(jnp.abs(want).max()) > 0.1 or passes == 0


def test_a_prompt_in_chunks_is_the_reference(served, monkeypatch):
    """At sizes where the loops over chunks and the buffer of held pairs
    are real (8 tokens a tokenwise chunk, 16 an expert sublayer's, whose
    buffer is 32 rows, half of their 64 pairs): the logits
    are the reference's, and a prefill leaves its counts in the cache."""
    from pytorch_distributed_tpu.models import exaone_moe as module
    from pytorch_distributed_tpu.ops import dropless_experts as op

    monkeypatch.setattr(module, "_TOKEN_CHUNK", 8)
    monkeypatch.setattr(module, "_EXPERT_CHUNK", 16)
    monkeypatch.setattr(op, "_ROW_TILE", 8)
    assert share_rows(16 * 4, 4, 16) == 32
    model, variables = served
    tokens = _tokens(5, 48)
    logits = model.apply(variables, tokens[None])[0]
    assert float(jnp.abs(logits - _reference(variables, tokens)).max()) < TOL
    cache = WindowedKVCache.create(model.cfg, n_slots=1, max_len=64)
    _, cache = _prefilled(model, variables, cache, 0, tokens, 48)
    stats = dict(zip(cache.STEP_STATS, cache.step_stats.tolist()))
    # four expert layers of three chunks; a chunk's 64 pairs fill two buffers
    assert 0 < stats["experts_fill_pct"] <= 200
    assert 0 <= stats["experts_spill"] <= 4 * 3
    assert (stats["experts_spill"] > 0) == (stats["experts_fill_pct"] > 100)


class _Spans:
    """``serving.engine.span`` replaced: what each span was told."""

    def __init__(self):
        self.seen = {}

    def __call__(self, name, **stats):
        seen = self.seen.setdefault(name, {})
        seen.update(stats)

        class Span:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def set_metadata(self, **stats):
                seen.update(stats)

        return Span()


@_real_chunks.CASES
def test_a_prefill_ends_at_the_last_real_token(served, n_real):
    """``tests/_real_chunks.py``; and what no loop ran is zero: the K rows
    of the full layer from the first chunk without a real token on."""
    block = _real_chunks.check_a_prefill_ends_at_the_last_real_token(
        served[0], n_real)
    ran = -(-n_real // _real_chunks.CHUNK) * _real_chunks.CHUNK
    rows = np.abs(np.asarray(block.k_full[0, 0])).max(axis=-1)
    assert (rows[:ran] > 0).all() and (rows[ran:] == 0).all()


def test_rows_that_no_chunk_computed_are_no_ones_pairs():
    """An expert sublayer's chunk of 16 reaches past the one tokenwise
    chunk of 8 that ran, into rows left zero. Zeros all choose experts
    0..3, and a holder of THOSE (the cells hold from 0) would find the
    eight rows' 32 pairs crowding its buffer of 32 into a second pass: they
    are taken out of the routing, and the real tokens' sums stay in the
    order of one pass."""
    model = family.build_model(CONFIG | dict(held_experts_first=0))
    block = _real_chunks.check_a_prefill_ends_at_the_last_real_token(model, 8)
    stats = dict(zip(block.STEP_STATS, block.step_stats.tolist()))
    assert stats["experts_spill"] == 0 and stats["experts_fill_pct"] <= 100


@pytest.mark.parametrize("which,n_real,bucket,computed", [
    ("looped", 9, 32, 16), ("looped", 32, 32, 32), ("one_chunk", 3, 8, 8),
    ("gpt2", 9, 32, 32)], ids=lambda v: str(v))
def test_the_prefill_span_counts_what_the_loops_ran(
        served, monkeypatch, which, n_real, bucket, computed):
    """``n_computed`` on ``pdt.engine.prefill`` is the loop's own trip
    count times the chunk: the rows the prefill left in the slot's full
    layer are zero exactly where no chunk ran (the loop's carry starts as
    zeros). A bucket of one chunk has no loop, GPT-2 no chunks: both
    compute the bucket."""
    from pytorch_distributed_tpu.serving import engine as engine_module

    spans = _Spans()
    monkeypatch.setattr(engine_module, "span", spans)
    if which == "gpt2":
        from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config

        model = GPT2(GPT2Config(vocab_size=97, n_positions=64, n_embd=48,
                                n_layer=2, n_head=4, dtype=jnp.float32))
        engine = InferenceEngine(
            model, model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)),
            n_slots=2, max_len=64, prefill_buckets=(bucket,))
    elif which == "one_chunk":
        engine = InferenceEngine(*served, n_slots=2, max_len=16,
                                 prefill_buckets=(bucket,))
    else:
        engine = _real_chunks.programs(served[0])[0]
    with pytest.MonkeyPatch.context() as patch:
        _real_chunks.small_sizes(patch)     # the host's expression reads it
        cache, _ = engine.prefill(engine.init_cache(), 1,
                                  _real_chunks.prompt_of(n_real))
    stats = spans.seen["engine.prefill"]
    assert (stats["bucket"], stats["n_real"]) == (bucket, n_real)
    k = cache.k_full[0, 1] if which != "gpt2" else cache.k[0, 1]
    ran = int((np.abs(np.asarray(k)).max(axis=-1) > 0).sum())
    assert stats["n_computed"] == ran == computed


# -- the engine and the scheduler ---------------------------------------------

def test_a_mixed_length_trace_through_the_scheduler_is_the_references(served):
    """Join, evict and refill: more requests than slots, short and long in
    one queue, through ``InferenceEngine`` + ``Scheduler``; every greedy
    token the reference's argmax (where its best two lie apart)."""
    model, variables = served
    engine = InferenceEngine(model, variables, n_slots=3, max_len=64)
    assert type(engine.init_cache()) is WindowedKVCache
    sched = Scheduler(engine, emit_events=False)
    prompts = [_tokens(20 + i, n) for i, n in enumerate([5, 37, 9, 30, 3,
                                                         17, 22])]
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
def test_engine_refuses_what_a_ring_cannot_do(served, kwargs, named):
    model, variables = served
    with pytest.raises(ValueError, match="WindowedKVCache") as e:
        InferenceEngine(model, variables, n_slots=2, max_len=32, **kwargs)
    assert named in str(e.value) and "ring" in str(e.value)


def test_decode_span_carries_the_steps_counts(served, monkeypatch):
    """``experts_hit``, the held pairs and the passes they took beyond the
    first, and the rows the step's reads held ride the read of the step's
    tokens onto the ``pdt.engine.decode`` span."""
    from pytorch_distributed_tpu.serving import engine as engine_module

    model, variables = served
    spans = _Spans()
    monkeypatch.setattr(engine_module, "span", spans)
    engine = InferenceEngine(model, variables, n_slots=2, max_len=32)
    cache = engine.init_cache()
    cache, tok = engine.prefill(cache, 0, _tokens(3, 11))
    cache, toks = engine.decode(cache, np.array([tok, 0], np.int32),
                                np.array([True, False]))
    assert toks.shape == (2,)
    stats = spans.seen["engine.decode"]
    # four expert layers, four held of sixteen, one token of four choices
    assert 0 <= stats["experts_hit"] <= 16
    # at most its four choices are held; one buffer holds them in one pass
    assert 0 <= stats["experts_fill_pct"] <= 100
    assert stats["experts_spill"] == 0
    assert set(stats) == set(WindowedKVCache.STEP_STATS)
    assert stats["kv_full_rows"] == 12 and stats["kv_ring_rows"] == 4 * WINDOW


def test_config_file_maps_onto_the_model():
    """``chipbench/configs/k-exaone-236b-a23b.json``: the published widths;
    the depth, the experts held, the vocabulary's rows and the prediction
    layer cut, nothing else."""
    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "chipbench/configs/k-exaone-236b-a23b.json").read_text())
    cfg = family.model_config(config)
    want = ExaoneMoEConfig(
        n_layer=5, vocab_size=19200, held_experts=(0, 16),
        layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                  "sliding_attention"),
        mlp_layer_types=("dense",) + ("sparse",) * 4)
    none = {"dtype": None, "param_dtype": None}
    assert dataclasses.asdict(cfg) | none == dataclasses.asdict(want) | none
    assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.bfloat16
    assert cfg.layer_windowed == (True, True, True, False, True)
    assert sorted(config["reduced"]) == sorted(config["published"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "num_nextn_predict_layers", "mtp_layer_types", "mtp_sliding_windows"])
    # the widths are the source's
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.sliding_window, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.routed_scaling_factor) == (
        6144, 64, 8, 128, 128, 18432, 2048, 128, 8, 1, 2.5)
    shapes = jax.eval_shape(
        lambda: family.build_model(config).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 3_712_028_416            # 7.42 GB in bfloat16


def test_bad_configs_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        ExaoneMoEConfig(n_layer=2, layer_types=("full_attention",),
                        mlp_layer_types=("dense", "sparse"))
    with pytest.raises(ValueError, match="held_experts"):
        ExaoneMoEConfig(n_layer=1, layer_types=("full_attention",),
                        mlp_layer_types=("dense",), held_experts=(120, 16))


def test_the_example_serves_short_and_long_in_one_queue(capsys):
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "examples/serve_exaone_moe.py"
    spec = importlib.util.spec_from_file_location("serve_exaone_moe", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    assert example.main(["--requests", "4", "--slots", "2"]) == 0
    out = capsys.readouterr().out
    assert "WindowedKVCache (1 full layer(s) of 128 rows, 4 rings of 8)" in out
    assert "every token is the uncached forward's argmax" in out
