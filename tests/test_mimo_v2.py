"""The ``mimo_v2`` configuration of the one block (``models/exaone_moe.py``:
K heads of 192 beside V heads of 128, 16 query heads a K/V head in the full
layers and 8 in the window layers, a sink in the window layers' softmax, a
third of a head turned by a base a kind, norms before the sublayers, no
shared expert) against its plain reference
``chipbench/references/mimo_v2.py``, at a tiny size on the CPU that keeps
192 / 128, the two groupings and the third, on seeded random weights: a
window of 8, so that twenty tokens wrap a ring twice.

Tolerances are ``tests/test_exaone_moe.py``'s: float32 on both sides, which
differ only in the ORDER of float32 sums, held to ``1e-4`` absolute on
logits whose range is about one; a kernel in the Pallas interpreter against
its dense twin in bfloat16 to ``2^-6``.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import mimo_v2 as family
from chipbench.references import mimo_v2 as reference
from pytorch_distributed_tpu.ops import gqa_attention
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    held_share,
    route_sigmoid_topk,
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

#: the configuration file's keys at a tiny size (``families/mimo_v2.py``
#: maps them onto the block's config): layers F W W W W F W, the first
#: dense, experts 4..7 of 16 held; 32 query heads on 2 K/V heads in a full
#: layer (16 each) and on 4 in a window layer (8 each)
CONFIG = dict(
    vocab_size=256, max_position_embeddings=4096, num_hidden_layers=7,
    hidden_size=64, num_attention_heads=32, swa_num_attention_heads=32,
    num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=192,
    swa_head_dim=192, v_head_dim=128, swa_v_head_dim=128,
    partial_rotary_factor=0.334, rope_theta=10000000, swa_rope_theta=10000,
    rope_scaling=dict(rope_type="default", type="default"),
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=4, router_width=16,
    held_experts_first=4, num_experts_per_tok=4, n_shared_experts=None,
    routed_scaling_factor=None, sliding_window=WINDOW,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], layernorm_epsilon=1e-5,
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


def test_the_block_has_the_families_parameters_and_no_others(served):
    _, variables = served
    p = variables["params"]
    assert set(p["layer_0_attn"]) == {"q", "k", "v", "o"}        # full
    assert set(p["layer_1_attn"]) == {"q", "k", "v", "o", "sink"}
    assert p["layer_0_attn"]["k"].shape == (64, 2 * 192)
    assert p["layer_0_attn"]["v"].shape == (64, 2 * 128)
    assert p["layer_1_attn"]["k"].shape == (64, 4 * 192)
    assert p["layer_1_attn"]["v"].shape == (64, 4 * 128)
    assert p["layer_1_attn"]["o"].shape == (32 * 128, 64)
    assert p["layer_1_attn"]["sink"].shape == (32,)
    assert float(jnp.abs(p["layer_1_attn"]["sink"]).max()) > 0.5
    assert set(p["layer_1_moe"]) == {"router", "router_bias", "experts_gate",
                                     "experts_up", "experts_down"}
    assert "layer_0_mlp" in p and "layer_0_moe" not in p


def test_forward_without_a_cache_is_the_reference(served):
    model, variables = served
    tokens = _tokens(1, 40)
    logits = model.apply(variables, tokens[None])[0]
    ref, margin = reference.forward(variables["params"], jnp.asarray(tokens),
                                    CONFIG)
    assert float(jnp.abs(logits - ref).max()) < TOL
    assert margin.shape == (40,) and float(margin.min()) > 0


@pytest.mark.parametrize("knobs", [
    dict(window=WINDOW - 1), dict(no_sink=True), dict(rotate_all=True),
    dict(swap_bases=True), dict(value_scale=1.0),
    dict(window_heads_as_full=True), dict(experts_per_token=3),
    dict(round_to="float8_e4m3fn")], ids=lambda k: next(iter(k)))
def test_a_degraded_reference_is_another_function(served, knobs):
    """Each knob the cell's limits are read with moves the logits by more
    than ten times what the program may lie from the reference."""
    _, variables = served
    tokens = _tokens(1, 40)
    moved = jnp.abs(_reference(variables, tokens, **knobs)
                    - _reference(variables, tokens)).max()
    assert float(moved) > 10 * TOL


@pytest.mark.parametrize("n_prompt,total,bucket", [
    (3, 7, 8),          # never leaves the first window
    (8, 20, 8),         # the prompt fills a ring exactly, decode wraps it
    (21, 44, 32),       # the prompt wraps twice, decode three times more
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
        # two full layers hold t + 1 rows, five rings at most a window each
        assert cache.step_stats.tolist()[-2:] == [
            2 * (t + 1), 5 * min(t + 1, WINDOW)]
        cache = cache.advance(1)
        assert float(jnp.abs(logits[0, 0] - ref[t]).max()) < TOL, t
    assert int(cache.lengths[0]) == total


def test_slots_of_mixed_lengths_decode_in_one_batch(served):
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


def test_the_cache_holds_a_width_a_kind_and_one_each_for_k_and_v(served):
    model, _ = served
    cache = WindowedKVCache.create(model.cfg, n_slots=3, max_len=64)
    assert cache.k_full.shape == (2, 3, 64, 2 * 192)
    assert cache.v_full.shape == (2, 3, 64, 2 * 128)
    assert cache.k_ring.shape == (5, 3, WINDOW, 4 * 192)
    assert cache.v_ring.shape == (5, 3, WINDOW, 4 * 128)
    assert cache.n_layers == 7 and cache.n_slots == 3 and cache.max_len == 64
    block = cache.one_slot(32, 5)
    assert block.k_full.shape == (2, 1, 32, 384)
    assert block.v_full.shape == (2, 1, 32, 256)
    assert block.k_ring.shape == (5, 1, WINDOW, 768)
    assert block.v_ring.shape == (5, 1, WINDOW, 512)


def test_the_published_cache_is_five_gigabytes_and_not_thirty_five():
    """40 slots of 24,576 rows at the configuration file's widths: two full
    layers of 2,560 B a row, five rings of 5,120 B a row."""
    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "chipbench/configs/mimo-v2.5.json").read_text())
    cache = jax.eval_shape(lambda: WindowedKVCache.create(
        family.model_config(config), n_slots=40, max_len=24576))
    assert cache.k_full.shape == (2, 40, 24576, 768)
    assert cache.v_full.shape == (2, 40, 24576, 512)
    assert cache.k_ring.shape == (5, 40, 128, 1536)
    assert cache.v_ring.shape == (5, 40, 128, 1024)
    held = sum(a.size * a.dtype.itemsize for a in (
        cache.k_full, cache.v_full, cache.k_ring, cache.v_ring))
    assert held == 2 * 40 * 24576 * 2560 + 5 * 40 * 128 * 5120 == 5_164_236_800


# -- the attention ops at unequal widths, with and without a sink ------------

def _plain_attention(q, k, v, window, sink=None):
    """The T x T masked softmax, the sink one more column of it."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    s, p = jnp.arange(T)[None, :], jnp.arange(T)[:, None]
    seen = (s <= p) & ((s > p - window) if window else True)
    scores = jnp.where(seen, scores, -jnp.inf)
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink[None, :, None, None], scores.shape[:3] + (1,))], -1)
    probs = jax.nn.softmax(scores, -1)[..., :T]
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _qkv(seed, T, Hq, Hkv, dtype=jnp.float32, B=2):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, T, Hq, 192), dtype),
            jax.random.normal(ks[1], (B, T, Hkv, 192), dtype),
            jax.random.normal(ks[2], (B, T, Hkv, 128), dtype),
            jax.random.normal(ks[3], (Hq,), jnp.float32))


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("T,window,Hkv", [
    (40, None, 1), (40, 8, 2), (600, 128, 2), (2304, None, 1)])
def test_blockwise_attention_takes_narrower_values_and_a_sink(
        T, window, Hkv, with_sink):
    q, k, v, sink = _qkv(T, T, 16, Hkv, B=1)
    sink = sink if with_sink else None
    got = gqa_attention.blockwise_attention(q, k, v, window=window, sink=sink)
    assert got.shape == (1, T, 16, 128)
    want = _plain_attention(q, k, v, window, sink)
    assert float(jnp.abs(got - want).max()) < TOL
    if with_sink:       # and the sink is not nothing
        none = gqa_attention.blockwise_attention(q, k, v, window=window)
        assert float(jnp.abs(got - none).max()) > 100 * TOL


@pytest.mark.parametrize("T,G", [
    (16, 16), (64, 16), (128, 8), (2048, 16), (3072, 8)])
def test_the_prefill_kernel_takes_keys_of_192_and_values_of_128(T, G):
    """``gqa_attention_prefill`` in the Pallas interpreter against the
    ``jax.numpy`` form: 16 query heads a K/V head walk 64 positions a step,
    8 walk 128."""
    q, k, v, _ = _qkv(T + G, T, 2 * G, 2, B=1)
    want = gqa_attention.blockwise_attention(q, k, v)
    got = gqa_attention.prefill_attention(q, k, v, kernel=True,
                                          interpret=True)
    assert got.shape == (1, T, 2 * G, 128)
    assert float(jnp.abs(got - want).max()) < TOL
    assert gqa_attention._kernel_query_block(G) == 1024 // max(G, 8)


def test_packed_keys_unpack_and_whole_tiles_lie_as_they_are():
    k = jax.random.normal(jax.random.key(0), (3, 5, 4, 192))
    rows = gqa_attention.pack_keys(k)
    assert rows.shape == (3, 5, 768)
    # every head's first 128 columns, then the heads' last 64 side by side
    assert (rows[..., 128:256] == k[..., 1, :128]).all()
    assert (rows[..., 512 + 64:512 + 128] == k[..., 1, 128:]).all()
    assert (gqa_attention._unpack_keys(rows, 192) == k).all()
    whole = jax.random.normal(jax.random.key(1), (3, 5, 4, 128))
    assert (gqa_attention.pack_keys(whole) == whole.reshape(3, 5, 512)).all()


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("depth,n_rows,Hkv,G", [
    (128, [128, 5, 0, 1], 4, 8),          # a ring: one block; none, one row
    (128, [128, 128, 77, 0], 8, 8),       # the published ring's 8 K/V heads
    (1024, [1024, 513, 0, 512], 2, 16),   # two blocks, one, none, one exactly
    (1536, [1, 1100, 1536, 128], 4, 16),  # the published full layer's heads
])
def test_the_read_kernel_is_the_dense_read_at_unequal_widths(
        depth, n_rows, Hkv, G, with_sink):
    """``gqa_attention_read`` in the Pallas interpreter against the dense
    twin over the same PACKED cache, layer 1 of 2, bfloat16 as served: a
    slot that holds no row, one row, exactly 128, and counts that are no
    multiple of the block."""
    ks = jax.random.split(jax.random.key(depth + Hkv), 4)
    S, Hq = len(n_rows), Hkv * G
    q = jax.random.normal(ks[0], (S, Hq, 192), jnp.bfloat16)
    k = gqa_attention.pack_keys(jax.random.normal(
        ks[1], (2, S, depth, Hkv, 192), jnp.bfloat16))
    v = jax.random.normal(ks[2], (2, S, depth, Hkv * 128), jnp.bfloat16)
    sink = jax.random.normal(ks[3], (Hq,)) if with_sink else None
    n = jnp.asarray(n_rows, jnp.int32)
    dense = gqa_attention.cached_read(q, k, v, 1, n, sink=sink)
    kernel = gqa_attention.cached_read(q, k, v, 1, n, sink=sink, kernel=True,
                                       interpret=True)
    assert kernel.shape == dense.shape == (S, Hq, 128)
    assert float(jnp.abs(kernel.astype(jnp.float32)
                         - dense.astype(jnp.float32)).max()) < 2.0 ** -6
    for idle in (i for i, r in enumerate(n_rows) if r == 0):
        assert not float(jnp.abs(kernel[idle]).max())
        assert not float(jnp.abs(dense[idle]).max())
    # the dense twin is the softmax over the held rows, the sink a column
    s = n_rows.index(max(n_rows))
    rows = max(n_rows)
    keys = gqa_attention._unpack_keys(k[1, s, :rows], 192)[None]
    want = _plain_attention(
        jnp.zeros((1, rows, Hq, 192)).at[0, -1].set(q[s].astype(jnp.float32)),
        keys.astype(jnp.float32),
        v[1, s, :rows].reshape(1, rows, Hkv, 128).astype(jnp.float32),
        None, sink)[0, -1]
    assert float(jnp.abs(dense[s].astype(jnp.float32) - want).max()) \
        < 2.0 ** -6


def test_kernels_take_the_uneven_widths_only_on_a_tpu(monkeypatch):
    from pytorch_distributed_tpu.ops import decode_attention

    full_k = jnp.zeros((2, 2, 24576, 768), jnp.bfloat16)
    full_v = jnp.zeros((2, 2, 24576, 512), jnp.bfloat16)
    ring_k = jnp.zeros((5, 2, 128, 1536), jnp.bfloat16)
    ring_v = jnp.zeros((5, 2, 128, 1024), jnp.bfloat16)
    q16 = jnp.zeros((1, 24576, 64, 192))
    k4, v4 = jnp.zeros((1, 24576, 4, 192)), jnp.zeros((1, 24576, 4, 128))
    assert not gqa_attention.kernel_reads(full_k, 192, full_v)      # the CPU
    assert not gqa_attention.kernel_prefills(q16, k4, v4)
    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    assert gqa_attention.kernel_reads(full_k, 192, full_v)
    assert gqa_attention.kernel_reads(ring_k, 192, ring_v)
    # three K heads of 192: the third's tail would share a tile with no one
    assert not gqa_attention.kernel_reads(
        jnp.zeros((1, 2, 128, 576)), 192, jnp.zeros((1, 2, 128, 384)))
    # V heads of 96 are no lane tiles
    assert not gqa_attention.kernel_reads(ring_k, 192,
                                          jnp.zeros((5, 2, 128, 768)))
    assert gqa_attention.kernel_prefills(q16, k4, v4)
    assert gqa_attention.kernel_prefills(q16[:, :2048], k4[:, :2048],
                                         v4[:, :2048])
    assert gqa_attention.kernel_prefills(q16[:, :64], k4[:, :64], v4[:, :64])
    # 96 is no whole block of 64 positions, nor a power of two under it
    assert not gqa_attention.kernel_prefills(q16[:, :96], k4[:, :96],
                                             v4[:, :96])


# -- the expert share ---------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen holders of two experts each of 32, no shared expert and no
    scale on the gates, against the reference's layer with all 32 held."""
    ks = jax.random.split(jax.random.key(0), 5)
    n, d, E, F = 24, 64, 32, 32
    p = {"router": jax.random.normal(ks[0], (d, E)) * 0.5,
         "router_bias": jnp.zeros((E,)),
         "experts_gate": jax.random.normal(ks[1], (E, d, F)) * d ** -0.5,
         "experts_up": jax.random.normal(ks[2], (E, d, F)) * d ** -0.5,
         "experts_down": jax.random.normal(ks[3], (E, F, d)) * F ** -0.5}
    x = jax.random.normal(ks[4], (n, d))
    sizes = reference._Sizes(num_experts_per_tok=8, n_routed_experts=E,
                             held_experts_first=0)

    def share(first, count):
        experts, gates = held_share(*route_sigmoid_topk(
            x, p["router"], p["router_bias"], 8, 1.0), first, count)
        held = slice(first, first + count)
        return dropless_experts(x, experts, gates, p["experts_gate"][held],
                                p["experts_up"][held],
                                p["experts_down"][held])

    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(p, x, sizes, round_to=None,
                                    experts_per_token=None)
        parts = [share(first, 2) for first in range(0, E, 2)]
        one = dict(p, **{name: p[name][6:8] for name in (
            "experts_gate", "experts_up", "experts_down")})
        own, _ = reference.experts(one, x, reference._Sizes(
            num_experts_per_tok=8, n_routed_experts=2, held_experts_first=6),
            round_to=None, experts_per_token=None)
    assert float(jnp.abs(sum(y for y, _ in parts) - want).max()) < TOL
    assert float(jnp.abs(parts[3][0] - own).max()) < TOL
    experts, _ = route_sigmoid_topk(x, p["router"], p["router_bias"], 8, 1.0)
    assert sum(int(hit) for _, hit in parts) == len(np.unique(experts))


def test_a_prompt_in_chunks_is_the_reference(served, monkeypatch):
    """At sizes where the loops over chunks are real (8 tokens a tokenwise
    chunk, 16 an expert sublayer's): the norm before a sublayer, the
    rotation of a third and the value's scale run inside them."""
    from pytorch_distributed_tpu.models import exaone_moe as module
    from pytorch_distributed_tpu.ops import dropless_experts as op

    monkeypatch.setattr(module, "_TOKEN_CHUNK", 8)
    monkeypatch.setattr(module, "_EXPERT_CHUNK", 16)
    monkeypatch.setattr(op, "_ROW_TILE", 8)
    model, variables = served
    tokens = _tokens(5, 48)
    logits = model.apply(variables, tokens[None])[0]
    assert float(jnp.abs(logits - _reference(variables, tokens)).max()) < TOL
    cache = WindowedKVCache.create(model.cfg, n_slots=1, max_len=64)
    logits, cache = _prefilled(model, variables, cache, 0, tokens, 48)
    assert float(jnp.abs(logits - _reference(variables, tokens)[-1]).max()) \
        < TOL


@_real_chunks.CASES
def test_a_prefill_ends_at_the_last_real_token(served, n_real):
    """``tests/_real_chunks.py``: the sink and the heads of two widths and
    two groupings through the band's bounded loop; a ring of wider keys
    than values holds real tokens' rows alone."""
    _real_chunks.check_a_prefill_ends_at_the_last_real_token(served[0], n_real)


# -- the engine and the scheduler ---------------------------------------------

def test_a_mixed_length_trace_through_the_scheduler_is_the_references(served):
    """Join, evict and refill: more requests than slots, short and long in
    one queue, through ``InferenceEngine`` + ``Scheduler`` with no branch
    for this configuration in either; every greedy token the reference's
    argmax (where its best two lie apart)."""
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


def test_config_file_maps_onto_the_block():
    """``chipbench/configs/mimo-v2.5.json``: the published widths; the
    depth, the kinds of the layers kept, the experts held and the
    vocabulary's rows cut, nothing else."""
    from pytorch_distributed_tpu.models import ExaoneMoEConfig

    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "chipbench/configs/mimo-v2.5.json").read_text())
    cfg = family.model_config(config)
    assert cfg.layer_windowed == (False, True, True, True, True, False, True)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 6
    assert sorted(config["reduced"]) == sorted(config["published"]) == sorted([
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"])
    # the widths are the source's
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.window_key_value_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.rotary_dim, cfg.sliding_window, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.routed_scaling_factor, cfg.value_scale, cfg.rope_theta,
            cfg.full_rope_theta, cfg.held_experts, cfg.vocab_size) == (
        4096, 64, 4, 8, 192, 128, 64, 128, 16384, 2048, 256, 8, 0, 1.0,
        0.707, 1e4, 1e7, (0, 16), 19072)
    assert cfg.norm_first and cfg.window_sink and not cfg.qk_norm
    assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.bfloat16
    # every field the family sets is one K-EXAONE's block leaves at default
    defaults = ExaoneMoEConfig(n_layer=0)
    assert not (defaults.norm_first or defaults.window_sink
                or defaults.v_head_dim or defaults.window_key_value_heads
                or defaults.rotary_dim or defaults.full_rope_theta)
    assert defaults.qk_norm and defaults.value_scale == 1.0
    shapes = jax.eval_shape(
        lambda: family.build_model(config).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 3_429_955_392            # 6.86 GB in bfloat16


@pytest.mark.parametrize("key,value,named", [
    ("swa_head_dim", 128, "swa_head_dim"),
    ("add_full_attention_sink_bias", True, "sink in the full layers"),
    ("n_shared_experts", 1, "shared expert"),
])
def test_a_file_the_block_cannot_state_is_refused(key, value, named):
    with pytest.raises(ValueError, match=named):
        family.model_config(dict(CONFIG, **{key: value}))
