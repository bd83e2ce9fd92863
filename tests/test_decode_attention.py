"""The slotted cache's attention against a float32 reference.

``ops.decode_attention.cached_attention`` writes the new K/V rows into the
whole ``[L, S, Tmax, H*D]`` cache at ``[layer, slot, position]`` and
attends against the folded rows as stored (block-diagonal query rows, or
the plain causal T x T program for a fresh prefill). Every case here holds
it, in bfloat16 as served, to a float32 ``jax.numpy`` attention over the
SAME stored K/V; the cache starts full of stale bytes (a reused slot), and
nothing of them may reach a result.

A sequence's earlier rows have two reads (the dense contraction, and the
lengths-aware Pallas kernel that copies in only the blocks a slot holds):
every case runs both, the kernel in interpret mode, to one tolerance.
``RAGGED`` adds what only the kernel can get wrong: block edges, idle
slots, a full slot. Which read serves a cache is ``KVCache.attend``'s.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
from pytorch_distributed_tpu.ops.decode_attention import cached_attention
from pytorch_distributed_tpu.serving import KVCache

pytestmark = pytest.mark.serving

L, S, TMAX, H, D = 3, 3, 32, 4, 8
C = H * D
LAYER = 1
PREFIX = (7, 0, 19)          # unequal lengths; slot 1 is empty

#: the T new tokens of a step and where they start: decode, speculative
#: verify (k+1 at each slot's own length), a fresh prefill bucket (no
#: offset: the cache is never read), and a draft refeed that rewrites slot
#: 0's last position
CASES = {
    "decode_T1": (1, PREFIX),
    "verify_T5_at_offset": (5, PREFIX),
    "prefill_T16_fresh": (16, None),
    "refeed_T3_one_position_back": (3, (6, 0, 19)),
}


def _reference(q, k_cache, v_cache, pos):
    """float32 attention of ``q [B,T,H,D]`` over layer LAYER of the caches
    as stored: query (b, t) sees positions <= pos[b, t]."""
    B, T = pos.shape
    t_max = k_cache.shape[2]
    k = np.asarray(k_cache[LAYER], np.float32).reshape(B, t_max, H, D)
    v = np.asarray(v_cache[LAYER], np.float32).reshape(B, t_max, H, D)
    scores = jnp.einsum("bthd,bshd->bhts", jnp.asarray(q, jnp.float32), k)
    scores = scores / np.sqrt(D)
    visible = np.arange(t_max)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)


def _stale_cache(seed):
    """A cache every byte of which an earlier occupant left behind."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(0, 30, (L, S, TMAX, C)), jnp.bfloat16),
            jnp.asarray(rng.normal(0, 30, (L, S, TMAX, C)), jnp.bfloat16))


def _occupy(k_cache, v_cache, rng):
    """This occupant's own earlier rows: PREFIX[b] positions of slot b."""
    for b, n in enumerate(PREFIX):
        rows = jnp.asarray(rng.normal(0, 1, (2, n, C)), jnp.bfloat16)
        k_cache = k_cache.at[LAYER, b, :n].set(rows[0])
        v_cache = v_cache.at[LAYER, b, :n].set(rows[1])
    return k_cache, v_cache


#: the two reads of earlier rows; a fresh prefill reads nothing and takes
#: the same arm under both
READS = {"dense": False, "kernel": True}


@pytest.mark.parametrize("read", READS)
@pytest.mark.parametrize("case", CASES)
def test_cached_attention_matches_float32_reference(case, read):
    T, offset = CASES[case]
    rng = np.random.default_rng(0)
    q, k_new, v_new = (
        jnp.asarray(rng.normal(0, 1, (S, T, H, D)), jnp.bfloat16)
        for _ in range(3))
    off = None if offset is None else jnp.asarray(offset, jnp.int32)
    pos = np.arange(T)[None] + (
        np.zeros((S, 1), int) if offset is None
        else np.asarray(offset)[:, None])

    outs = []
    for stale_seed in (1, 2):
        k0, v0 = _occupy(*_stale_cache(stale_seed),
                         np.random.default_rng(3))
        out, k1, v1 = jax.jit(
            cached_attention, static_argnums=5,
            static_argnames=("kernel", "interpret"),
        )(q, k_new, v_new, k0, v0, LAYER, off, kernel=READS[read],
          interpret=True)
        assert out.shape == (S, T, H, D) and out.dtype == jnp.bfloat16
        assert k1.shape == k0.shape and k1.dtype == k0.dtype
        # the new rows lie where the positions say, nothing else moved
        want_k, want_v = np.array(k0), np.array(v0)
        for b in range(S):
            want_k[LAYER, b, pos[b]] = np.asarray(k_new[b]).reshape(T, C)
            want_v[LAYER, b, pos[b]] = np.asarray(v_new[b]).reshape(T, C)
        np.testing.assert_array_equal(np.asarray(k1), want_k)
        np.testing.assert_array_equal(np.asarray(v1), want_v)
        ref = _reference(q, k1, v1, pos)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=2e-2, atol=2e-2)
        outs.append(np.asarray(out))
    # another previous occupant, the very same result: stale bytes in a
    # reused slot are masked, not merely small
    np.testing.assert_array_equal(outs[0], outs[1])


# -- lengths the dense read never cared about -------------------------------
#: the kernel copies a slot's rows in 128-position blocks. Per case: T,
#: each slot's offset, and the cache's positions
RAGGED = {
    "idle_slots_at_0": (1, (0, 37, 0, 0, 201, 0), 256),
    "offsets_on_a_block_edge": (1, (127, 128, 129, 0, 1, 255), 256),
    "every_slot_full": (1, (255,) * 6, 256),
    "verify_T5_straddles_a_block_edge": (
        5, (125, 126, 127, 128, 0, 251), 256),
    "one_to_eight_blocks": (1, (511, 512, 513, 640, 1023, 0), 1024),
}


@pytest.mark.parametrize("case", RAGGED)
def test_kernel_read_of_ragged_lengths_matches_float32_reference(case):
    """The kernel against the float32 reference and against the dense
    read, over a cache whose every position past a slot's new rows holds
    large stale values: one of them reaching a result moves it by far more
    than the tolerance, and two different fills must give the same bits."""
    T, offset, t_max = RAGGED[case]
    slots = len(offset)
    rng = np.random.default_rng(11)
    q, k_new, v_new = (
        jnp.asarray(rng.normal(0, 1, (slots, T, H, D)), jnp.bfloat16)
        for _ in range(3))
    off = jnp.asarray(offset, jnp.int32)
    pos = np.asarray(offset)[:, None] + np.arange(T)[None]
    own = np.arange(t_max)[None, :, None] < pos[:, -1:, None] + 1
    rows = rng.normal(0, 1, (2, L, slots, t_max, C))

    outs = []
    for stale_seed in (1, 2):
        stale = np.random.default_rng(stale_seed).normal(
            0, 1e4, (2, L, slots, t_max, C))
        k0, v0 = (jnp.asarray(np.where(own, r, junk), jnp.bfloat16)
                  for r, junk in zip(rows, stale))
        read = jax.jit(cached_attention, static_argnums=5,
                       static_argnames=("kernel", "interpret"))
        out, k1, v1 = read(q, k_new, v_new, k0, v0, LAYER, off,
                           kernel=True, interpret=True)
        dense, kd, vd = read(q, k_new, v_new, k0, v0, LAYER, off)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(kd))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(vd))
        ref = np.asarray(_reference(q, k1, v1, pos))
        for got in (out, dense):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), ref, rtol=2e-2, atol=2e-2)
        outs.append(np.asarray(out))
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("heads", [12, 16, 17, 20])
def test_kernel_read_adapts_to_the_head_count(heads, T):
    """A token's query rows are one per head, padded to whole 16-row
    tiles: one tile to 16 heads, two from 17 (GPT-2 large has 20). The
    padding rows own no column and must add nothing to a result."""
    slots, t_max, width = 3, 256, heads * D
    offset = (130, 0, 255 - T)
    rng = np.random.default_rng(heads)
    q, k_new, v_new = (
        jnp.asarray(rng.normal(0, 1, (slots, T, heads, D)), jnp.bfloat16)
        for _ in range(3))
    k0, v0 = (jnp.asarray(rng.normal(0, 1, (L, slots, t_max, width)),
                          jnp.bfloat16) for _ in range(2))
    off = jnp.asarray(offset, jnp.int32)
    read = jax.jit(cached_attention, static_argnums=5,
                   static_argnames=("kernel", "interpret"))
    out, k1, v1 = read(q, k_new, v_new, k0, v0, LAYER, off,
                       kernel=True, interpret=True)
    dense, kd, vd = read(q, k_new, v_new, k0, v0, LAYER, off)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(kd))
    pos = np.asarray(offset)[:, None] + np.arange(T)[None]
    k = np.asarray(k1[LAYER], np.float32).reshape(slots, t_max, heads, D)
    v = np.asarray(v1[LAYER], np.float32).reshape(slots, t_max, heads, D)
    scores = jnp.einsum("bthd,bshd->bhts", jnp.asarray(q, jnp.float32), k)
    visible = np.arange(t_max)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(visible[:, None], scores / np.sqrt(D), -jnp.inf)
    ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)
    for got in (out, dense):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref),
            rtol=2e-2, atol=2e-2)


def test_the_cache_chooses_its_read_from_where_it_lies(monkeypatch):
    """``KVCache.attend`` reads with the kernel exactly where the backend
    is a TPU and the cache lies whole on a device; a cache that ``placed``
    laid out over a mesh, and any cache on another backend, reads densely.
    Nobody passes the choice in."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import kv_cache

    cfg = GPT2Config(n_embd=128, n_head=4, n_layer=2, n_positions=128,
                     dtype=jnp.bfloat16)
    chosen = []

    def spy(q, *rest, kernel, **kw):
        chosen.append(kernel)
        return q, rest[2], rest[3]

    monkeypatch.setattr(kv_cache, "cached_attention", spy)
    q = jnp.zeros((2, 1, 4, 32), jnp.bfloat16)
    offset = jnp.zeros((2,), jnp.int32)
    whole = KVCache.create(cfg, n_slots=2, max_len=128)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    laid_out = whole.placed(NamedSharding(mesh, P(None, None, None, "tp")))
    assert laid_out.sharded and not whole.sharded
    assert laid_out.k.sharding.spec == P(None, None, None, "tp")

    for platform, cache, want in (
            ("cpu", whole, False), ("cpu", laid_out, False),
            ("tpu", whole, True), ("tpu", laid_out, False)):
        monkeypatch.setattr(decode_attention, "_platform", lambda: platform)
        cache.attend(0, q, q, q, offset)
        assert chosen.pop() is want, (platform, cache.sharded)
    # any head count is the kernel's: GPT-2 large, 20 heads of 1,280 wide
    # rows, was served before there was a kernel and still is
    large = KVCache.create(
        GPT2Config(n_embd=1280, n_head=20, n_layer=1, n_positions=128,
                   dtype=jnp.bfloat16), n_slots=2, max_len=128)
    q20 = jnp.zeros((2, 1, 20, 64), jnp.bfloat16)
    large.attend(0, q20, q20, q20, offset)
    assert chosen.pop() is True
    # a cache Mosaic cannot copy whole blocks of stays with the dense read
    odd = KVCache.create(cfg, n_slots=2, max_len=96)
    odd.attend(0, q, q, q, offset)
    assert chosen.pop() is False
    # the static field travels with the tree: a traced cache still knows
    assert jax.eval_shape(lambda c: c, laid_out).sharded


def test_cached_attention_rejects_a_cache_that_is_not_the_batch():
    q = jnp.zeros((2, 1, H, D), jnp.bfloat16)
    cache = jnp.zeros((L, S, TMAX, C), jnp.bfloat16)
    with pytest.raises(ValueError, match="slot"):
        cached_attention(q, q, q, cache, cache, 0, jnp.zeros((2,), jnp.int32))


# -- through the model: logits within bf16 tolerance ------------------------
CFG = dict(vocab_size=97, n_positions=48, n_embd=48, n_layer=3, n_head=4)

#: (T, starts at each slot's length?, n_layers)
FORWARDS = {
    "decode_T1": (1, True, None),
    "verify_T4_at_offset": (4, True, None),
    "prefill_bucket_T16": (16, False, None),
    "draft_T1_two_of_three_layers": (1, True, 2),
    "draft_prefill_T16_one_layer": (16, False, 1),
}


@pytest.fixture(scope="module")
def served_and_reference():
    served = GPT2(GPT2Config(dtype=jnp.bfloat16, **CFG))
    variables = served.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return served, variables


@pytest.mark.parametrize("case", FORWARDS)
def test_cached_forward_logits_match_float32_uncached(
        served_and_reference, case):
    """The bf16 cached forward over a bf16 cache against the float32
    uncached forward of the same weights on the whole sequence, at the T
    new positions of every slot; truncated to ``n_layers`` on both sides."""
    served, variables = served_and_reference
    T, at_length, n_layers = FORWARDS[case]
    lengths = (9, 4, 13) if at_length else (0, 0, 0)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 97, n + T) for n in lengths]

    cache = KVCache.create(served.cfg, n_slots=3, max_len=32)
    # a previous occupant's bytes everywhere, then each slot's own prefix
    junk = jnp.asarray(
        np.random.default_rng(7).normal(0, 30, cache.k.shape), cache.k.dtype)
    cache = cache.replace(k=junk, v=-junk)
    if at_length:
        for b, n in enumerate(lengths):
            one = KVCache.create(served.cfg, n_slots=1, max_len=32)
            _, one = served.apply(
                variables, jnp.asarray(seqs[b][None, :n], jnp.int32),
                kv_cache=one, position_offset=None)
            cache = cache.replace(
                k=cache.k.at[:, b, :n].set(one.k[:, 0, :n]),
                v=cache.v.at[:, b, :n].set(one.v[:, 0, :n]))
    new = jnp.asarray(np.stack([s[-T:] for s in seqs]), jnp.int32)
    logits, new_cache = served.apply(
        variables, new, kv_cache=cache, n_layers=n_layers,
        position_offset=jnp.asarray(lengths, jnp.int32) if at_length
        else None)
    assert new_cache.k.shape == cache.k.shape

    nl = n_layers or CFG["n_layer"]
    reference = GPT2(GPT2Config(dtype=jnp.float32,
                                **{**CFG, "n_layer": nl}))
    for b, seq in enumerate(seqs):
        ref = reference.apply(variables, jnp.asarray(seq[None], jnp.int32))
        ref = np.asarray(ref[0, -T:])
        got = np.asarray(logits[b], np.float32)
        span = ref.max() - ref.min()
        assert np.abs(got - ref).max() < 2.0 ** -5 * span, case
    # layers past the truncation keep the previous occupant's bytes
    np.testing.assert_array_equal(
        np.asarray(new_cache.k[nl:]), np.asarray(cache.k[nl:]))
