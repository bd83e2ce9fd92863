"""Xing4.0 (latent attention, dropless experts, hyper-connections) against
its plain reference ``chipbench/references/xing4.py``, at a tiny size on the
CPU, on seeded random weights.

Tolerances. Everything here runs in float32 on both sides, so the two
differ only in the ORDER of float32 sums (absorbed against expanded
attention, sorted-and-grouped against masked experts, blocked against whole
softmax): a few ulps of values of order one, held to ``1e-4`` absolute on
logits whose range is about one. Greedy tokens through the engine and the
scheduler are compared exactly against the argmax of the uncached forward:
at these sizes the best two logits lie 1e-3 apart or more.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.references import xing4 as reference
from pytorch_distributed_tpu.models import Xing4, Xing4Config
from pytorch_distributed_tpu.models import xing4 as model_file
from pytorch_distributed_tpu.ops import latent_attention as mla
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts,
    route_sigmoid_topk,
)
from pytorch_distributed_tpu.serving import (
    InferenceEngine,
    LatentCache,
    Request,
    Scheduler,
)

TOL = 1e-4

#: the configuration file's keys at a tiny size (``families/xing4.py`` maps
#: them onto the model's config)
CONFIG = dict(
    vocab_size=256, max_position_embeddings=4096, num_hidden_layers=3,
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, first_k_dense_replace=1, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=2, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=64,
                      type="yarn"),
    assumed=dict(compute_dtype="float32", param_dtype="float32",
                 initializer_range=0.02),
)


@pytest.fixture(scope="module")
def served():
    from chipbench.families import xing4 as family

    model = family.build_model(CONFIG)
    variables = jax.jit(model.init)(jax.random.key(0),
                                    jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         CONFIG["vocab_size"]), np.int32)


def test_forward_without_a_cache_is_the_reference(served):
    model, variables = served
    tokens = _tokens(1, 40)
    logits = model.apply(variables, tokens[None])[0]
    ref, margin = reference.forward(variables["params"], tokens, CONFIG)
    assert float(jnp.abs(logits - ref).max()) < TOL
    assert margin.shape == (40,) and float(margin.min()) > 0


def test_prefill_then_decode_through_the_cache_is_the_reference(served):
    """The fresh prefill (expanded attention, the last real position's
    logits only) and every decode step after it (absorbed attention over
    the latent cache) against the reference's one full forward."""
    model, variables = served
    tokens = _tokens(2, 29)
    ref, _ = reference.forward(variables["params"], tokens, CONFIG)
    n_prompt, bucket = 21, 24
    cache = LatentCache.create(model.cfg, n_slots=1, max_len=64)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    fresh = cache.one_slot(bucket, n_prompt)
    logits, block = model.apply(variables, jnp.asarray(padded),
                                kv_cache=fresh, position_offset=None)
    assert logits.shape == (1, 1, CONFIG["vocab_size"])
    assert float(jnp.abs(logits[0, 0] - ref[n_prompt - 1]).max()) < TOL
    cache = cache.write_slot(0, block, n_prompt)
    for t in range(n_prompt, len(tokens)):
        logits, cache = model.apply(
            variables, jnp.asarray(tokens[None, t:t + 1]), kv_cache=cache,
            position_offset=cache.lengths)
        cache = cache.advance(1)
        assert float(jnp.abs(logits[0, 0] - ref[t]).max()) < TOL, t
    assert int(cache.lengths[0]) == len(tokens)
    # two expert layers of eight experts, one token, two experts each
    assert 2 <= int(cache.step_stats[0]) <= 4


def _attention_inputs(T, B=3, H=4, d_c=32, d_n=16, d_r=8, d_v=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, d_n + d_r))
    latent = jax.random.normal(ks[1], (B, T, d_c + d_r))
    kv_b = jax.random.normal(ks[2], (d_c, H, d_n + d_v)) * d_c ** -0.5
    return q, latent, kv_b, dict(d_c=d_c, d_n=d_n, scale=0.3)


@pytest.mark.parametrize("T_new", [1, 3])
def test_absorbed_attention_is_expanded_attention(T_new):
    """Rows written by a fresh prefill, then T_new tokens at each slot's
    own offset through the absorbed dense read: the last T_new rows of the
    expanded causal attention over the whole sequence."""
    T = 12
    q, latent, kv_b, sizes = _attention_inputs(T + T_new)
    whole = mla.expanded_attention(q, latent, kv_b, **sizes)
    rows = jnp.zeros((2, 3, 32, mla.row_width(32, 8)))
    _, rows = mla.latent_attention(q[:, :T], latent[:, :T], kv_b, rows, 1,
                                   None, **sizes)
    offset = jnp.full((3,), T, jnp.int32)
    y, rows = mla.latent_attention(q[:, T:], latent[:, T:], kv_b, rows, 1,
                                   offset, **sizes)
    assert float(jnp.abs(y - whole[:, T:]).max()) < TOL
    assert float(jnp.abs(rows[0]).max()) == 0.0      # the other layer


@pytest.mark.parametrize("T_new,offsets", [
    (1, [0, 5, 31]), (1, [17, 17, 17]), (4, [0, 9, 28])])
def test_kernel_read_is_the_dense_read(T_new, offsets):
    """The lengths-aware kernel (interpret mode) against the dense read on
    one cache, stale rows past every length 30 times larger."""
    q, latent, kv_b, sizes = _attention_inputs(T_new, seed=3)
    offset = jnp.asarray(offsets, jnp.int32)
    W = mla.row_width(32, 8)
    stale = jnp.where(jnp.arange(32)[None, :, None] < offset[:, None, None],
                      1.0, 30.0)
    rows = jax.random.normal(jax.random.key(9), (2, 3, 32, W)) * stale \
        * (jnp.arange(W) < 40)
    dense, r1 = mla.latent_attention(q, latent, kv_b, rows, 1, offset,
                                     **sizes)
    kern, r2 = mla.latent_attention(q, latent, kv_b, rows, 1, offset,
                                    kernel=True, interpret=True, **sizes)
    assert float(jnp.abs(kern - dense).max()) < TOL
    assert bool(jnp.array_equal(r1, r2))


def _masked_experts(x, experts, gates, w_gate, w_up, w_down):
    """Every expert applied to every token under a mask."""
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        g = jnp.where(experts == e, gates, 0.0).sum(-1)
        y += g[:, None] * reference.ffn(x, w_gate[e], w_up[e], w_down[e],
                                        jnp.matmul)
    return y


@pytest.mark.parametrize("routing", ["even", "one_expert", "random"])
def test_dropless_experts_drop_nothing(routing):
    n, d, F, E = 24, 16, 8, 6
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (n, d))
    w_gate, w_up = (jax.random.normal(k, (E, d, F)) * 0.3 for k in ks[1:3])
    w_down = jax.random.normal(ks[3], (E, F, d)) * 0.3
    if routing == "even":
        experts = jnp.stack([jnp.arange(n) % E, (jnp.arange(n) + 1) % E], 1)
    elif routing == "one_expert":            # 24 rows in one group, 5 empty
        experts = jnp.full((n, 1), 4)
    else:
        experts = jax.random.randint(ks[4], (n, 2), 0, E)
        experts = experts.at[:, 1].set((experts[:, 0] + 1 + experts[:, 1]
                                        % (E - 1)) % E)
    gates = jax.random.uniform(ks[4], experts.shape) + 0.5
    y, hit = dropless_experts(x, experts.astype(jnp.int32), gates, w_gate,
                              w_up, w_down)
    want = _masked_experts(x, experts, gates, w_gate, w_up, w_down)
    assert float(jnp.abs(y - want).max()) < TOL
    assert int(hit) == len(np.unique(np.asarray(experts)))


def test_router_gates_are_the_references():
    x = jax.random.normal(jax.random.key(5), (10, 16))
    w = jax.random.normal(jax.random.key(6), (16, 8))
    bias = jnp.zeros((8,)).at[3].set(0.2)
    experts, gates = route_sigmoid_topk(x, w, bias, 3, 2.0)
    s = jax.nn.sigmoid(x @ w)
    order = jnp.argsort(-(s + bias), axis=-1)[:, :3]
    assert bool(jnp.array_equal(jnp.sort(experts, -1), jnp.sort(order, -1)))
    picked = jnp.take_along_axis(s, experts, -1)      # the bias only steers
    assert float(jnp.abs(gates - picked / picked.sum(-1, keepdims=True) * 2.0
                         ).max()) < 1e-6


def test_expert_layer_is_the_references(served):
    model, variables = served
    p = variables["params"]["layer_1_moe"]
    x = jax.random.normal(jax.random.key(7), (2, 9, CONFIG["hidden_size"]))
    (y, hit) = model_file.Experts(model.cfg).apply({"params": p}, x)
    want, _ = reference.experts(p, x.reshape(18, -1), CONFIG)
    assert float(jnp.abs(y.reshape(18, -1) - want).max()) < TOL
    assert 2 <= int(hit) <= 8


def test_hyper_connection_is_doubly_stochastic_and_the_references(served):
    model, variables = served
    p = variables["params"]["layer_1_attn_hc"]
    X = jax.random.normal(jax.random.key(8), (2, 5, 4, CONFIG["hidden_size"]))
    h_pre, h_post, h_res = model_file.HyperConnection(model.cfg).apply(
        {"params": p}, X)
    assert float(jnp.abs(h_res.sum(-1) - 1).max()) < 1e-5
    assert float(jnp.abs(h_res.sum(-2) - 1).max()) < 1e-5
    assert float(h_res.min()) > 0 and float(h_pre.max()) < 1 \
        and float(h_post.max()) < 2

    # the sublayer around a plain function, against the reference's
    def double(x):
        return (2.0 * x,)

    class Around(model_file.nn.Module):
        @model_file.nn.compact
        def __call__(self, X):
            return model_file._connected(model.cfg, "hc", X, double)[0]

    got = Around().apply({"params": {"hc": p}}, X)
    want = jnp.stack([reference.hyper_connected(p, X[b], double, CONFIG)[0]
                      for b in range(2)])
    assert float(jnp.abs(got - want).max()) < TOL


def test_yarn_frequencies_are_the_references(served):
    model, _ = served
    got = np.asarray(model.cfg.inv_freq, np.float64)
    want = reference.inv_freq(CONFIG)
    assert np.allclose(got, want, rtol=1e-6)
    # the fastest pair keeps its frequency, the slowest is divided by 64
    assert np.isclose(got[0], 1.0) and np.isclose(
        got[-1], 10000 ** (-6 / 8) / 64, rtol=1e-6)
    assert np.isclose(model.cfg.softmax_scale,
                      reference.softmax_scale(CONFIG))
    # published widths: the ramp lies between pairs 10 and 23 of 32
    full = mla.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(full[:11], plain[:11]) and np.allclose(
        full[23:], plain[23:] / 64)


def test_engine_and_scheduler_churn_token_exact(served):
    """Join, evict and refill: more requests than slots, through
    ``InferenceEngine`` + ``Scheduler`` as GPT-2 goes, every greedy token
    the argmax of the uncached forward over what came before it (one
    compiled program at the cache's length, once a request: the model is
    causal, so no position sees the ones after it or the padding)."""
    model, variables = served
    uncached = jax.jit(model.apply)
    engine = InferenceEngine(model, variables, n_slots=3, max_len=64)
    assert type(engine.init_cache()) is LatentCache
    sched = Scheduler(engine, emit_events=False)
    prompts = [_tokens(20 + i, n) for i, n in enumerate([5, 17, 9, 30, 12,
                                                         7, 22])]
    news = [6, 3, 8, 4, 7, 5, 6]
    ids = [sched.submit(Request(prompt=p, max_new_tokens=n))
           for p, n in zip(prompts, news)]
    done = {f.request_id: f.tokens for f in sched.run()}
    assert sorted(done) == sorted(ids)
    for rid, prompt, n in zip(ids, prompts, news):
        served_seq = list(prompt) + list(done[rid])
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(served_seq)] = served_seq
        logits = uncached(variables, padded)
        seq = list(prompt)
        for tok in done[rid]:
            assert tok == int(jnp.argmax(logits[0, len(seq) - 1])), \
                (rid, len(seq))
            seq.append(tok)
        assert len(done[rid]) == n


def test_a_shared_prefix_is_served_from_pages_as_from_slots(served):
    """``LatentCache`` names a paged twin (PR 54): the same requests through
    ``cache_kind="paged"``, the second and third behind the first's pages,
    give the slotted engine's tokens."""
    model, variables = served
    document = np.random.default_rng(3).integers(0, 97, 21)
    prompts = [np.concatenate([document, tail]) for tail in (
        [5, 9], [7], [5, 9, 11, 2, 40, 8, 8, 3, 1, 60])]

    def tokens(**kwargs):
        sched = Scheduler(InferenceEngine(
            model, variables, n_slots=2, max_len=64, prefill_len=48,
            **kwargs), emit_events=False)
        out = []
        for prompt in prompts:
            sched.submit(Request(prompt=prompt, max_new_tokens=5))
            out += [done.tokens for done in sched.run()]
        return out, sched

    slotted, _ = tokens()
    paged, sched = tokens(cache_kind="paged", page_size=4, tail_len=8)
    assert paged == slotted
    assert sched.prefill_tokens_cached == 2 * 20
    sched.allocator.check()


@pytest.mark.parametrize("kwargs,named", [
    (dict(spec_k=2, draft_layers=1), "spec_k > 0"),
    (dict(cache_sharding=object()), "cache_sharding"),
])
def test_engine_refuses_what_the_latent_cache_cannot_do(served, kwargs,
                                                        named):
    model, variables = served
    with pytest.raises(ValueError, match="LatentCache") as e:
        InferenceEngine(model, variables, n_slots=2, max_len=32, **kwargs)
    assert named in str(e.value)


def test_decode_span_carries_experts_hit(served, monkeypatch):
    """``experts_hit`` rides the read of the step's tokens: the decode
    program returns one array, and the span gets the count."""
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
    cache, tok = engine.prefill(cache, 0, _tokens(3, 6))
    cache, toks = engine.decode(cache, np.array([tok, 0], np.int32),
                                np.array([True, False]))
    assert toks.shape == (2,)
    assert 2 <= seen["engine.decode"]["experts_hit"] <= 8


def test_config_file_maps_onto_the_model():
    """``chipbench/configs/xing4.0-29b-a4b.json``: the published widths, the
    depth and the prediction module cut, nothing else."""
    import json
    from pathlib import Path

    from chipbench.families import xing4 as family

    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "chipbench/configs/xing4.0-29b-a4b.json").read_text())
    cfg = family.model_config(config)
    assert dataclasses.asdict(cfg) | {"dtype": None, "param_dtype": None} == \
        dataclasses.asdict(Xing4Config(
            n_layer=6, first_k_dense_replace=1)) | {
            "dtype": None, "param_dtype": None}
    assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.bfloat16
    assert sorted(config["reduced"]) == [
        "first_k_dense_replace", "num_hidden_layers",
        "num_nextn_predict_layers"]
    assert config["n_routed_experts"] == 64 and config["vocab_size"] == 131072
