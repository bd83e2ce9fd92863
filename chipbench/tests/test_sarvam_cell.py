"""What PR 54 brought to the benchmark: the ``sarvam_mla`` family and its
cell resolve to files, the configuration keeps the catalog's numbers, the
session generator is a function of ``base_seed`` that ``--seed`` rotates and
every ask of a document shares the document's tokens to the last one, the
cost functions of ``kernel_costs_latent_paged.py`` against hand counts, the
new readers on hand-made spans, the sample a run checks, and the driver end
to end at a size only this test chooses."""

import dataclasses
import json

import numpy as np
import pytest

from chipbench import cells, kernel_costs_latent_paged as costs
from chipbench.drivers import serve_sessions_by_family as driver
from chipbench.program_trace import HostSpan
from chipbench.readers import (prefill_kernel_roofline_where,
                               program_span_ratio, program_span_stat,
                               program_span_where)
from chipbench.trace_reduce import DeviceTrace, Reduced

BENCH = cells.load_benchmark()
CELL = "sarvam-105b.serve-doc-sessions"
NEW = {"prefix_cached_tokens_pct", "pool_pages_held_pct",
       "pages_reclaimed_step", "latent_paged_read_roofline_pct",
       "latent_prefill_roofline_pct", "prefill_warm_ms_p50",
       "prefill_cold_ms_per_ktok"}


def test_the_cell_resolves_to_files():
    cell = cells.resolve(BENCH, CELL)
    assert cell.chips == 1 and cell.config["family"] == "sarvam_mla"
    assert cell.traffic["kind"] == "serve_sessions_by_family"
    assert cells.load_driver(cell.traffic["kind"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"serve_ttft_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"prefill_ms_p50", "decode_moe_ms_step",
                  "decode_latent_attn_ms_step", "moe_experts_hit_step",
                  "prefill_moe_ms_p50", "moe_spill_step", "gen_late_p95_ms",
                  "prefill_real_tokens_pct", "ttft_admit_ms_mean"} <= names
    # the slotted kernel's shares count another kernel's time
    assert not {"latent_read_roofline_pct",
                "latent_rows_read_roofline_pct"} & names
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
        assert metric["moves"] in e2e, metric["name"]
    assert len(BENCH["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_metric_is_the_new_cells_alone(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_ttft_p95_ms"
    assert metric.endswith("_roofline_pct") <= (entry["unit"] == "%")


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every number of the catalog row's ``config`` is the file's, but for
    the keys under ``reduced``, each with its published value beside; the
    nested YaRN group is the source's whole."""
    config = cells.resolve(BENCH, CELL).config
    declared = {c["name"]: c for c in BENCH["configs"]}["sarvam-105b"]
    assert declared["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert declared["source"] == config["source"] == (
        "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json")
    published = {
        "default_theta": 10000, "first_k_dense_replace": 1, "head_dim": 576,
        "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "v_head_dim": 128,
        "vocab_size": 262144}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
    assert config["use_qk_norm"] is True and "q_lora_rank" not in config
    assert config["moe_router_enable_expert_bias"] is True
    assert config["tie_word_embeddings"] is False
    assert config["model_type"] == "sarvam_mla"
    assert config["router_width"] == config["published"]["num_experts"]
    assert config["held_experts_first"] == 0
    assert "eight pipeline stages of four chips" in config["deployment"]
    for reading in ("use_qk_norm", "router", "norm_placement", "rope_pairs"):
        assert config["assumed"][reading]
    # the floors of a configuration that is still the model
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]


def test_the_family_builds_the_cut_the_issue_reckons():
    import jax
    import jax.numpy as jnp

    from chipbench.families import sarvam_mla as family

    config = cells.resolve(BENCH, CELL).config
    model = family.build_model(config)
    cfg = model.cfg
    assert (cfg.n_layer, cfg.kda_layers, len(cfg.full_attn_layers)) == (
        5, (), 5)
    assert (cfg.num_experts, cfg.held_experts) == (128, (0, 32))
    assert (cfg.rope_theta, cfg.rope_factor) == (10000.0, 40.0)
    assert cfg.dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 4535e6) < 0.01 * 4535e6
    from pytorch_distributed_tpu.serving.kv_cache import LatentCache
    assert model.cache_class is LatentCache
    with pytest.raises(ValueError, match="YaRN"):
        family.build_model(dict(config, rope_scaling=dict(
            config["rope_scaling"], type="linear")))


# -- the sessions -------------------------------------------------------------

def _traffic(**kw):
    return dict(cells.resolve(BENCH, CELL).traffic, **kw)


def test_the_sessions_are_the_issues_traffic():
    traffic = _traffic()
    assert (traffic["n_slots"], traffic["max_len"], traffic["page_size"],
            traffic["n_pages"]) == (24, 32768, 128, 3584)
    assert (traffic["warm_seconds"], traffic["trace_seconds"],
            traffic["base_seed"]) == (24.0, 8.0, 54)
    asks = driver.sessions(traffic, 7, 51.0, 65536)
    assert asks == sorted(asks, key=lambda a: a.due_s)
    docs = {}
    for a in asks:
        docs.setdefault(a.doc, []).append(a)
    lens = np.array([d[0].doc_len for d in docs.values()])
    assert lens.min() >= 4096 and lens.max() <= 28672
    assert 9000 < np.median(lens) < 13000
    for doc, of_doc in docs.items():
        of_doc.sort(key=lambda a: a.ask)
        first = of_doc[0]
        # a load document's asks past the tail are left out, from the last
        assert [a.ask for a in of_doc] == list(range(len(of_doc)))
        assert 1 <= len(of_doc) <= 5
        for a in of_doc:
            # every ask shares the document's tokens to the last one
            assert a.doc_len == first.doc_len
            np.testing.assert_array_equal(a.prompt[:a.doc_len],
                                          first.prompt[:first.doc_len])
            assert 64 <= len(a.prompt) - a.doc_len <= 448
            assert 64 <= a.output_len <= 256
            assert a.prompt.max() < 65536
        assert all(b.due_s > a.due_s for a, b in zip(of_doc, of_doc[1:]))
    whole = [d for d in docs.values() if d[-1].due_s < 24 + 51]
    assert {len(d) for d in whole} == {3, 4, 5}
    # measured: every ask of the documents that arrive in the window, the
    # same number under every seed; the others are load
    measured = {}
    for a in asks:
        if a.measured:
            measured.setdefault(a.doc, []).append(a)
    n_docs = round(traffic["arrivals"]["rate_per_s"] / 4 * 51)
    assert len(measured) == n_docs
    for of_doc in measured.values():
        assert 24.0 <= of_doc[0].due_s < 75.0 and of_doc[0].ask == 0
        assert len(of_doc) in (3, 4, 5)
        assert all(a.measured for a in docs[of_doc[0].doc])
    counts = {sum(a.measured for a in driver.sessions(traffic, seed, 51.0,
                                                      65536))
              for seed in (7, 11, 2 ** 31 + 54)}
    assert counts == {sum(len(d) for d in measured.values())}
    assert all(a.due_s < 24 + 51 + 30 for a in asks if not a.measured)
    # questions differ between the asks of a document
    two = next(d for d in docs.values() if len(d) > 1)
    assert not np.array_equal(two[0].prompt[two[0].doc_len:][:64],
                              two[1].prompt[two[1].doc_len:][:64])


def test_the_sessions_are_a_function_of_base_seed_and_the_seed_rotates_them():
    traffic = _traffic()

    def shape(asks):
        return sorted((a.doc_len, len(a.prompt) - a.doc_len, a.output_len)
                      for a in asks if a.ask == 0)

    a, b = (driver.sessions(traffic, seed, 51.0, 65536)
            for seed in (7, 2 ** 31 + 54))
    again = driver.sessions(traffic, 7, 51.0, 65536)
    assert [x.due_s for x in a] == [x.due_s for x in again]
    np.testing.assert_array_equal(a[5].prompt, again[5].prompt)
    # the same documents' lengths under every seed, from another start
    assert sorted(x.doc_len for x in a if x.ask == 0) == sorted(
        x.doc_len for x in b if x.ask == 0)
    assert [x.doc_len for x in a if x.ask == 0] != [
        x.doc_len for x in b if x.ask == 0]
    assert not np.array_equal(a[0].prompt[:64], b[0].prompt[:64])
    other = driver.sessions(_traffic(base_seed=55), 7, 51.0, 65536)
    assert shape(other) != shape(a) or [x.due_s for x in other] != [
        x.due_s for x in a]


# -- the cost functions and the readers ---------------------------------------

def test_the_costs_against_a_hand_count():
    config = cells.resolve(BENCH, CELL).config
    assert costs.latent_paged_read_bytes(1000, config) == 1000 * 1152
    # 5 layers x the causal half of 3 tokens (6 pairs) x 64 heads x 320 x 2
    assert costs.latent_prefill_flops(3, config) == 5 * 6 * 64 * 320 * 2
    # the issue's count at 28k: 80 TFLOP of attention over 5 layers
    assert 78e12 < costs.latent_prefill_flops(28000, config) < 82e12


def _context(spans, modules=(), ops=(), config=None):
    reduced = Reduced(
        devices=[DeviceTrace(ordinal=0, ops=list(ops), modules=list(modules),
                             async_ops=[])],
        spans=[], window=(0.0, 10.0))
    return {"trace": reduced, "program_spans": list(spans),
            "counters": {"device_kind": "TPU v5 lite",
                         "config": config or cells.resolve(BENCH,
                                                           CELL).config}}


def _args(metric):
    return cells.load_reader(metric)[1]


PREFILLS = [
    HostSpan("engine.prefill", 1.0, 1.5, {"bucket": 8192, "n_real": 5000,
                                          "cached_len": 0, "cold": 1}),
    HostSpan("engine.prefill", 2.0, 2.02, {"bucket": 256, "n_real": 200,
                                           "cached_len": 4864, "cold": 0}),
    HostSpan("engine.prefill", 3.0, 3.04, {"bucket": 512, "n_real": 300,
                                           "cached_len": 9984, "cold": 0}),
    HostSpan("engine.prefill", 4.0, 4.06, {"bucket": 512, "n_real": 400,
                                           "cached_len": 128, "cold": 0}),
]


def test_the_warm_and_the_cold_prefills_are_read_apart():
    context = _context(PREFILLS)
    warm = program_span_where.read(context, **_args("prefill_warm_ms_p50"))
    assert abs(warm - 40.0) < 1e-6            # of 20, 40 and 60 ms
    cold = program_span_where.read(context,
                                   **_args("prefill_cold_ms_per_ktok"))
    assert abs(cold - 100.0) < 1e-6           # 500 ms over 5,000 tokens
    # the parent: no such statistic on its spans, or no span at all
    bare = _context([HostSpan("engine.prefill", 1.0, 1.5,
                              {"bucket": 8192, "n_real": 5000})])
    for metric in ("prefill_warm_ms_p50", "prefill_cold_ms_per_ktok"):
        assert program_span_where.read(bare, **_args(metric)) is None
        assert program_span_where.read(_context([]), **_args(metric)) is None


def test_the_pool_metrics_read_the_schedulers_counts():
    steps = [HostSpan("sched.step", i + 0.0, i + 0.5, dict(
        step=i, pages=4095, pages_free=4095 - held, pages_held=held,
        pages_reclaimed=gone, radix_hits=i, radix_misses=1))
        for i, (held, gone) in enumerate([(1000, 0), (2000, 3), (3142, 6)])]
    admits = [HostSpan("sched.admit", 0.1, 0.2, {"prompt_len": 1000,
                                                 "cached_len": 0}),
              HostSpan("sched.admit", 1.1, 1.2, {"prompt_len": 1100,
                                                 "cached_len": 896}),
              HostSpan("sched.admit", 2.1, 2.2, {"prompt_len": 1200,
                                                 "cached_len": 896})]
    context = _context(steps + admits)
    held = program_span_ratio.read(context, **_args("pool_pages_held_pct"))
    assert abs(held - 100 * 6142 / (3 * 4095)) < 1e-9
    gone = program_span_stat.read(context, **_args("pages_reclaimed_step"))
    assert gone == 3.0
    cached = program_span_ratio.read(context,
                                     **_args("prefix_cached_tokens_pct"))
    assert abs(cached - 100 * 1792 / 3300) < 1e-9
    bare = _context([HostSpan("sched.step", 0.0, 0.5, {"step": 0,
                                                       "kv_rows": 5})])
    assert program_span_ratio.read(
        bare, **_args("pool_pages_held_pct")) is None
    assert program_span_stat.read(
        bare, **_args("pages_reclaimed_step")) is None


def test_the_prefill_roofline_counts_the_cold_prompts_alone():
    names = {8192: {"custom-call.1":
                    "jit(paged_prefill_fn)/KimiLinear/mla/layer_0_attn/"
                    "prefill/jit(_kernel_prefill)/gqa_attention_prefill/"
                    "pallas_call"}}
    modules = [("jit_paged_prefill_fn(3)", 1.0, 1.5)]
    ops = [("%custom-call.1 = bf16[8] custom-call(...)", 1.0, 1.2)]
    context = _context(PREFILLS, modules, ops)
    context["prefill_op_names"] = lambda bucket: names.get(bucket, {})
    share = prefill_kernel_roofline_where.read(
        context, **_args("latent_prefill_roofline_pct"))
    config = context["counters"]["config"]
    want = 100 * costs.latent_prefill_flops(5000, config) / 0.2 / 197e12
    assert abs(share - want) < 1e-9
    context["prefill_op_names"] = lambda bucket: {}
    context.pop("prefill_runs")
    assert prefill_kernel_roofline_where.read(
        context, **_args("latent_prefill_roofline_pct")) is None
    assert prefill_kernel_roofline_where.read(
        {"trace": None}, **_args("latent_prefill_roofline_pct")) is None


# -- the sample a run checks --------------------------------------------------

@dataclasses.dataclass
class _Served:
    arrivals: list
    tokens: dict
    admitted: dict


def _ask(doc, ask, doc_len, measured=True):
    return driver.Ask(0.0, np.zeros(doc_len + 70, np.int32), 64, measured,
                      doc=doc, ask=ask, doc_len=doc_len)


def test_the_sample_holds_the_four_requests_the_issue_names():
    from chipbench.families import sarvam_mla as family

    arrivals = [_ask(0, 0, 20000), _ask(1, 0, 5000), _ask(0, 1, 20000),
                _ask(2, 0, 27000), _ask(1, 1, 5000), _ask(0, 2, 20000),
                _ask(3, 0, 9000), _ask(3, 1, 9000), _ask(9, 0, 4100, False)]
    admitted = {0: (0, 0), 1: (0, 0), 2: (19968, 0), 3: (0, 0),
                4: (4992, 0), 5: (19968, 4), 6: (0, 9), 7: (8960, 9),
                8: (0, 0)}
    served = _Served(
        arrivals, {i: [1, 2] for i in range(9) if i != 3},
        {i: {"cached_len": c, "reclaimed_before": r}
         for i, (c, r) in admitted.items()})
    sample = family.sample_of(served, 7)
    # the longest FINISHED cold document over 16,384, a warm ask of it, the
    # warm ask with the most cached after a page was reclaimed, the shortest
    # cold; the unmeasured and the unfinished never
    assert sample == [0, 2, 5, 1]
    served.admitted[5]["reclaimed_before"] = 0
    assert family.sample_of(served, 7) == [0, 2, 7, 1]
    # a run with no long document still checks a warm ask
    short = _Served(arrivals[1:2] + arrivals[4:5], {0: [1], 1: [1]},
                    {0: {"cached_len": 0, "reclaimed_before": 0},
                     1: {"cached_len": 4992, "reclaimed_before": 0}})
    assert family.sample_of(short, 7) == [0, 1]


def test_each_degraded_reference_names_knobs_the_reference_has():
    import inspect

    from chipbench.families import sarvam_mla as family

    config = cells.resolve(BENCH, CELL).config
    knobs = set(inspect.signature(family.reference.forward).parameters)
    for name, make in {**family.DEGRADED,
                       **family.NOT_TOLD_APART_ON_THE_CHIP}.items():
        made = make(config, 20000)
        assert made and set(made) <= knobs, name
    assert family.DEGRADED["reference_7_experts"](config, 1) == {
        "experts_per_token": 7}
    assert family.DEGRADED["reference_read_a_page_short"](
        config, 20070) == {"drop_page": (155 * 128, 128)}
    assert set(family.NOT_TOLD_APART_ON_THE_CHIP) == {"reference_bf16"}
    assert family.reference_width(_traffic()) == 29440


# -- the driver, end to end ---------------------------------------------------

TINY = dict(
    family="sarvam_mla", vocab_size=256, max_position_embeddings=4096,
    num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, q_head_dim=32,
    v_head_dim=16, rope_theta=10000,
    rope_scaling=dict(type="deepseek_yarn", factor=40, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=64),
    intermediate_size=96, first_k_dense_replace=1, moe_intermediate_size=32,
    num_experts=4, router_width=8, held_experts_first=0,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=2.5,
    rms_norm_eps=1e-6,
    assumed=dict(compute_dtype="float32", param_dtype="float32",
                 initializer_range=0.02))
TINY_SESSIONS = dict(
    kind="serve_sessions_by_family", n_slots=4, max_len=256, page_size=8,
    n_pages=48, tail_len=16, prefill_buckets=[8, 16, 64, 128, 256],
    doc_len=dict(dist="log_uniform", min=24, max=150),
    question_len=dict(dist="log_uniform", min=3, max=14),
    output_len=dict(dist="log_uniform", min=4, max=12),
    asks=[3, 4, 5], ask_gap_mean_s=0.3,
    arrivals=dict(gaps="exponential_quantiles", rate_per_s=24.0),
    warm_seconds=0.5, tail_seconds=0.5, drain_seconds_max=60.0,
    trace_seconds=1.0, base_seed=54)


def test_the_driver_serves_sessions_at_a_tiny_size(capsys, monkeypatch):
    import jax

    from chipbench.families import sarvam_mla as family

    monkeypatch.setattr(family, "LONG", 100)
    # float32 on both sides, router logits of a 64-wide model
    monkeypatch.setattr(family, "NEAR_TIE", 1e-5)
    cell = cells.Cell("tiny", 1, "tiny", TINY, "tiny", TINY_SESSIONS, [], [])
    flag = "jax_persistent_cache_min_compile_time_secs"
    seen = []
    forward = family.reference.forward
    monkeypatch.setattr(family.reference, "forward", lambda *a, **k: (
        seen.append((getattr(jax.config, flag), a[1].shape[0],
                     k["logits_to"] - k["logits_from"])), forward(*a, **k))[1])
    before = getattr(jax.config, flag)
    result = driver.run(cell, 2 ** 31 + 54, 1.5, False, jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    assert getattr(jax.config, flag) == before != float("inf")
    # one width, and the head's rows are the longest output's
    assert {s for s in seen} == {(float("inf"), 256, 12)}
    assert result.attempted > 20 and result.failed == 0
    assert result.end_to_end["serve_ttft_p95_ms"] > 0
    assert set(result.end_to_end) == {"serve_ttft_p95_ms"}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    setup = next(l for l in lines if l["event"] == "setup")
    assert setup["prefill_buckets"] == [64, 128, 256, 8, 16]
    check = next(l for l in lines if l["event"] == "check")
    assert check["compiled_while_serving"] == 0
    kinds = [(c["cached_len"] > 0, c["reclaimed_before"] > 0)
             for c in check["checked"]]
    assert (False, False) in kinds or (False, True) in kinds     # a cold ask
    assert any(warm for warm, _ in kinds)                        # a warm one
    assert any(after for _, after in kinds)           # the pool was pressed
    assert check["pages_reclaimed"] > 0
    assert 30 < check["prefix_cached_tokens_pct"] < 90
    assert check["radix_hits"] > 0 and check["reference_s"] > 0
    # float32 on both sides here: every token off a near tie is the argmax
    assert check["argmax_matches"] + check["router_near_ties"] == \
        check["checked_tokens"] > 0
