"""What PR 40 brought to the benchmark: the ``exaone_moe`` family and its
cell resolve to files; the cost function against a hand count; the new
readers (``prefill_time_by_scope``, ``decode_kernel_roofline_by_stats``) on
hand-made traces, and None, not an error, on a program that lacks the span,
the statistic or the registry entry; the sample a run checks."""

import dataclasses

import numpy as np

from chipbench import cells, kernel_costs_gqa, loadgen, prefill_trace
from chipbench.program_trace import HostSpan
from chipbench.readers import (decode_kernel_roofline_by_stats,
                               prefill_kernel_roofline,
                               prefill_time_by_scope)
from chipbench.trace_reduce import DeviceTrace, Reduced

BENCH = cells.load_benchmark()
CELL = "k-exaone-236b-a23b.serve-mixed-len"


def test_the_cell_resolves_to_files():
    cell = cells.resolve(BENCH, CELL)
    assert cell.chips == 1 and cell.config["family"] == "exaone_moe"
    assert cell.traffic["kind"] == "serve_open_loop_by_family"
    assert cells.load_driver(cell.traffic["kind"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"serve_ttft_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"prefill_window_attn_ms_p50", "prefill_full_attn_ms_p50",
            "decode_window_attn_ms_step", "decode_full_attn_ms_step",
            "kv_full_rows_step", "gqa_read_roofline_pct",
            "gqa_prefill_roofline_pct", "prefill_ms_p50",
            "decode_moe_ms_step", "moe_experts_hit_step"} <= names
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
        assert metric["moves"] in e2e, metric["name"]


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every top-level number of the source's config is the file's, but
    for the keys under ``reduced``, each with its published value beside."""
    config = cells.resolve(BENCH, CELL).config
    declared = {c["name"]: c for c in BENCH["configs"]}["k-exaone-236b-a23b"]
    assert declared["reduced"] == config["reduced"]
    assert declared["source"] == config["source"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
        "intermediate_size": 18432, "max_position_embeddings": 262144,
        "moe_intermediate_size": 2048, "n_group": 1,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "sliding_window": 128,
        "topk_group": 1, "vocab_size": 153600}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["router_width"] == config["published"]["num_experts"]
    n = config["num_hidden_layers"]
    assert n == len(config["layer_types"]) == len(config["mlp_layer_types"]) \
        == len(config["sliding_windows"]) == 5
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}


def test_the_traffic_is_short_and_long_in_one_queue():
    cell = cells.resolve(BENCH, CELL)
    arrivals = loadgen.stream(cell.traffic, 2 ** 31 + 5, 51.0,
                              cell.config["vocab_size"])
    prompts = np.array([len(a.prompt) for a in arrivals if a.measured])
    assert prompts.min() >= 256 and prompts.max() <= 28672
    assert (prompts < 1024).any() and (prompts > 8192).any()
    assert max(a.prompt.max() for a in arrivals) < cell.config["vocab_size"]
    longest = max(len(a.prompt) + a.output_len for a in arrivals)
    assert longest < cell.traffic["max_len"]


def test_read_bytes_against_a_hand_count():
    config = {"num_key_value_heads": 8, "head_dim": 128}
    # one slot 1,000 long: 1,001 rows of one full layer, 128 of four rings,
    # K and V of 8 x 128 bfloat16 each: 4,096 B a row
    assert kernel_costs_gqa.gqa_read_bytes(1001, 4 * 128, config) == \
        (1001 + 512) * 4096
    assert kernel_costs_gqa.gqa_read_bytes(0, 0, config) == 0


def test_prefill_flops_against_a_hand_count():
    config = {"num_attention_heads": 64, "head_dim": 128,
              "sliding_window": 128,
              "layer_types": ["sliding_attention"] * 3 + [
                  "full_attention", "sliding_attention"]}
    flops = kernel_costs_gqa.gqa_prefill_flops
    # the one full layer: 100 x 101 / 2 pairs, 4 FLOPs x 8,192 columns each
    assert flops(100, config) == 5050 * 4 * 8192
    assert flops(1000, dict(config, layer_types=["full_attention"] * 2)) == \
        2 * 500500 * 4 * 8192
    assert flops(1000, dict(config, layer_types=["sliding_attention"])) == 0
    # the issue's arithmetic: 13.5 TFLOP in the full layer at 28,672 tokens
    assert abs(flops(28672, config) - 13.47e12) < 0.01e12


# -- the readers on hand-made traces -----------------------------------------

NAMES = {
    256: {"fusion.1": "jit(prefill_fn)/ExaoneMoE/layer_0_attn/attn/window/"
                      "while/body/dot_general",
          "fusion.2": "jit(prefill_fn)/ExaoneMoE/layer_3_attn/attn/full/"
                      "while/body/while/body/dot_general",
          "fusion.3": "jit(prefill_fn)/ExaoneMoE/layer_1_moe/moe/experts/x"},
    # another bucket's program names its instructions its own way
    1024: {"fusion.1": "jit(prefill_fn)/ExaoneMoE/layer_3_attn/attn/full/"
                       "while/body/while/body/dot_general",
           "fusion.7": "jit(prefill_fn)/ExaoneMoE/layer_2_attn/attn/window/"
                       "while/body/dot_general"},
}


def _context(modules, ops, spans, names=NAMES):
    reduced = Reduced(
        devices=[DeviceTrace(ordinal=0, ops=ops, modules=modules,
                             async_ops=[])],
        spans=[], window=(0.0, 10.0))
    return {"trace": reduced, "program_spans": list(spans),
            "prefill_op_names": (lambda bucket: names.get(bucket, {})),
            "counters": {"device_kind": "TPU v5 lite",
                         "config": {"num_key_value_heads": 8,
                                    "head_dim": 128}}}


def _prefill_case():
    modules = [("jit_prefill_fn(3)", 1.0, 2.0), ("jit_decode_fn(7)", 2.5, 2.6),
               ("jit_prefill_fn(4)", 3.0, 5.0), ("jit_prefill_fn(3)", 6.0, 7.0)]
    ops = [("%fusion.1 = bf16[256] fusion(...)", 1.1, 1.2),      # window
           ("%fusion.2 = bf16[256] fusion(...)", 1.2, 1.5),      # full
           ("%fusion.3 = bf16[256] fusion(...)", 1.5, 1.9),      # experts
           ("%fusion.1 = bf16[32] fusion(...)", 2.5, 2.55),      # decode's
           ("%fusion.1 = bf16[1024] fusion(...)", 3.0, 4.0),     # 1024: full
           ("%fusion.7 = bf16[1024] fusion(...)", 4.0, 4.2),     # 1024: window
           # a loop's event spans its body's events and is not counted
           ("%fusion.2 = (s32[], bf16[256]) while(%tuple.1), body=%b", 6.0, 6.6),
           ("%fusion.1 = bf16[256] fusion(...)", 6.1, 6.4),
           ("%fusion.2 = bf16[256] fusion(...)", 6.4, 6.5)]
    spans = [HostSpan("engine.prefill", 0.9, 2.1, {"bucket": 256}),
             HostSpan("engine.prefill", 2.9, 5.1, {"bucket": 1024}),
             HostSpan("engine.prefill", 5.9, 7.1, {"bucket": 256})]
    return modules, ops, spans


def test_a_prefill_operation_is_named_by_its_own_buckets_program():
    context = _context(*_prefill_case())
    runs = prefill_trace.prefill_runs(context)
    assert [bucket for bucket, _ in runs] == [256, 1024, 256]
    assert [len(ops) for _, ops in runs] == [3, 2, 2]
    ms = prefill_time_by_scope.read
    # window: 100, 200, 300 ms a run; full: 300, 1000, 100 ms
    assert abs(ms(context, include="attn/window") - 200.0) < 1e-6
    assert abs(ms(context, include="attn/full") - 300.0) < 1e-6
    assert abs(ms(context, include="attn/full", percentile=100) - 1e3) < 1e-6
    assert ms(context, include="/nothing/") is None


def test_a_program_without_span_bucket_or_registry_reads_nothing():
    modules, ops, spans = _prefill_case()
    ms = prefill_time_by_scope.read
    # no text of the prefill programs (the parent of the PR that adds them)
    assert ms(_context(modules, ops, spans, names={}),
              include="attn/window") is None
    # no pdt.engine.prefill span, or one without its bucket
    assert ms(_context(modules, ops, []), include="attn/window") is None
    bare = [dataclasses.replace(s, stats={}) for s in spans]
    assert ms(_context(modules, ops, bare), include="attn/window") is None
    # no trace at all
    assert ms({"trace": None}, include="attn/window") is None


def test_prefill_roofline_is_the_flops_of_the_real_tokens_over_time():
    names = {256: {"custom-call.1": "jit(prefill_fn)/ExaoneMoE/layer_3_attn/"
                   "attn/full/jit(_kernel_prefill)/gqa_attention_prefill/"
                   "pallas_call", "fusion.3": "jit(prefill_fn)/ExaoneMoE/x"}}
    modules = [("jit_prefill_fn(3)", 1.0, 2.0), ("jit_prefill_fn(3)", 3.0, 4.0)]
    ops = [("%custom-call.1 = bf16[8] custom-call(...)", 1.0, 1.001),
           ("%fusion.3 = bf16[8] fusion(...)", 1.1, 1.9),
           ("%custom-call.1 = bf16[8] custom-call(...)", 3.0, 3.001)]
    spans = [HostSpan("engine.prefill", 0.9, 2.1, {"bucket": 256,
                                                   "n_real": 200}),
             HostSpan("engine.prefill", 2.9, 4.1, {"bucket": 256,
                                                   "n_real": 256})]
    context = _context(modules, ops, spans, names=names)
    config = dict(context["counters"]["config"], num_attention_heads=64,
                  sliding_window=128, layer_types=["full_attention"])
    context["counters"]["config"] = config
    args = dict(kernel="gqa_attention_prefill", costs="kernel_costs_gqa",
                flops="gqa_prefill_flops")
    share = prefill_kernel_roofline.read(context, **args)
    spent = (200 * 201 // 2 + 256 * 257 // 2) * 4 * 8192
    assert abs(share - 100 * spent / 0.002 / 197e12) < 1e-9
    assert 0 < share < 100
    bare = [dataclasses.replace(s, stats={"bucket": 256}) for s in spans]
    assert prefill_kernel_roofline.read(
        dict(_context(modules, ops, bare, names=names)), **args) is None
    assert prefill_kernel_roofline.read(
        _context(modules, ops, spans, names={}), **args) is None
    assert prefill_kernel_roofline.read({"trace": None}, **args) is None


def _decode_case(stats):
    names = {"custom-call.2": "jit(decode_fn)/ExaoneMoE/layer_3_attn/attn/"
             "full/jit(_kernel_read)/gqa_attention_read/pallas_call"}
    modules = [("jit_decode_fn(7)", 0.0, 1.0), ("jit_decode_fn(7)", 1.0, 2.0)]
    ops = [("%custom-call.2 = bf16[32,128,128] custom-call(...)", 0.0, 0.002),
           ("%custom-call.2 = bf16[32,128,128] custom-call(...)", 1.0, 1.002)]
    context = _context(modules, ops, [
        HostSpan("engine.decode", i + 0.0, i + 0.9, s)
        for i, s in enumerate(stats)])
    context["decode_op_names"] = names
    return context


ARGS = dict(kernel="gqa_attention_read", costs="kernel_costs_gqa",
            bytes="gqa_read_bytes", stats=["kv_full_rows", "kv_ring_rows"])


def test_roofline_share_is_the_rows_held_over_time_over_peak():
    context = _decode_case([
        {"kv_full_rows": 60_000, "kv_ring_rows": 4_000, "experts_hit": 50},
        {"kv_full_rows": 61_000, "kv_ring_rows": 4_096, "experts_hit": 48}])
    share = decode_kernel_roofline_by_stats.read(context, **ARGS)
    moved = (60_000 + 61_000 + 4_000 + 4_096) * 4096
    assert abs(share - 100 * moved / 0.004 / 819e9) < 1e-6
    assert 0 < share < 100


def test_roofline_reads_nothing_without_the_steps_counts():
    read = decode_kernel_roofline_by_stats.read
    assert read(_decode_case([{"experts_hit": 5}, {}]), **ARGS) is None
    context = _decode_case([{"kv_full_rows": 1, "kv_ring_rows": 1}])
    context["decode_op_names"] = {"x": "y"}       # the kernel never ran
    assert read(context, **ARGS) is None
    assert read({"trace": None}, **ARGS) is None


# -- the sample a run checks --------------------------------------------------

@dataclasses.dataclass
class _Served:
    arrivals: list
    tokens: dict


def test_the_sample_holds_a_long_and_a_short_request():
    from chipbench.families import exaone_moe as family

    lengths = [300, 2000, 9000, 28000, 700, 5000, 12000, 400]
    arrivals = [loadgen.Arrival(float(i), np.zeros(n, np.int32), 8, i != 3)
                for i, n in enumerate(lengths)]
    served = _Served(arrivals, {i: [0] * 8 for i in range(len(lengths))
                                if i != 5})
    sample = family.sample_of(served, seed=3)
    assert len(sample) == family.CHECKED_REQUESTS == len(set(sample))
    # the longest MEASURED and FINISHED over 8,192, the shortest under 1,024
    assert 6 in sample and 0 in sample and 3 not in sample and 5 not in sample
    assert family.sample_of(served, seed=3) == sample
    assert family.sample_of(_Served(arrivals, {}), seed=3) == []
    only_mid = _Served(arrivals, {1: [0] * 8})
    assert family.sample_of(only_mid, seed=0) == [1]


# -- the driver end to end, at a size only this test chooses ------------------

TINY = {
    "family": "exaone_moe", "vocab_size": 256,
    "max_position_embeddings": 4096, "num_hidden_layers": 5,
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 4, "router_width": 16, "held_experts_first": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "sliding_window": 8,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                "sliding_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "assumed": {"compute_dtype": "float32", "param_dtype": "float32",
                "initializer_range": 0.02},
}
TINY_SERVE = {
    "kind": "serve_open_loop_by_family", "n_slots": 4, "max_len": 128,
    "cache_kind": "slotted",
    "prompt_len": {"dist": "log_uniform", "min": 4, "max": 80},
    "output_len": {"dist": "log_uniform", "min": 4, "max": 12},
    "arrivals": {"gaps": "exponential_quantiles", "rate_per_s": 20.0},
    "warm_seconds": 0.3, "tail_seconds": 1.0, "drain_seconds_max": 30.0,
    "trace_seconds": 1.0, "base_seed": 1,
}


def test_the_driver_serves_the_family_at_a_tiny_size(capsys, monkeypatch):
    import json

    import jax

    from chipbench.drivers import serve_open_loop_by_family
    from chipbench.families import exaone_moe as family

    # the prompts here are tens of tokens long, not thousands
    monkeypatch.setattr(family, "LONG", 40)
    monkeypatch.setattr(family, "SHORT", 10)
    monkeypatch.setattr(family, "WIDTHS", (32,))
    # float32 on both sides, and router logits of a 64-wide model: a tie is
    # near where the logits differ by rounding, not by 0.05
    monkeypatch.setattr(family, "NEAR_TIE", 1e-5)
    cell = cells.Cell("tiny", 1, "tiny", TINY, "tiny", TINY_SERVE, [], [])
    # the harness's threshold for writing a program to the compile cache,
    # which the reference raises while it runs and puts back
    flag = "jax_persistent_cache_min_compile_time_secs"
    monkeypatch.setattr(family, "CACHED_FROM_S", 7.0)
    seen = []
    forward = family.reference.forward
    monkeypatch.setattr(family.reference, "forward", lambda *a, **k: (
        seen.append((getattr(jax.config, flag), a[1].shape[0],
                     k["logits_to"] - k["logits_from"])), forward(*a, **k))[1])
    before = getattr(jax.config, flag)
    result = serve_open_loop_by_family.run(cell, 2 ** 31 + 11, 1.0, False,
                                           jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    assert getattr(jax.config, flag) == before != 7.0
    # two widths, and the head's rows are the longest output's at both
    assert {s[0] for s in seen} == {7.0} and {s[2] for s in seen} == {12}
    assert {s[1] for s in seen} == {32, 128}
    assert result.attempted == 20 and result.failed == 0
    assert result.end_to_end["serve_ttft_p95_ms"] > 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    check = next(l for l in lines if l["event"] == "check")
    lens = check["checked_prompt_lens"]
    assert len(lens) == 4 and max(lens) > 40 and min(lens) < 10
    # float32 on both sides here: every token off a near tie is the argmax
    assert check["argmax_matches"] + check["router_near_ties"] == \
        check["checked_tokens"] > 0
    assert check["compiled_while_serving"] == 0
    # each degraded reference is another function at this size too
    for name, knobs in family.DEGRADED.items():
        assert set(knobs(TINY)) <= {"round_to", "experts_per_token", "window",
                                    "window_layers_full", "rotate_full"}, name
