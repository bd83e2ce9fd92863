"""What PR 49 brought to the benchmark: the ``mimo_v2`` family and its cell
resolve to files; the file keeps the catalog's numbers; the cost functions
of the kernels at unequal widths against hand counts, and through the
readers that were there (a share cannot pass 100% by construction); the
family's limits refuse a hand-made degraded record; the driver end to end at
a size only this test chooses."""

import dataclasses
import json

import numpy as np

from chipbench import cells, kernel_costs_gqa_uneven as costs, loadgen
from chipbench.families import mimo_v2 as family
from chipbench.program_trace import HostSpan
from chipbench.readers import (decode_kernel_roofline_by_stats,
                               prefill_kernel_roofline)
from chipbench.tests.test_exaone_moe_cell import _context

BENCH = cells.load_benchmark()
CELL = "mimo-v2.5.serve-code-agent"
NEW = {"gqa_uneven_read_roofline_pct", "gqa_uneven_prefill_roofline_pct",
       "decode_attn_proj_ms_step", "prefill_attn_proj_ms_p50",
       "kv_ring_rows_step"}


def test_the_cell_resolves_to_files():
    cell = cells.resolve(BENCH, CELL)
    assert cell.chips == 1 and cell.config["family"] == "mimo_v2"
    assert cell.traffic["kind"] == "serve_open_loop_by_family"
    assert cells.load_driver(cell.traffic["kind"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert {"serve_ttft_p95_ms", "setup_s"} <= e2e <= {
        "serve_ttft_p95_ms", "setup_s", "serve_tpot_p50_ms"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"prefill_window_attn_ms_p50", "prefill_full_attn_ms_p50",
                  "decode_window_attn_ms_step", "decode_full_attn_ms_step",
                  "kv_full_rows_step", "prefill_ms_p50", "decode_moe_ms_step",
                  "moe_experts_hit_step", "moe_spill_step"} <= names
    # K-EXAONE's shares count 128-wide heads of one number a layer
    assert not {"gqa_read_roofline_pct", "gqa_prefill_roofline_pct"} & names
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
        assert metric["moves"] in e2e, metric["name"]
        if metric["name"] in NEW:       # this cell's alone
            assert metric["workloads"] == [CELL]


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every top-level number of the source's config is the file's, but
    for the keys under ``reduced``, each with its published value beside;
    ``reduced`` is the entry's and names no width."""
    config = cells.resolve(BENCH, CELL).config
    declared = {c["name"]: c for c in BENCH["configs"]}["mimo-v2.5"]
    assert declared["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert declared["source"] == config["source"]
    published = {
        "attention_chunk_size": 128, "attention_value_scale": 0.707,
        "swa_num_key_value_heads": 8, "swa_num_attention_heads": 64,
        "swa_head_dim": 192, "swa_v_head_dim": 128, "head_dim": 192,
        "hidden_size": 4096, "intermediate_size": 16384,
        "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
        "moe_intermediate_size": 2048, "n_group": 1,
        "n_routed_experts": 256, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
        "rope_theta": 10000000, "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000, "topk_group": 1,
        "v_head_dim": 128, "vocab_size": 152576}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["add_swa_attention_sink_bias"] is True
    assert config["add_full_attention_sink_bias"] is False
    assert config["routed_scaling_factor"] is None
    assert config["n_shared_experts"] is None
    assert config["router_width"] == config["published"]["n_routed_experts"]
    assert config["n_routed_experts"] * 16 == config["router_width"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # layer 0 and one whole period in the published ratio, the first 7
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert config["num_hidden_layers"] == 7
    assert "sixteen-chip" in config["deployment"]
    assert "NOT SERVED" in config["deployment"]


def test_the_traffic_is_long_prompts_and_long_answers():
    cell = cells.resolve(BENCH, CELL)
    arrivals = loadgen.stream(cell.traffic, 2 ** 31 + 5, 51.0,
                              cell.config["vocab_size"])
    prompts = np.array([len(a.prompt) for a in arrivals if a.measured])
    outputs = np.array([a.output_len for a in arrivals if a.measured])
    assert prompts.min() >= 2048 and prompts.max() <= 22528
    assert outputs.min() >= 256 and outputs.max() <= 2048
    assert (prompts > 16384).any() and (prompts < 4096).any()
    assert max(a.prompt.max() for a in arrivals) < cell.config["vocab_size"]
    longest = max(len(a.prompt) + a.output_len for a in arrivals)
    assert longest <= cell.traffic["max_len"] == 24576


def test_read_bytes_against_a_hand_count():
    config = cells.resolve(BENCH, CELL).config
    # one slot 1,000 long: 1,001 rows in each of two full layers of 4 x
    # (192 + 128) x 2 B = 2,560 B, 128 in each of five rings of 5,120 B
    assert costs.gqa_uneven_read_bytes(2 * 1001, 5 * 128, config) == \
        2 * 1001 * 2560 + 5 * 128 * 5120
    assert costs.gqa_uneven_read_bytes(0, 0, config) == 0
    # the cache the program allocates holds exactly those bytes a row: a
    # layout that padded a K head to 256 would hold 3,072 and 6,144
    import jax

    from pytorch_distributed_tpu.serving import WindowedKVCache

    cache = jax.eval_shape(lambda: WindowedKVCache.create(
        family.model_config(config), n_slots=1, max_len=256))
    assert 2 * (cache.k_full.shape[3] + cache.v_full.shape[3]) == 2560
    assert 2 * (cache.k_ring.shape[3] + cache.v_ring.shape[3]) == 5120


def test_prefill_flops_against_a_hand_count():
    config = cells.resolve(BENCH, CELL).config
    flops = costs.gqa_uneven_prefill_flops
    # two full layers: 100 x 101 / 2 pairs each, 2 FLOPs x 64 x 320 columns
    assert flops(100, config) == 2 * 5050 * 2 * 64 * 320
    assert flops(100, dict(config, hybrid_layer_pattern=[1, 1])) == 0
    # 20.8 TFLOP at the longest prompt: 0.11 s at the chip's peak
    assert abs(flops(22528, config) - 20.79e12) < 0.01e12


def test_the_shares_read_through_the_readers_that_are_there():
    """``decode_kernel_roofline_by_stats`` and ``prefill_kernel_roofline``
    with this cell's metric files' arguments, on hand-made traces: bytes
    and FLOPs that must move, over the kernel's time, over the peak."""
    config = cells.resolve(BENCH, CELL).config
    _, args = cells.load_reader("gqa_uneven_read_roofline_pct")
    names = {"custom-call.2": "jit(decode_fn)/ExaoneMoE/layer_5_attn/attn/"
             "full/jit(_kernel_read)/gqa_attention_read/pallas_call"}
    modules = [("jit_decode_fn(7)", 0.0, 1.0), ("jit_decode_fn(7)", 1.0, 2.0)]
    ops = [("%custom-call.2 = bf16[40,64,128] custom-call(...)", 0.0, 0.002),
           ("%custom-call.2 = bf16[40,64,128] custom-call(...)", 1.0, 1.002)]
    stats = [{"kv_full_rows": 500_000, "kv_ring_rows": 19_840},
             {"kv_full_rows": 500_062, "kv_ring_rows": 19_840}]
    context = _context(modules, ops, [
        HostSpan("engine.decode", i + 0.0, i + 0.9, s)
        for i, s in enumerate(stats)])
    context["decode_op_names"] = names
    context["counters"]["config"] = config
    share = decode_kernel_roofline_by_stats.read(context, **args)
    moved = 1_000_062 * 2560 + 39_680 * 5120
    assert abs(share - 100 * moved / 0.004 / 819e9) < 1e-6
    assert 0 < share < 100
    # a program that lacks the counts (the parent): nothing, and no error
    bare = dict(context, program_spans=[
        dataclasses.replace(s, stats={}) for s in context["program_spans"]])
    assert decode_kernel_roofline_by_stats.read(bare, **args) is None
    assert decode_kernel_roofline_by_stats.read({"trace": None}, **args) \
        is None

    _, args = cells.load_reader("gqa_uneven_prefill_roofline_pct")
    names = {2048: {"custom-call.1": "jit(prefill_fn)/ExaoneMoE/layer_0_attn/"
                    "attn/full/jit(_kernel_prefill)/gqa_attention_prefill/"
                    "pallas_call"}}
    modules = [("jit_prefill_fn(3)", 1.0, 2.0)]
    ops = [("%custom-call.1 = bf16[8] custom-call(...)", 1.0, 1.01)]
    spans = [HostSpan("engine.prefill", 0.9, 2.1, {"bucket": 2048,
                                                   "n_real": 2000})]
    context = _context(modules, ops, spans, names=names)
    context["counters"]["config"] = config
    share = prefill_kernel_roofline.read(context, **args)
    spent = 2 * (2000 * 2001 // 2) * 2 * 64 * 320
    assert abs(share - 100 * spent / 0.01 / 197e12) < 1e-9
    assert 0 < share < 100
    assert prefill_kernel_roofline.read(
        _context(modules, ops, spans, names={}), **args) is None


def test_a_share_counts_no_more_than_the_kernels_move():
    """By construction: the bytes counted a step are never more than the
    rows times the stored row, and the FLOPs never more than the products
    the kernel's blocks make over the causal half."""
    config = cells.resolve(BENCH, CELL).config
    for n in (1, 127, 128, 129, 5000, 24575):
        full, ring = 2 * (n + 1), 5 * min(n + 1, 128)
        stored = full * (768 + 512) * 2 + ring * (1536 + 1024) * 2
        assert costs.gqa_uneven_read_bytes(full, ring, config) == stored
    for tokens, bucket in ((2048, 2048), (3000, 4096), (22528, 24576)):
        # every (64-query, 1,024-key) block pair of the bucket's causal
        # half, whole: what the kernel multiplies at the least
        pairs = sum(64 * 1024 * (-(-(q + 64) // 1024))
                    for q in range(0, bucket, 64))
        made = 2 * pairs * 2 * 64 * (192 + 128)
        assert costs.gqa_uneven_prefill_flops(tokens, config) <= made


# -- the limits ---------------------------------------------------------------

def _record(n, exact, over, ties=0.3, seed=0):
    """A hand-made run: ``n`` checked positions, ``ties`` of them router
    near ties; of the others ``exact`` are the argmax and ``over`` lie past
    the tolerance, the rest just under it."""
    rng = np.random.default_rng(seed)
    margins = np.where(rng.random(n) < ties, family.NEAR_TIE / 2,
                       family.NEAR_TIE * 10)
    regrets = np.zeros(n)
    rest = np.flatnonzero(margins >= family.NEAR_TIE)
    n_over = int(round(over * len(rest)))
    n_off = len(rest) - int(round(exact * len(rest)))
    regrets[rest[:n_over]] = 4 * family.TOKEN_TOLERANCE
    regrets[rest[n_over:n_off]] = family.TOKEN_TOLERANCE / 2
    return regrets, margins


def test_the_limits_pass_the_program_and_refuse_a_degraded_record():
    """The program's reading on the chip passes; a record with the exact
    share of the nearest degraded reference, or with its share over the
    tolerance, is refused by that limit alone; a run that checked nothing
    or almost only near ties is refused."""
    record, faults = family.faults_of(*_record(
        3000, family.MIN_EXACT_SHARE + 0.04, family.MAX_OVER_TOLERANCE / 3))
    assert not faults and record["checked_tokens"] == 3000
    assert record["router_near_ties"] > 0
    _, faults = family.faults_of(*_record(
        3000, family.MIN_EXACT_SHARE - 0.04, 0.0))
    assert len(faults) == 1 and "argmax" in faults[0]
    _, faults = family.faults_of(*_record(
        3000, family.MIN_EXACT_SHARE + 0.04,
        family.MAX_OVER_TOLERANCE * 1.5))
    assert len(faults) == 1 and "logit range" in faults[0]
    _, faults = family.faults_of(*_record(3000, 1.0, 0.0, ties=0.9))
    assert len(faults) == 1 and "near ties" in faults[0]
    _, faults = family.faults_of(np.zeros(0), np.zeros(0))
    assert faults == ["no finished request to check"]
    assert set(family.DEGRADED) == {
        "reference_8bit", "reference_no_sink",
        "reference_all_columns_rotated", "reference_bases_swapped",
        "reference_value_scale_1", "reference_window_heads_as_full"}
    assert set(family.NOT_TOLD_APART_ON_THE_CHIP) == {
        "reference_7_experts", "reference_window_127"}


@dataclasses.dataclass
class _Served:
    arrivals: list
    tokens: dict


def test_the_sample_holds_the_longest_and_the_shortest_prompt():
    lengths = [3000, 2100, 9000, 22000, 7000, 5000, 12000, 4000]
    arrivals = [loadgen.Arrival(float(i), np.zeros(n, np.int32), 8, i != 3)
                for i, n in enumerate(lengths)]
    served = _Served(arrivals, {i: [0] * 8 for i in range(len(lengths))
                                if i != 5})
    sample = family.sample_of(served, seed=3)
    assert len(sample) == family.CHECKED_REQUESTS == len(set(sample))
    # the longest MEASURED and FINISHED over 8,192, the shortest under 4,096
    assert 6 in sample and 1 in sample and 3 not in sample and 5 not in sample
    assert family.sample_of(served, seed=3) == sample
    assert family.sample_of(_Served(arrivals, {}), seed=3) == []


# -- the driver end to end, at a size only this test chooses ------------------

TINY = {
    "family": "mimo_v2", "vocab_size": 256, "max_position_embeddings": 4096,
    "num_hidden_layers": 7, "hidden_size": 64, "num_attention_heads": 32,
    "swa_num_attention_heads": 32, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 192, "swa_head_dim": 192,
    "v_head_dim": 128, "swa_v_head_dim": 128, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "router_width": 16,
    "held_experts_first": 4, "num_experts_per_tok": 4,
    "n_shared_experts": None, "routed_scaling_factor": None,
    "sliding_window": 8, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "layernorm_epsilon": 1e-5,
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
    import jax

    from chipbench.drivers import serve_open_loop_by_family

    # the prompts here are tens of tokens long, not thousands
    monkeypatch.setattr(family, "LONG", 40)
    monkeypatch.setattr(family, "SHORT", 10)
    monkeypatch.setattr(family, "WIDTHS", (32,))
    # float32 on both sides: a tie is near where logits differ by rounding
    monkeypatch.setattr(family, "NEAR_TIE", 1e-5)
    cell = cells.Cell("tiny", 1, "tiny", TINY, "tiny", TINY_SERVE, [], [])
    flag = "jax_persistent_cache_min_compile_time_secs"
    seen = []
    forward = family.reference.forward
    monkeypatch.setattr(family.reference, "forward", lambda *a, **k: (
        seen.append((getattr(jax.config, flag), a[1].shape[0],
                     k["logits_to"] - k["logits_from"])), forward(*a, **k))[1])
    before = getattr(jax.config, flag)
    result = serve_open_loop_by_family.run(cell, 2 ** 31 + 11, 1.0, False,
                                           jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    # nothing the reference compiles is written to the compile cache
    assert getattr(jax.config, flag) == before != float("inf")
    assert {s[0] for s in seen} == {float("inf")}
    # two widths, and the head's rows are the longest output's at both
    assert {s[2] for s in seen} == {12} and {s[1] for s in seen} == {32, 128}
    assert result.attempted == 20 and result.failed == 0
    assert result.end_to_end["serve_ttft_p95_ms"] > 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    check = next(l for l in lines if l["event"] == "check")
    lens = check["checked_prompt_lens"]
    assert len(lens) == 4 and max(lens) > 40 and min(lens) < 10
    assert check["argmax_matches"] + check["router_near_ties"] == \
        check["checked_tokens"] > 0
    assert check["compiled_while_serving"] == 0 and check["reference_s"] > 0
    for name, knobs in {**family.DEGRADED,
                        **family.NOT_TOLD_APART_ON_THE_CHIP}.items():
        assert set(knobs(TINY)) <= {
            "round_to", "experts_per_token", "window", "no_sink",
            "rotate_all", "swap_bases", "value_scale",
            "window_heads_as_full"}, name
