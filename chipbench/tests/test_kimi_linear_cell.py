"""What PR 45 brought to the benchmark: the ``kimi_linear`` family and its
cell resolve to files, every metric of the cell names a reader, the cost
functions of ``kernel_costs_kda.py`` against hand counts, the accepted
readers pointed at the new scopes and counts on hand-made traces, the sample
a run checks, and the driver end to end at a size only this test chooses."""

import dataclasses
import json

import numpy as np
import pytest

from chipbench import cells, kernel_costs_kda, loadgen
from chipbench.program_trace import HostSpan
from chipbench.readers import (decode_kernel_roofline_by_stats,
                               decode_time_by_scope, prefill_kernel_roofline,
                               prefill_time_by_scope, program_span_stat)
from chipbench.trace_reduce import DeviceTrace, Reduced

BENCH = cells.load_benchmark()
CELL = "kimi-linear-48b-a3b.serve-long-answer"
NEW = {"decode_kda_ms_step", "prefill_kda_ms_p50", "kda_state_bytes_step",
       "kda_decode_roofline_pct", "kda_prefill_roofline_pct",
       "latent_rows_read_roofline_pct"}


def test_the_cell_resolves_to_files():
    cell = cells.resolve(BENCH, CELL)
    assert cell.chips == 1 and cell.config["family"] == "kimi_linear"
    assert cell.traffic["kind"] == "serve_open_loop_by_family"
    assert cells.load_driver(cell.traffic["kind"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert {"serve_ttft_p95_ms", "setup_s"} <= e2e <= {
        "serve_ttft_p95_ms", "serve_tpot_p50_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"prefill_ms_p50", "decode_moe_ms_step",
                  "decode_latent_attn_ms_step", "moe_experts_hit_step",
                  "prefill_moe_ms_p50", "moe_spill_step",
                  "prefill_real_tokens_pct"} <= names
    # kernel_costs.latent_read_bytes counts EVERY layer of the stack as a
    # reader of rows; two of these eight are: the accepted share would read
    # four times too high here, so the cell reports its own
    assert "latent_read_roofline_pct" not in names
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
        assert metric["moves"] in e2e, metric["name"]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_metric_is_the_new_cells_alone(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_ttft_p95_ms"
    assert metric.endswith("_roofline_pct") == (entry["unit"] == "%")


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every top-level number of the source's config is the file's, but
    for the keys under ``reduced``, each with its published value beside;
    no width of the nested group is touched."""
    config = cells.resolve(BENCH, CELL).config
    declared = {c["name"]: c for c in BENCH["configs"]}["kimi-linear-48b-a3b"]
    assert declared["reduced"] == config["reduced"]
    assert declared["source"] == config["source"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_max_length": 1048576, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert config["mla_use_nope"] is True
    assert config["router_width"] == config["published"]["num_experts"]
    linear = config["linear_attn_config"]
    assert (linear["head_dim"], linear["num_heads"],
            linear["short_conv_kernel_size"]) == (128, 32, 4)
    # the first eight layers' entries of the published lists, 3 : 1
    was = config["published"]["linear_attn_config"]
    n = config["num_hidden_layers"]
    assert linear["kda_layers"] == [i for i in was["kda_layers"] if i <= n]
    assert linear["full_attn_layers"] == [
        i for i in was["full_attn_layers"] if i <= n] == [4, 8]
    assert "four pipeline stages of four chips" in config["deployment"]
    # the floors of a configuration that is still the model
    assert n - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]


def test_the_traffic_is_prompts_in_and_long_answers_out():
    cell = cells.resolve(BENCH, CELL)
    arrivals = loadgen.stream(cell.traffic, 2 ** 31 + 5, 51.0,
                              cell.config["vocab_size"])
    measured = [a for a in arrivals if a.measured]
    prompts = np.array([len(a.prompt) for a in measured])
    outputs = np.array([a.output_len for a in measured])
    assert prompts.min() >= 256 and prompts.max() <= 4096
    assert outputs.min() >= 256 and outputs.max() <= 2048
    assert (prompts < 512).any() and (prompts > 2048).any()
    assert max(a.prompt.max() for a in arrivals) < cell.config["vocab_size"]
    assert max(len(a.prompt) + a.output_len
               for a in arrivals) < cell.traffic["max_len"]
    # every prompt fills whole chunks of a bucket the engine compiles
    assert cell.traffic["max_len"] % cell.config["assumed"]["kda_chunk"] == 0


# -- the cost functions against hand counts -----------------------------------

SMALL = {"linear_attn_config": {"kda_layers": [1, 2, 4], "num_heads": 2,
                                "head_dim": 8, "short_conv_kernel_size": 4},
         "assumed": {"kda_chunk": 4}, "kv_lora_rank": 32,
         "qk_rope_head_dim": 8}


def test_decode_bytes_against_a_hand_count():
    # a layer a slot: a float32 state of 2 x 8 x 8 read and written (2 x 512
    # B), a tail of 3 rows of 3 x 16 bfloat16 channels read (288 B)
    assert kernel_costs_kda.kda_slot_bytes(SMALL) == 3 * (1024 + 288)
    assert kernel_costs_kda.kda_decode_bytes(5, SMALL) == 5 * 3 * 1312
    assert kernel_costs_kda.kda_decode_bytes(0, SMALL) == 0
    # the issue's arithmetic: 12.6 MB of state a slot, 3.2 GB moved a step
    # at 128 live slots (read and written)
    config = cells.resolve(BENCH, CELL).config
    assert kernel_costs_kda.kda_slot_bytes(config) == 6 * (
        2 * 32 * 128 * 128 * 4 + 3 * 12288 * 2) == 25_608_192
    assert abs(kernel_costs_kda.kda_decode_bytes(128, config) - 3.28e9) < 1e7


def test_prefill_flops_against_a_hand_count():
    # a token a head, C = 4, d = 8: A_kk and A_qk 2 x 4 x 8 = 64, the solve
    # applied 64, three products with the 8 x 8 state 384, A_qk U 32
    assert kernel_costs_kda.kda_prefill_flops(1, SMALL) == 3 * 2 * 544
    assert kernel_costs_kda.kda_prefill_flops(100, SMALL) == 100 * 3264
    wider = dict(SMALL, assumed={"kda_chunk": 8})
    assert kernel_costs_kda.kda_prefill_flops(10, wider) == \
        10 * 3 * 2 * (128 + 128 + 384 + 64)
    config = cells.resolve(BENCH, CELL).config
    # C = 64, d = 128: 139,264 a token a head (the triangular halves alone),
    # x 32 heads x 6 layers = 26.7 MFLOP a token
    assert kernel_costs_kda.kda_prefill_flops(4096, config) == \
        4096 * 6 * 32 * 139_264


def test_latent_rows_bytes_against_a_hand_count():
    # 40-wide rows of bfloat16; the rows arrive summed over the MLA layers
    assert kernel_costs_kda.latent_rows_bytes(7, SMALL) == 7 * 80
    config = cells.resolve(BENCH, CELL).config
    assert kernel_costs_kda.latent_rows_bytes(1000, config) == 1000 * 1152


# -- the accepted readers, pointed at the new scopes and counts ---------------

def _context(modules, ops, spans, config=SMALL):
    reduced = Reduced(
        devices=[DeviceTrace(ordinal=0, ops=ops, modules=modules,
                             async_ops=[])],
        spans=[], window=(0.0, 10.0))
    return {"trace": reduced, "program_spans": list(spans),
            "counters": {"device_kind": "TPU v5 lite", "config": config}}


def _args(metric):
    return cells.load_reader(metric)[1]


def _decode_case(stats):
    names = {
        "fusion.1": "jit(decode_fn)/KimiLinear/kda/layer_0_attn/"
                    "pdt.kda.decode/mul",
        "fusion.2": "jit(decode_fn)/KimiLinear/layer_1_moe/moe/experts/x",
        "custom-call.3": "jit(decode_fn)/KimiLinear/mla/layer_3_attn/"
                         "jit(_kernel_read)/latent_attention_read/pallas_call"}
    modules = [("jit_decode_fn(7)", 0.0, 1.0), ("jit_decode_fn(7)", 1.0, 2.0)]
    ops = [("%fusion.1 = f32[4,2,8,8] fusion(...)", 0.0, 0.003),
           ("%fusion.2 = bf16[4,64] fusion(...)", 0.1, 0.2),
           ("%custom-call.3 = bf16[4,2,32] custom-call(...)", 0.3, 0.301),
           ("%fusion.1 = f32[4,2,8,8] fusion(...)", 1.0, 1.001),
           ("%custom-call.3 = bf16[4,2,32] custom-call(...)", 1.3, 1.301)]
    context = _context(modules, ops, [
        HostSpan("engine.decode", i + 0.0, i + 0.9, s)
        for i, s in enumerate(stats)])
    context["decode_op_names"] = names
    return context


STEPS = [{"live_slots": 3, "state_kib": 3 * 3, "latent_rows": 50},
         {"live_slots": 4, "state_kib": 4 * 3, "latent_rows": 58}]


def test_the_decode_metrics_read_the_scope_and_the_counts():
    context = _decode_case(STEPS)
    ms = decode_time_by_scope.read(context, **_args("decode_kda_ms_step"))
    assert abs(ms - 2.0) < 1e-9                 # 3 ms and 1 ms over two steps
    moved = program_span_stat.read(context, **_args("kda_state_bytes_step"))
    assert moved == (9 + 12) * 1024 / 2
    share = decode_kernel_roofline_by_stats.read(
        context, **_args("kda_decode_roofline_pct"))
    assert abs(share - 100 * 7 * 3 * 1312 / 0.004 / 819e9) < 1e-12
    rows = decode_kernel_roofline_by_stats.read(
        context, **_args("latent_rows_read_roofline_pct"))
    assert abs(rows - 100 * 108 * 80 / 0.002 / 819e9) < 1e-12


def test_the_decode_metrics_read_nothing_from_a_program_without_them():
    """The parent: no KDA scope in its decode program, no counts on its
    span. None, never an error."""
    for metric, reader in (
            ("decode_kda_ms_step", decode_time_by_scope),
            ("kda_state_bytes_step", program_span_stat),
            ("kda_decode_roofline_pct", decode_kernel_roofline_by_stats),
            ("latent_rows_read_roofline_pct",
             decode_kernel_roofline_by_stats)):
        bare = _decode_case([{"experts_hit": 5}, {}])
        bare["decode_op_names"] = {"fusion.1": "jit(decode_fn)/GPT2/h_0/x"}
        assert reader.read(bare, **_args(metric)) is None, metric
        assert reader.read({"trace": None}, **_args(metric)) is None, metric


def test_the_prefill_metrics_read_the_scope_and_the_real_tokens():
    names = {256: {
        "fusion.1": "jit(prefill_fn)/KimiLinear/kda/layer_0_attn/"
                    "pdt.kda.prefill/while/body/dot_general",
        "fusion.2": "jit(prefill_fn)/KimiLinear/layer_1_moe/moe/experts/x"}}
    modules = [("jit_prefill_fn(3)", 1.0, 2.0), ("jit_prefill_fn(3)", 3.0, 4.0)]
    ops = [("%fusion.1 = f32[8] fusion(...)", 1.0, 1.004),
           ("%fusion.2 = bf16[8] fusion(...)", 1.1, 1.9),
           # the scan's own event spans its body's and is not counted
           ("%while.9 = (s32[], f32[8]) while(%tuple.1), body=%b", 3.0, 3.5),
           ("%fusion.1 = f32[8] fusion(...)", 3.0, 3.002)]
    spans = [HostSpan("engine.prefill", 0.9, 2.1, {"bucket": 256,
                                                   "n_real": 200}),
             HostSpan("engine.prefill", 2.9, 4.1, {"bucket": 256,
                                                   "n_real": 256})]
    context = _context(modules, ops, spans)
    context["prefill_op_names"] = lambda bucket: names.get(bucket, {})
    ms = prefill_time_by_scope.read(context, **_args("prefill_kda_ms_p50"))
    assert abs(ms - 2.0) < 1e-9        # of 4 and 2 ms a run (nearest rank)
    share = prefill_kernel_roofline.read(
        context, **_args("kda_prefill_roofline_pct"))
    assert abs(share - 100 * 456 * 3264 / 0.006 / 197e12) < 1e-12
    context["prefill_op_names"] = lambda bucket: {}
    context.pop("prefill_runs")
    for metric, reader in (("prefill_kda_ms_p50", prefill_time_by_scope),
                           ("kda_prefill_roofline_pct",
                            prefill_kernel_roofline)):
        assert reader.read(context, **_args(metric)) is None
        assert reader.read({"trace": None}, **_args(metric)) is None


# -- the sample a run checks --------------------------------------------------

@dataclasses.dataclass
class _Served:
    arrivals: list
    tokens: dict


def test_the_sample_holds_a_long_and_a_short_prompt():
    from chipbench.families import kimi_linear as family

    lengths = [300, 1000, 3000, 4000, 700, 1500, 3500, 400]
    arrivals = [loadgen.Arrival(float(i), np.zeros(n, np.int32), 8, i != 3)
                for i, n in enumerate(lengths)]
    served = _Served(arrivals, {i: [0] * 8 for i in range(len(lengths))
                                if i != 5})
    sample = family.sample_of(served, seed=3)
    assert len(sample) == family.CHECKED_REQUESTS == len(set(sample))
    # the longest MEASURED and FINISHED over 2,048, the shortest under 512
    assert 6 in sample and 0 in sample and 3 not in sample and 5 not in sample
    assert family.sample_of(served, seed=3) == sample
    assert family.sample_of(_Served(arrivals, {}), seed=3) == []


def test_each_degraded_reference_names_knobs_the_reference_has():
    import inspect

    from chipbench.families import kimi_linear as family

    config = cells.resolve(BENCH, CELL).config
    knobs = set(inspect.signature(family.reference.forward).parameters)
    assert set(family.DEGRADED) == {
        "reference_8bit", "reference_7_experts", "reference_no_decay",
        "reference_beta_one", "reference_3_tap_conv"}
    # ISSUE 45's other two, which no rule on served tokens tells from the
    # float32 reference while it passes a bfloat16 program
    assert set(family.NOT_TOLD_APART_ON_THE_CHIP) == {
        "reference_mla_rotated", "reference_bf16_state"}
    both = {**family.DEGRADED, **family.NOT_TOLD_APART_ON_THE_CHIP}
    for name, make in both.items():
        assert set(make(config)) <= knobs, name
    assert family.DEGRADED["reference_7_experts"](config) == {
        "experts_per_token": 7}
    assert family.DEGRADED["reference_3_tap_conv"](config) == {"conv_taps": 3}


# -- the driver end to end, at a size only this test chooses ------------------

TINY = {
    "family": "kimi_linear", "vocab_size": 256, "model_max_length": 4096,
    "num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "rope_scaling": None,
    "rope_theta": 10000, "mla_use_nope": True, "intermediate_size": 96,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "num_experts": 4, "router_width": 16, "held_experts_first": 4,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"kda_layers": [1, 2, 3], "full_attn_layers": [4],
                           "num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4},
    "assumed": {"compute_dtype": "float32", "param_dtype": "float32",
                "initializer_range": 0.02, "kda_chunk": 64,
                "state_dtype": "float32"},
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
    from chipbench.families import kimi_linear as family

    # the prompts here are tens of tokens long, not thousands
    monkeypatch.setattr(family, "LONG", 40)
    monkeypatch.setattr(family, "SHORT", 10)
    # float32 on both sides, and router logits of a 64-wide model: a tie is
    # near where the logits differ by rounding, not by 0.02
    monkeypatch.setattr(family, "NEAR_TIE", 1e-5)
    cell = cells.Cell("tiny", 1, "tiny", TINY, "tiny", TINY_SERVE, [], [])
    # the harness's threshold for writing a program to the compile cache,
    # which the reference raises past any compile while it runs, and puts
    # back
    flag = "jax_persistent_cache_min_compile_time_secs"
    assert family.NEVER_CACHED_S == float("inf")
    seen = []
    forward = family.reference.forward
    monkeypatch.setattr(family.reference, "forward", lambda *a, **k: (
        seen.append((getattr(jax.config, flag), a[1].shape[0],
                     k["logits_to"] - k["logits_from"])), forward(*a, **k))[1])
    before = getattr(jax.config, flag)
    result = serve_open_loop_by_family.run(cell, 2 ** 31 + 11, 1.0, False,
                                           jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    assert getattr(jax.config, flag) == before != float("inf")
    # one width, and the head's rows are the longest output's
    assert {s for s in seen} == {(float("inf"), 128, 12)}
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
    assert check["reference_s"] > 0


def test_the_family_builds_only_what_the_configuration_states(monkeypatch):
    """What no limit on served tokens can tell (``NOT_TOLD_APART_ON_THE_
    CHIP``) is held where it is stated: a cache that keeps a KDA state in
    another type than ``assumed.state_dtype``, positions let into the MLA
    layers, or a chunk other than the one the cost function counts with, is
    refused before anything is served."""
    import jax.numpy as jnp

    from chipbench.families import kimi_linear as family
    from pytorch_distributed_tpu.ops import kda
    from pytorch_distributed_tpu.serving import HybridStateCache

    config = cells.resolve(BENCH, CELL).config
    assert config["assumed"]["state_dtype"] == "float32"
    assert config["assumed"]["kda_chunk"] == kda.CHUNK
    family.build_model(TINY)
    for key, value, said in [("kda_chunk", 128, "chunks of 128"),
                             ("state_dtype", "bfloat16", "bfloat16 KDA state")]:
        with pytest.raises(ValueError, match=said):
            family.build_model(dict(TINY, assumed=dict(TINY["assumed"],
                                                       **{key: value})))
    with pytest.raises(ValueError, match="no rotation"):
        family.build_model(dict(TINY, mla_use_nope=False))
    # the program's side of it: a cache that rounds its states
    create = HybridStateCache.create.__func__

    def rounded(cls, *args, **kwargs):
        cache = create(cls, *args, **kwargs)
        return cache.replace(state=tuple(s.astype(jnp.bfloat16)
                                         for s in cache.state))

    monkeypatch.setattr(HybridStateCache, "create", classmethod(rounded))
    with pytest.raises(ValueError, match=r"keeps \['bfloat16'\]"):
        family.build_model(TINY)
