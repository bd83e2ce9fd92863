"""The ``mimo_v2`` family: how a configuration file of MiMo-V2.5's sizes
(the source's own keys) becomes the program's model, and how what the
program served is held to the plain reference ``references/mimo_v2.py``.

The program has no model file of this family's own: the block of
``pytorch_distributed_tpu/models/exaone_moe.py`` is configured
(``model_config`` below says which of its fields state what).

The file states the SHARE this chip holds: ``n_routed_experts`` experts of
the ``published`` number, from ``held_experts_first`` on (the router stays
``router_width`` = the published number wide and chooses among all of
them), ``vocab_size`` rows of the vocabulary, and the first
``num_hidden_layers`` layers (``hybrid_layer_pattern`` and
``moe_layer_freq`` name their kinds).

The sample, the regrets and the rule are ``families/exaone_moe.py``'s
(``faults_of``: router near ties by logit margin set aside, then the exact
share and the share over the tolerance), at this family's own constants."""

from __future__ import annotations

import time
from typing import Any, Dict

from chipbench.families.xing4 import regrets_of
from chipbench.references import mimo_v2 as reference

#: Finished requests the reference checks a run. The sample always holds
#: the finished request with the longest prompt over ``LONG`` tokens and the
#: one with the shortest (where the run finished such): the first has read
#: the full layers thousands of rows deep at 16 query heads a K/V head, and
#: every request of this traffic (answers of 256 and more) has wrapped
#: every ring twice at the least.
CHECKED_REQUESTS = 4
LONG, SHORT = 8192, 4096
#: The reference runs a checked request at the smallest of these widths
#: that holds it, or at the traffic's longest prompt and output rounded up
#: to 128 (24,576); its head always reads the traffic's longest output's
#: rows, so no seed's lengths add a program: three kinds of layer and the
#: head at two widths. NOTHING that compiles while it runs is written to
#: the compile cache, which the machines cap at 192 MiB and which is full
#: (PERF.md section 7): the threshold for writing is raised past any
#: compile for as long as it runs and put back after
#: (``families/kimi_linear.py`` measured what that costs a run, after the
#: measured window and outside ``setup_s``: the ``check`` line's
#: ``reference_s``).
WIDTHS = (8192,)
NEVER_CACHED_S = float("inf")
#: A position is a NEAR TIE where, in some expert layer, the reference's
#: router LOGIT of the last expert it chose lies less than this above the
#: best it did not choose (``families/xing4.py`` says why such positions
#: are set aside: a flipped expert is another function, not a rounding).
#: An expert layer's input is normed here, so a router's 256 logits have a
#: deviation of 1.28 (0.02 x sqrt(4096)) and the eighth and ninth largest
#: lie about 0.07 apart; the program's bfloat16 residual moves a logit by a
#: few thousandths. On the chip (``records/mimo-v2.5/limits_readings*.log``,
#: 2,291 tokens of four requests) the eighth and ninth lie closer than
#: 0.002 in SOME of six expert layers at 15% of positions, than 0.005 at
#: 34%, than 0.01 at 57%, than 0.02 at 81%, and the program's exact share
#: reads 0.978 with nothing set aside, 0.978 / 0.980 / 0.987 / 0.986 at
#: 0.002 / 0.005 / 0.01 / 0.02 with NO token over the tolerance at any: at
#: a 16-way share a flipped expert is seldom held, so the near ties hold
#: little here; 0.005 keeps two thirds of what was checked (33-36% aside
#: over the six seeds of ``setA``). More than ``MAX_NEAR_TIES`` would say
#: that the run checked almost nothing.
NEAR_TIE = 0.005
MAX_NEAR_TIES = 0.6
#: the thresholds ``tools/check_limits_knobs.py`` prints its readings at
THRESHOLDS_READ = (0.0, 0.002, 0.005, 0.01, 0.02, 0.05)
#: Of the other positions, two SHARES (as ``families/xing4.py``: the worst
#: regret is reported, not limited): how many are the reference's argmax
#: itself, and how many lie more than ``TOKEN_TOLERANCE`` of the
#: reference's logit range below its best. Readings on the chip at 0.005
#: (``records/mimo-v2.5/limits_readings_final.log``, 2,291 tokens of four
#: requests, 1,519 off the near ties; then the twelve seeds of ``setA`` and
#: ``setB`` and two traced; PERF.md, PR 49), exact share | share over the
#: tolerance: the program 0.982 | 0 (fourteen seeds: 0.972-0.983 | at most 1
#: token in 1,878 = 0.0005); the reference in 8 bits 0.573 | 0.277; with no
#: sink 0.795 | 0.065; with all 192 columns rotated 0.027 | 0.959; with the
#: two bases swapped 0.065 | 0.920; with a value scale of 1 0.495 | 0.387;
#: with the window layers' heads mapped as the full layers' 0.188 | 0.756.
#: Every one of the six fails BOTH limits. The exact share lies midway
#: between the program's lowest reading and the nearest degraded one (no
#: sink): 0.09 of room on either side; the share over the tolerance fifty
#: times over the program's highest reading and under half the nearest
#: degraded one's (no sink, again).
TOKEN_TOLERANCE = 2.0 ** -6
MAX_OVER_TOLERANCE = 0.03
MIN_EXACT_SHARE = 0.88


#: The degraded references the limits must each refuse
#: (``tools/check_limits_knobs.py``): a name and, from the configuration,
#: the knobs of ``reference.forward`` that make it.
DEGRADED = {
    "reference_8bit": lambda config: {"round_to": "float8_e4m3fn"},
    "reference_no_sink": lambda config: {"no_sink": True},
    "reference_all_columns_rotated": lambda config: {"rotate_all": True},
    "reference_bases_swapped": lambda config: {"swap_bases": True},
    "reference_value_scale_1": lambda config: {"value_scale": 1.0},
    "reference_window_heads_as_full": lambda config: {
        "window_heads_as_full": True},
}
#: Two more that ISSUE 49 lists and NO rule on served tokens can refuse
#: while it passes the bfloat16 program with room for fresh seeds (same
#: log): with 7 experts a token 0.947 | 0, with a window of 127 0.958 | 0,
#: where the program's fourteen seeds read down to 0.972. At a 16-way
#: share a token's last expert is held here one time in sixteen a layer,
#: and one key of 128 under residuals that no norm rescales moves the
#: logits less than bfloat16 does (with the sinks drawn normal(0, 1), as
#: first run, they read 0.967 and 0.970 beside 0.980:
#: ``limits_readings_sink_0_1.log``).
#: ``tools/check_limits_knobs.py`` does not read this list
#: (``records/mimo-v2.5/limits_all.py`` reads both). What holds them
#: instead, since tokens cannot: ``tests/test_mimo_v2.py`` in float32, where
#: ``TOL`` = 1e-4 fails both references by an order; the window is the
#: depth of the rings the program allocates and reads
#: (``kv_ring_rows_step`` counts 128 a wrapped slot a layer, and the cache's
#: shapes are held in the tests), and the experts a token come from the
#: configuration file through ``model_config`` to ``route_sigmoid_topk``
#: with no constant between.
NOT_TOLD_APART_ON_THE_CHIP = {
    "reference_7_experts": lambda config: {
        "experts_per_token": config["num_experts_per_tok"] - 1},
    "reference_window_127": lambda config: {
        "window": config["sliding_window"] - 1},
}


def model_config(config: Dict[str, Any]):
    """The one configured block's config (``models/exaone_moe.py``) from
    the source's keys: the kinds of layer from ``hybrid_layer_pattern`` and
    ``moe_layer_freq``, a window layer's base as ``rope_theta`` and a full
    layer's as ``full_rope_theta``."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import ExaoneMoEConfig

    assumed = config["assumed"]
    if config["rope_scaling"]["rope_type"] != "default":
        raise ValueError("no scaled rotary positions in this family")
    for kind in ("head_dim", "v_head_dim", "num_attention_heads"):
        if config[f"swa_{kind}"] != config[kind]:
            raise ValueError(f"swa_{kind} differs from {kind}: one block "
                             f"holds one of each")
    if config["add_full_attention_sink_bias"] or config["n_shared_experts"] \
            or config["routed_scaling_factor"] is not None:
        raise ValueError("a sink in the full layers, a shared expert or a "
                         "scale on the gates: not this family as published")
    same = ("vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "sliding_window")
    return ExaoneMoEConfig(
        n_layer=config["num_hidden_layers"],
        n_positions=config["max_position_embeddings"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"],
                      config["n_routed_experts"]),
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in config["hybrid_layer_pattern"]),
        mlp_layer_types=tuple("sparse" if m else "dense"
                              for m in config["moe_layer_freq"]),
        num_shared_experts=0, routed_scaling_factor=1.0,
        rms_norm_eps=config["layernorm_epsilon"],
        norm_first=True, qk_norm=False,
        window_key_value_heads=config["swa_num_key_value_heads"],
        rotary_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        rope_theta=float(config["swa_rope_theta"]),
        full_rope_theta=float(config["rope_theta"]),
        value_scale=config["attention_value_scale"],
        window_sink=config["add_swa_attention_sink_bias"],
        initializer_range=assumed["initializer_range"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        **{key: config[key] for key in same})


def build_model(config: Dict[str, Any]):
    from pytorch_distributed_tpu.models import ExaoneMoE

    return ExaoneMoE(model_config(config))


def sample_of(served, seed: int):
    """The finished measured requests a run checks: the one with the
    longest prompt over ``LONG`` tokens, the shortest under ``SHORT``, and
    a seeded sample of the others."""
    import numpy as np

    done = sorted(i for i in served.tokens if served.arrivals[i].measured)
    length = {i: len(served.arrivals[i].prompt) for i in done}
    ends = {max((i for i in done if length[i] > LONG), key=length.get,
                default=None),
            min((i for i in done if length[i] < SHORT), key=length.get,
                default=None)} - {None}
    rest = [i for i in done if i not in ends]
    rng = np.random.default_rng(seed)
    more = rng.choice(rest, max(0, min(CHECKED_REQUESTS - len(ends),
                                       len(rest))), replace=False)
    return sorted(ends) + [int(i) for i in more]


def reference_logits(variables, config, traffic, served, i: int, **knobs):
    """Teacher forcing of request ``i`` on the plain reference: ``(tokens,
    logits [len(tokens), V], margin [len(tokens)])``, the reference's logits
    at the position that produced each served token and that position's
    smallest router margin. ``knobs`` go to ``reference.forward``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prompt = served.arrivals[i].prompt
    tokens = np.asarray(served.tokens[i])
    seq = np.concatenate([prompt, tokens[:-1]])
    out_max = traffic["output_len"]["max"]
    longest = 128 * -(-(traffic["prompt_len"]["max"] + out_max) // 128)
    first = len(prompt) - 1
    width = min([w for w in WIDTHS if first + out_max <= w < longest]
                + [longest])
    buf = np.zeros((width,), np.int32)
    buf[:len(seq)] = seq                 # causal: the padded tail is unseen
    flag = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, flag)
    jax.config.update(flag, NEVER_CACHED_S)
    try:
        logits, margin = reference.forward(
            variables["params"], jnp.asarray(buf), config, logits_from=first,
            logits_to=first + out_max, **knobs)
        logits, margin = np.asarray(logits, np.float32), np.asarray(margin)
    finally:
        jax.config.update(flag, was)
    return (tokens, logits[:len(tokens)],
            margin[first:first + len(tokens)])


def served_regrets(variables, config, traffic, served, seed: int):
    """``(regrets, margins)`` of the served tokens of the checked sample."""
    import numpy as np

    regrets, margins = [np.zeros(0)], [np.zeros(0)]
    for i in sample_of(served, seed):
        tokens, logits, margin = reference_logits(
            variables, config, traffic, served, i)
        regrets.append(regrets_of(logits, tokens))
        margins.append(margin)
    return np.concatenate(regrets), np.concatenate(margins)


def faults_of(regrets, margins):
    """``(record, faults)`` of checked positions' regrets and router
    margins under the rule of the module's constants."""
    tie = margins < NEAR_TIE
    rest = regrets[~tie]
    over = int((rest > TOKEN_TOLERANCE).sum())
    record = {
        "checked_tokens": int(len(regrets)),
        "router_near_ties": int(tie.sum()),
        "argmax_matches": int((rest == 0).sum()),
        "over_tolerance": over,
        "worst_regret": float(rest.max()) if len(rest) else None,
        "near_tie_argmax_matches": int((regrets[tie] == 0).sum()),
        "near_tie_worst_regret": float(regrets[tie].max()) if tie.any()
        else None,
    }
    faults = []
    if not len(regrets):
        faults.append("no finished request to check")
    elif tie.mean() > MAX_NEAR_TIES or not len(rest):
        faults.append(f"{tie.mean():.3f} of the checked positions are router "
                      f"near ties (limit {MAX_NEAR_TIES})")
    elif over > MAX_OVER_TOLERANCE * len(rest):
        faults.append(f"{over} of {len(rest)} served tokens lie more than "
                      f"{TOKEN_TOLERANCE} of the logit range below the "
                      f"reference's best (limit {MAX_OVER_TOLERANCE}; worst "
                      f"{rest.max():.4f})")
    elif (rest == 0).mean() < MIN_EXACT_SHARE:
        faults.append(f"only {(rest == 0).mean():.3f} of the served tokens "
                      f"are the reference's argmax (limit {MIN_EXACT_SHARE})")
    return record, faults


def check_served(variables, config, traffic, served, seed: int):
    """``(record, faults)``: the ``check`` line's numbers and why the run is
    not correct, if it is not (the rule: module constants above)."""
    t0 = time.perf_counter()
    record, faults = faults_of(*served_regrets(
        variables, config, traffic, served, seed))
    # the reference's own compiles among them, every run (NEVER_CACHED_S)
    record["reference_s"] = time.perf_counter() - t0
    record["checked_prompt_lens"] = [len(served.arrivals[i].prompt)
                                     for i in sample_of(served, seed)]
    return record, faults
