"""The ``exaone_moe`` family: how a configuration file of K-EXAONE's sizes
(the source's own keys) becomes the program's model, and how what the
program served is held to the plain reference ``references/exaone_moe.py``.

The file states the SHARE this chip holds: ``num_experts`` experts of the
``published`` number, from ``held_experts_first`` on (the router stays
``router_width`` = the published number wide and chooses among all of
them), and ``vocab_size`` rows of the vocabulary."""

from __future__ import annotations

from typing import Any, Dict

from chipbench.families.xing4 import regrets_of
from chipbench.references import exaone_moe as reference

#: Finished requests the reference checks a run. The sample always holds
#: the longest finished request over ``LONG`` tokens and the shortest under
#: ``SHORT`` (where the run finished such): the first has wrapped every
#: ring two hundred times and read a full layer thousands of rows deep, the
#: second is served almost wholly from inside one window.
CHECKED_REQUESTS = 4
LONG, SHORT = 8192, 1024
#: The reference runs a checked request at the smallest of these widths
#: that holds it, or at the traffic's longest prompt and output rounded up
#: to 128. The machines cap the compile cache, and what this cell adds to it
#: can push an old cell's programs out (PERF.md section 7): so the reference
#: has TWO widths, its head always reads the traffic's longest output's
#: rows (ONE head program, whatever a seed's lengths), and what compiles in
#: under ``CACHED_FROM_S`` while it runs (slices and gathers shaped by a
#: seed's own lengths) is not written to the cache. That leaves seven
#: entries, three kinds of layer at two widths and the head, and no seed
#: adds another (``records/k-exaone/review/call.log``).
WIDTHS = (8192,)
CACHED_FROM_S = 1.0      # JAX's own default; ``run.py`` sets 0
#: A position is a NEAR TIE where, in some expert layer, the reference's
#: router LOGIT of the last expert it chose lies less than this above the
#: best it did not choose (``families/xing4.py`` says why such positions
#: are set aside: a flipped expert is another function, not a rounding;
#: ``references/exaone_moe.py::experts`` says why the margin is in logits).
#: Here a flip matters less than there (seven of eight chosen experts are
#: another chip's and add nothing): the program's exact share is 0.974
#: with no position set aside and 0.979 / 0.981 / 0.986 at 0.01 / 0.02 /
#: 0.05; what the near ties hold is the few LARGE regrets (4 of 1,182
#: tokens over 2^-6 at 0, 1 at 0.01, 0 at 0.02). 0.02 sets 28% of the
#: checked positions aside; more than ``MAX_NEAR_TIES`` would say that the
#: run checked almost nothing.
NEAR_TIE = 0.02
MAX_NEAR_TIES = 0.6
#: the thresholds ``tools/check_limits_knobs.py`` prints its readings at
THRESHOLDS_READ = (0.0, 0.01, 0.02, 0.05, 0.1, 0.2)
#: Of the other positions, two SHARES (as ``families/xing4.py``: the worst
#: regret is reported, not limited): how many are the reference's argmax
#: itself, and how many lie more than ``TOKEN_TOLERANCE`` of the
#: reference's logit range below its best. Readings on the chip
#: (``tools/check_limits_knobs.py``, 1,182 tokens of four requests, 851 not
#: near ties, ``records/k-exaone/limits_readings.log``; then the six seeds
#: of ``records/k-exaone/setA``; PERF.md, PR 40), exact share | share over
#: the tolerance: the program 0.981 | 0 (six seeds: 0.978-0.996 | 0); the
#: reference in 8 bits 0.48 | 0.39; with 7 experts a token 0.894 | 0.027;
#: with a window of 127 0.857 | 0.025; with the window layers computed full
#: 0.04 | 0.95; with rotation in the full layer 0.847 | 0.025. Every
#: degraded reference fails BOTH limits; the exact share has the more room
#: (0.04 on either side of the limit).
TOKEN_TOLERANCE = 2.0 ** -6
MAX_OVER_TOLERANCE = 0.015
MIN_EXACT_SHARE = 0.94


#: The degraded references the limits must each refuse
#: (``tools/check_limits_knobs.py``): a name and, from the configuration,
#: the knobs of ``reference.forward`` that make it.
DEGRADED = {
    "reference_8bit": lambda config: {"round_to": "float8_e4m3fn"},
    "reference_7_experts": lambda config: {
        "experts_per_token": config["num_experts_per_tok"] - 1},
    "reference_window_127": lambda config: {
        "window": config["sliding_window"] - 1},
    "reference_window_layers_full": lambda config: {
        "window_layers_full": True},
    "reference_full_layer_rotated": lambda config: {"rotate_full": True},
}


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import ExaoneMoEConfig

    assumed, rope = config["assumed"], config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise ValueError(f"no rope_type {rope['rope_type']!r} in this family")
    same = ("vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "num_shared_experts", "sliding_window", "rms_norm_eps")
    return ExaoneMoEConfig(
        n_layer=config["num_hidden_layers"],
        n_positions=config["max_position_embeddings"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"], config["num_experts"]),
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(rope["rope_theta"]),
        initializer_range=assumed["initializer_range"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        **{key: config[key] for key in same})


def build_model(config: Dict[str, Any]):
    from pytorch_distributed_tpu.models import ExaoneMoE

    return ExaoneMoE(model_config(config))


def sample_of(served, seed: int):
    """The finished measured requests a run checks: the longest over
    ``LONG`` tokens, the shortest under ``SHORT``, and a seeded sample of
    the others."""
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
    jax.config.update(flag, CACHED_FROM_S)
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
    record, faults = faults_of(*served_regrets(
        variables, config, traffic, served, seed))
    record["checked_prompt_lens"] = [len(served.arrivals[i].prompt)
                                     for i in sample_of(served, seed)]
    return record, faults
