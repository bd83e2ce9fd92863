"""The ``sarvam_mla`` family (``sarvamai/sarvam-105b``): how a configuration
file of its sizes (the source's own keys) becomes the program's model, and
how what the program served is held to the plain reference
``references/sarvam_mla.py``.

The program has no model file of this name: the block is
``models/kimi_linear.py``'s with no KDA layer, rotation under YaRN and 64
heads (``ROADMAP.md`` R1: one configured block before a fifth model file).
The file states the SHARE this chip holds: ``num_experts`` experts of the
``published`` number, from ``held_experts_first`` on (the router stays
``router_width`` wide), ``vocab_size`` rows of the vocabulary, the first
``num_hidden_layers`` layers.

WHICH REQUESTS A RUN CHECKS (``sample_of``; the driver
``serve_sessions_by_family`` says what each admission found cached): the
COLD ask of the longest document over ``LONG`` tokens, a WARM ask of that
same document, the warm ask with the most cached positions admitted after
the radix tree first gave a page back (where the run had one) and the
shortest document's cold ask: a prompt through the prefill kernel's longest
grid and decode steps over the longest chain, a tail behind shared pages, a
tail behind pages that outlived a reclaim, and a prompt of few blocks. The
regrets and the rule are ``families/kimi_linear.py``'s (``faults_of``:
router near ties set aside, then the exact share and the share over the
tolerance), at this family's own constants."""

from __future__ import annotations

import time
from typing import Any, Dict

from chipbench.families.xing4 import regrets_of
from chipbench.references import sarvam_mla as reference

LONG = 16384
#: The reference runs a checked request at ONE width (the traffic's longest
#: document, question and answer rounded up to 128: 29,440) and its head
#: reads the longest answer's rows, so no seed's lengths add a program.
#: Nothing that compiles while it runs is written to the compile cache
#: (``families/kimi_linear.py::NEVER_CACHED_S`` says why).
NEVER_CACHED_S = float("inf")
#: A position is a NEAR TIE where, in some expert layer, the reference's
#: router LOGIT of the last expert it chose lies less than this above the
#: best it did not choose (``families/xing4.py``: a flipped expert is
#: another function, not a rounding). An expert layer's input is normed, so
#: a router's 128 logits have a deviation of 1.28 (0.02 x sqrt(4096)).
NEAR_TIE = 0.005
MAX_NEAR_TIES = 0.6
THRESHOLDS_READ = (0.0, 0.002, 0.005, 0.01, 0.02, 0.05)
#: Of the other positions, two SHARES: how many are the reference's argmax
#: itself, and how many lie more than ``TOKEN_TOLERANCE`` of the
#: reference's logit range below its best. Readings on the chip at 0.005
#: (``records/sarvam-105b/limits_readings.log``, 305 tokens of three
#: requests, 85 near ties; PERF.md section 6, PR 54), exact share | share
#: over the tolerance: the program 0.941 | 0.023 (its runs: section 6); the
#: reference in 8 bits 0.495 | 0.400; with 7 experts a token 0.764 | 0.091;
#: with no rotation 0.000 | 1.000; with one page of the chain another's rows
#: 0.591 | 0.264; with the last whole page of the prompt never read 0.650 |
#: 0.200. Every one of the five fails BOTH limits, each of which lies about
#: midway between the program's reading and the nearest degraded one (7
#: experts a token).
TOKEN_TOLERANCE = 2.0 ** -6
MAX_OVER_TOLERANCE = 0.055
MIN_EXACT_SHARE = 0.85


def _last_page(prompt_len: int) -> int:
    """The last whole page of a prompt's positions."""
    return (prompt_len // 128 - 1) * 128


#: The degraded references the limits must each refuse
#: (``records/sarvam-105b/limits.py``): a name and, from the configuration
#: and the checked prompt's length, the knobs of ``reference.forward``.
DEGRADED = {
    "reference_8bit": lambda config, n: {"round_to": "float8_e4m3fn"},
    "reference_7_experts": lambda config, n: {
        "experts_per_token": config["num_experts_per_tok"] - 1},
    "reference_no_rotation": lambda config, n: {"no_rotation": True},
    "reference_page_of_another": lambda config, n: {
        "swap_page": (_last_page(n), 0, 128)},
    "reference_read_a_page_short": lambda config, n: {
        "drop_page": (_last_page(n), 128)},
}
#: What the issue lists and no rule on served tokens can refuse while it
#: passes the bfloat16 program: the reference with every product's operands
#: rounded to bfloat16 IS the program's arithmetic and reads 0.964 | 0.005,
#: nearer the float32 reference than the program's own 0.941 | 0.023 (same
#: log). The nearest precision BELOW the one the configuration states is 8
#: bits, which ``DEGRADED`` holds.
NOT_TOLD_APART_ON_THE_CHIP = {
    "reference_bf16": lambda config, n: {"round_to": "bfloat16"},
}


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.kimi_linear import KimiLinearConfig

    assumed, scaling = config["assumed"], config["rope_scaling"]
    if scaling["type"] != "deepseek_yarn" or scaling["mscale"] != \
            scaling["mscale_all_dim"]:
        raise ValueError(
            "this family rotates under DeepSeek's YaRN with cos and sin "
            "unscaled (mscale = mscale_all_dim)")
    if config["q_head_dim"] != (config["qk_nope_head_dim"]
                                + config["qk_rope_head_dim"]):
        raise ValueError("q_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    same = ("vocab_size", "hidden_size", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "first_k_dense_replace",
            "moe_intermediate_size", "num_shared_experts", "rms_norm_eps")
    layers = config["num_hidden_layers"]
    return KimiLinearConfig(
        n_layer=layers, n_positions=config["max_position_embeddings"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"], config["num_experts"]),
        num_experts_per_token=config["num_experts_per_tok"],
        kda_layers=(), full_attn_layers=tuple(range(1, layers + 1)),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original_max_position_embeddings=scaling[
            "original_max_position_embeddings"],
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        initializer_range=assumed["initializer_range"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        **{key: config[key] for key in same})


def build_model(config: Dict[str, Any]):
    from pytorch_distributed_tpu.models.kimi_linear import KimiLinear

    return KimiLinear(model_config(config))


def sample_of(served, seed: int):
    """The finished measured requests a run checks (module docstring), as
    stream indices; ``served.admitted`` says what each admission found."""
    done = [i for i in sorted(served.tokens)
            if served.arrivals[i].measured and i in served.admitted]
    ask = served.arrivals
    cached = {i: served.admitted[i]["cached_len"] for i in done}
    cold = [i for i in done if cached[i] == 0]
    warm = [i for i in done if cached[i] > 0]
    chosen = []
    long_cold = max((i for i in cold if ask[i].doc_len > LONG),
                    key=lambda i: ask[i].doc_len, default=None)
    if long_cold is not None:
        chosen.append(long_cold)
        again = [i for i in warm if ask[i].doc == ask[long_cold].doc]
        if again:
            chosen.append(max(again, key=cached.get))
    after = [i for i in warm if served.admitted[i]["reclaimed_before"] > 0]
    if after:
        chosen.append(max(after, key=cached.get))
    if cold:
        chosen.append(min(cold, key=lambda i: ask[i].doc_len))
    if not any(cached[i] > 0 for i in chosen) and warm:
        chosen.append(max(warm, key=cached.get))    # a short window's run
    return list(dict.fromkeys(chosen))


def reference_width(traffic) -> int:
    longest = (traffic["doc_len"]["max"] + traffic["question_len"]["max"]
               + traffic["output_len"]["max"])
    return 128 * -(-longest // 128)


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
    first = len(prompt) - 1
    buf = np.zeros((reference_width(traffic),), np.int32)
    buf[:len(seq)] = seq     # causal: the padded tail is unseen
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
    not correct, if it is not."""
    t0 = time.perf_counter()
    sample = sample_of(served, seed)
    record, faults = faults_of(*served_regrets(
        variables, config, traffic, served, seed))
    if sample and not any(served.admitted[i]["cached_len"] for i in sample):
        faults.append("no warm ask among the finished requests to check")
    # the reference's own compiles among them, every run (NEVER_CACHED_S)
    record["reference_s"] = time.perf_counter() - t0
    record["checked"] = [
        {"doc": served.arrivals[i].doc, "ask": served.arrivals[i].ask,
         "prompt_len": len(served.arrivals[i].prompt),
         **served.admitted[i]} for i in sample]
    return record, faults
