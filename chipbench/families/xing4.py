"""The ``xing4`` family: how a configuration file of Xing4.0's sizes (the
source's own keys) becomes the program's model, and how what the program
served is held to the plain reference ``references/xing4.py``."""

from __future__ import annotations

from typing import Any, Dict

from chipbench.references import xing4 as reference

#: Finished requests the reference checks a run (a request is one forward
#: of 8,192 positions through every expert: seconds each).
CHECKED_REQUESTS = 6
#: A position is a NEAR TIE where, in some expert layer, the reference's
#: score of the last expert it chose lies less than this above the best it
#: did not choose. With random routers the fourth and fifth of 64 sigmoid
#: scores lie 0.02 apart on average, bfloat16 activations move a score by
#: about 0.001, and a flipped expert is another function, not a rounding
#: (on the chip the near ties' worst regret is 0.16-0.30 of the range,
#: where the other positions' median worst is 0.02). The limits below hold
#: for the other positions only; the near ties (0.63-0.70 of what was
#: checked, fourteen runs) are counted on the ``check`` line and may be no
#: more than ``MAX_NEAR_TIES``. A computation that routes to three experts,
#: or in eight bits, is wrong at EVERY position and fails on the others.
NEAR_TIE = 0.004
MAX_NEAR_TIES = 0.85
#: Of the other positions, two SHARES (a flip at an earlier position
#: reaches a later one through attention, so one token's regret is not
#: bounded by rounding: the worst of 200-400 read 0.003-0.39 over fourteen
#: runs, and it is reported, not limited): how many are the reference's
#: argmax itself, and how many lie more than ``TOKEN_TOLERANCE`` of the
#: reference's logit range below its best. Each limit lies between two
#: readings on the chip (``tools/check_limits.py``; PERF.md, PR 33): the
#: program reads 0.937-0.982 exact and at most 0.019 over the tolerance;
#: the reference in 8 bits 0.41 and 0.49, with 3 experts a token 0.32 and
#: 0.56. Both degraded readings fail both limits.
TOKEN_TOLERANCE = 2.0 ** -6
MAX_OVER_TOLERANCE = 0.10
MIN_EXACT_SHARE = 0.75


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import Xing4Config

    assumed, rope = config["assumed"], config["rope_scaling"]
    same = ("vocab_size", "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "first_k_dense_replace",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "rms_norm_eps")
    return Xing4Config(
        n_layer=config["num_hidden_layers"],
        n_positions=config["max_position_embeddings"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        mhc_h_res_clamp_min=float(config["mhc_h_res_clamp_min"]),
        mhc_h_res_clamp_max=float(config["mhc_h_res_clamp_max"]),
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max_position_embeddings=rope[
            "original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        initializer_range=assumed["initializer_range"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        **{key: config[key] for key in same})


def build_model(config: Dict[str, Any]):
    from pytorch_distributed_tpu.models import Xing4

    return Xing4(model_config(config))


def sample_of(served, seed: int):
    """The finished measured requests a run checks: a seeded sample."""
    import numpy as np

    done = sorted(i for i in served.tokens if served.arrivals[i].measured)
    rng = np.random.default_rng(seed)
    return rng.choice(done, min(CHECKED_REQUESTS, len(done)), replace=False)


def reference_logits(variables, config, traffic, served, i: int, **knobs):
    """Teacher forcing of request ``i`` on the plain reference: ``(tokens,
    logits [len(tokens), V], margin [len(tokens)])``, the reference's logits
    at the position that produced each served token and that position's
    smallest router margin. ``knobs`` go to ``reference.forward``."""
    import jax.numpy as jnp
    import numpy as np

    # one width for every run, so that the programs are always cached
    width = 128 * -(-(traffic["prompt_len"]["max"]
                      + traffic["output_len"]["max"]) // 128)
    prompt = served.arrivals[i].prompt
    tokens = np.asarray(served.tokens[i])
    seq = np.concatenate([prompt, tokens[:-1]])
    buf = np.zeros((width,), np.int32)
    buf[:len(seq)] = seq                 # causal: the padded tail is unseen
    first = len(prompt) - 1
    logits, margin = reference.forward(
        variables["params"], jnp.asarray(buf), config, logits_from=first,
        **knobs)
    return (tokens, np.asarray(logits[:len(tokens)], np.float32),
            np.asarray(margin[first:first + len(tokens)]))


def regrets_of(logits, tokens):
    """How far the logit of each token lies below the best at its position,
    as a share of the logit range there (0 = the token IS the argmax)."""
    import numpy as np

    top = logits.max(-1)
    got = logits[np.arange(len(tokens)), tokens]
    return (top - got) / (top - logits.min(-1))


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


def check_served(variables, config, traffic, served, seed: int):
    """``(record, faults)``: the ``check`` line's numbers and why the run is
    not correct, if it is not (the rule: module constants above)."""
    regrets, margins = served_regrets(variables, config, traffic, served, seed)
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
