"""What the tests of ``models/exaone_moe.py``'s block, of its ``mimo_v2``
configuration and of ``models/kimi_linear.py`` share about a prefill whose
loops over chunks end at the prompt's last real token: the same model's
prefill at sizes where those loops are real, once as it is and once with
every loop run to the bucket's end (what it was before the bound followed
the prompt's length), and the comparison of the two.

Sizes: a bucket of 32 in tokenwise chunks of 8 (four) and expert chunks of
16 (two), query blocks of 4 in a window layer's band (eight) and of 8 in a
full layer's walk (four). The program's own sizes are 2,048 / 4,096 and 256
/ 1,024; they are read when a program is traced, so they are set small
around the tracing alone (``programs``).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.models import exaone_moe, kimi_linear
from pytorch_distributed_tpu.ops import dropless_experts, gqa_attention
from pytorch_distributed_tpu.serving import InferenceEngine

BUCKET = 32
CHUNK = 8
#: real tokens of a prompt in the bucket of 32
CASES = pytest.mark.parametrize("n_real", [1, 8, 9, 31, 32], ids=[
    "one_token", "one_chunk", "one_chunk_and_one", "all_but_one", "whole"])


def small_sizes(patch):
    for module in (exaone_moe, kimi_linear):
        patch.setattr(module, "_TOKEN_CHUNK", CHUNK)
        patch.setattr(module, "_EXPERT_CHUNK", 2 * CHUNK)
    patch.setattr(dropless_experts, "_ROW_TILE", 8)
    patch.setattr(gqa_attention, "_WINDOW_QUERY_BLOCK", 4)
    patch.setattr(gqa_attention, "_QUERY_BLOCK", 8)


@functools.lru_cache(maxsize=None)
def programs(model):
    """``(engine, bounded, static)``: an ``InferenceEngine`` of two slots
    whose buckets are 32 and 64, and the engine's prefill forward
    (``(variables, tokens [1, 32], one-slot cache) -> (logits, block)``)
    compiled twice: as it is, and with ``map_upto`` running every entry
    whatever the bound. All three traced here, at the small sizes."""
    whole = gqa_attention.map_upto

    def prefill(variables, tokens, block):
        return model.apply(variables, tokens, kv_cache=block,
                           position_offset=None)

    with pytest.MonkeyPatch.context() as patch:
        small_sizes(patch)
        variables = jax.jit(model.init)(jax.random.key(0),
                                        jnp.zeros((1, 8), jnp.int32))
        engine = InferenceEngine(model, variables, n_slots=2, max_len=64,
                                 prefill_buckets=(BUCKET,))
        engine.prefill(engine.init_cache(), 0, np.ones((3,), np.int32))
        args = (variables, jnp.zeros((1, BUCKET), jnp.int32),
                engine.init_cache().one_slot(BUCKET, 1))
        bounded = jax.jit(prefill).lower(*args).compile()
        patch.setattr(gqa_attention, "map_upto",
                      lambda fn, xs, upto=None: whole(fn, xs))
        static = jax.jit(prefill).lower(*args).compile()
    return engine, bounded, static


def prompt_of(n_real):
    return np.asarray(jax.random.randint(jax.random.key(40 + n_real),
                                         (n_real,), 1, 200), np.int32)


def check_a_prefill_ends_at_the_last_real_token(model, n_real):
    """The prompt's prefill against the same prefill run to the bucket's
    end: the logits at the last real position and whatever of the cache a
    real token wrote, bit for bit; everything finite, past the last real
    token too; the engine's first token the same, from ONE executable
    whatever the length. Returns the bounded prefill's block."""
    engine, bounded, static = programs(model)
    variables = engine.params
    prompt = prompt_of(n_real)
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :n_real] = prompt
    slot = engine.init_cache().one_slot(BUCKET, n_real)
    logits, block = bounded(variables, jnp.asarray(padded), slot)
    want_logits, want = static(variables, jnp.asarray(padded), slot)
    assert logits.shape == want_logits.shape == (1, 1, model.cfg.vocab_size)
    assert np.array_equal(logits, want_logits)
    # (the counts are not compared: a chunk that is not run counts nothing)
    leaves, want_leaves = (jax.tree_util.tree_leaves(
        c.replace(step_stats=None)) for c in (block, want))
    for got, ref in zip(leaves, want_leaves):
        got, ref = np.asarray(got), np.asarray(ref)
        assert np.isfinite(got).all() and got.shape == ref.shape
        if got.ndim == 4 and got.shape[2] == BUCKET:    # a row a position
            got, ref = got[:, :, :n_real], ref[:, :, :n_real]
        # a ring's rows and a state are real tokens' whatever the padding
        assert np.array_equal(got, ref)
    _, tok = engine.prefill(engine.init_cache(), 1, prompt)
    assert tok == int(np.argmax(np.asarray(want_logits[0, 0])))
    assert engine._prefill._cache_size() == 1
    return block
