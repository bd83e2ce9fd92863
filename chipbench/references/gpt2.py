"""Plain reference of GPT-2 (Radford et al. 2019; the layer equations of
``openai-community/gpt2``): pre-LN blocks, learned positions, GELU(tanh),
causal softmax attention, head tied to the token embedding. Straightforward
``jax.numpy`` in float32 with the matmul precision set to ``highest``; no
cache, no kernel, no batching tricks. It reads the program's parameter tree
(``wte``, ``wpe``, ``h_<i>/{ln_1,attn/{c_attn,c_proj},ln_2,mlp/{c_fc,c_proj}}``,
``ln_f``) so the same seeded weights serve both sides.

Departure from the published model: none in the forward. Weights are random
(the program's initialiser), not the released checkpoint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _block(x, blk, causal, n_head: int, eps: float):
    B, T, C = x.shape
    D = C // n_head
    h = _layer_norm(x, blk["ln_1"], eps)
    q, k, v = jnp.split(_dense(h, blk["attn"]["c_attn"]), 3, axis=-1)
    q, k, v = (a.reshape(B, T, n_head, D) for a in (q, k, v))
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(D)
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, C)
    x = x + _dense(a, blk["attn"]["c_proj"])
    h = _layer_norm(x, blk["ln_2"], eps)
    h = jax.nn.gelu(_dense(h, blk["mlp"]["c_fc"]), approximate=True)
    return x + _dense(h, blk["mlp"]["c_proj"])


def forward(params, tokens, *, n_layer: int, n_head: int, eps: float):
    """``tokens [B, T]`` -> logits ``[B, T, V]`` in float32. The blocks are
    one ``lax.scan`` over their stacked parameters, each a
    ``jax.checkpoint``: that changes no value, compiles one block and not
    ``n_layer``, and lets the gradient keep one block's activations at a
    time."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        T = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][:T]
        causal = jnp.tril(jnp.ones((T, T), bool))
        blocks = jax.tree_util.tree_map(
            lambda *a: jnp.stack(a),
            *(params[f"h_{i}"] for i in range(n_layer)))
        block = jax.checkpoint(
            lambda x, blk: (_block(x, blk, causal, n_head, eps), None))
        x, _ = jax.lax.scan(block, x, blocks)
        x = _layer_norm(x, params["ln_f"], eps)
        return x @ params["wte"].T


def _sequence_loss(params, tok, tgt, **sizes):
    logp = jax.nn.log_softmax(forward(params, tok[None], **sizes)[0])
    return -jnp.take_along_axis(logp, tgt[:, None], axis=-1).mean()


def loss_and_grad(params, tokens, targets, **sizes):
    """Mean next-token cross-entropy over ``[B, T]`` and its gradient by
    the parameters, summed one sequence at a time so that the ``[T, V]``
    float32 logits of one sequence are all that is alive."""
    def add_one(total, pair):
        one = jax.value_and_grad(_sequence_loss)(params, *pair, **sizes)
        return jax.tree_util.tree_map(jnp.add, total, one), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add_one, zero, (tokens, targets))
    return jax.tree_util.tree_map(lambda a: a / tokens.shape[0], total)
