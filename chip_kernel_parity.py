"""The decode-attention kernel against the dense read, ON THE CHIP.

A kernel whose interpret-mode parity holds on the CPU can still be wrong
on the chip (PERF.md, PR 31): Mosaic's arithmetic, its tiling and its
copies exist only there. This runs ``ops.decode_attention``'s two reads
of a sequence's earlier rows in one process on the TPU, on the same random
cache at the shapes of the ``gpt2-125m.serve-chat`` cell (``[12, 64, 1024,
768]`` bf16, every position past a slot's length filled with large stale
values), and holds both to a float32 reference over the rows as stored, at
the tolerance ``tests/test_decode_attention.py`` holds the dense read to.
Two more cases at GPT-2 large's widths (20 heads, 1,280-wide rows, 4
layers of them): a token's query rows take two 16-row tiles there.
Then ``ops.latent_attention``'s two absorbed reads the same way at the
shapes of the ``xing4.0-29b-a4b.serve-docqa`` cell (``[6, 48, 8192, 640]``
bf16 latent rows, 32 heads): a token with most slots idle, every slot full,
and T = 5 at random offsets, held to a float32 reference that EXPANDS K and
V from the rows as stored (no absorption). Run it BEFORE a cell, after any
change to a kernel (``slotted`` or ``latent`` alone runs that half):

    chiprun --chips 1 -- python3 chip_kernel_parity.py [slotted|latent|gqa [prefill|share]|gqa_uneven|kda|grouped [sweep]|latent_paged]

``latent_paged`` (alone; PR 54) is ``ops.latent_paged_attention`` at the
``sarvam-105b.serve-doc-sessions`` cell's shapes (``latent_paged_cases``).

``gqa_uneven`` (alone; PR 49) is ``ops.gqa_attention`` at the
``mimo-v2.5.serve-code-agent`` cell's shapes (``gqa_uneven_cases``): K heads
of 192 beside V heads of 128, 64 query heads on 4 K/V heads over full layers
``[2, 40, 24576, 768 | 512]`` and on 8 over rings ``[5, 40, 128, 1536 |
1024]`` with and without a sink, rings wrapped, slots of no row and of one;
the prefill kernel at 16 query heads a K/V head against the T x T softmax
at 2,048 tokens and timed at 24,576, and the window band with a sink.

``grouped`` (alone; PR 48) is ``ops.grouped_matmul``, the experts' grouped
products of the three mixture-of-experts cells, against ``ragged_dot`` at
their real shapes in both regimes (``grouped_cases``; ``grouped sweep``
also times other tiles than the kernel takes by itself).

``kda`` (alone) is the gated delta rule of the
``kimi-linear-48b-a3b.serve-long-answer`` cell (``kda_cases``): no Pallas
kernel, but two forms of one function whose float32 arithmetic
(``Precision.HIGHEST``, exponentials of sums of logarithms) the TPU's
compiler lowers its own way.

``gqa`` (alone; the default runs the other two) is the cache of two depths
of the ``k-exaone-236b-a23b.serve-mixed-len`` cell: ``ops.gqa_attention``'s
two reads over a full layer (``[1, 32, 32768, 1024]`` bf16, 64 query heads on
8 K/V heads) and over a ring (``[4, 32, 128, 1024]``), rows past a slot's
count stale and large; the prefill's attention in both forms (blockwise in
``jax.numpy``, and the Pallas kernel) against the T x T softmax at 4,096
tokens and timed at 32,768, banded and full; and a share
of the experts (16 of 128 held) through ``dropless_experts``, over the
buffer of held pairs and over every pair sorted at once, against every held
expert applied to every token under its gate or zero (``share_cases``).

One JSON line a case, then ``{"ok": ...}``; exit 1 where a case fails or
the backend is not a TPU. The times are whole-token times of the
attention alone (every layer's write and read), host clock around 20 calls.
"""

import functools
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops import latent_attention
from pytorch_distributed_tpu.ops.decode_attention import cached_attention

S, TMAX, D = 64, 1024, 64
LAYER = 2
RTOL = ATOL = 2e-2          # tests/test_decode_attention.py
#: (offsets, T, heads, layers)
CASES = [("chat", 1, 12, 12), ("full", 1, 12, 12), ("verify", 5, 12, 12),
         ("chat", 1, 20, 4), ("verify", 5, 20, 4)]


def _offsets(case, rng):
    if case == "chat":       # the cell's: 7 of 64 slots hold a request
        live = rng.integers(32, 768, 7)
        return np.concatenate([live, np.zeros(S - 7, np.int64)])
    if case == "full":
        return np.full(S, TMAX - 1)
    return rng.integers(0, TMAX - 5, S)          # verify, T = 5


def _reference(q, k_cache, v_cache, pos):
    """float32 attention over layer LAYER as stored, exact products."""
    hi = jax.lax.Precision.HIGHEST
    H = q.shape[2]
    k = k_cache[LAYER].astype(jnp.float32).reshape(S, TMAX, H, D)
    v = v_cache[LAYER].astype(jnp.float32).reshape(S, TMAX, H, D)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k,
                        precision=hi) / np.sqrt(D)
    visible = jnp.arange(TMAX)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v,
                      precision=hi)


def _token(kernel):
    """The attention of one decode token: every layer's write and read."""
    def run(q, k_new, v_new, k_cache, v_cache, offset):
        out = jnp.zeros(q.shape, jnp.float32)
        for layer in range(k_cache.shape[0]):
            y, k_cache, v_cache = cached_attention(
                q, k_new, v_new, k_cache, v_cache, layer, offset,
                kernel=kernel)
            out += y
        return out, k_cache, v_cache
    return jax.jit(run, donate_argnums=(3, 4))


# -- the latent cache's read (ops.latent_attention) --------------------------
L_S, L_TMAX, L_LAYERS, L_H = 48, 8192, 6, 32
D_C, D_N, D_R, D_V = 512, 128, 64, 128
L_SCALE = latent_attention.yarn_softmax_scale(D_N + D_R, 64.0, 1.0)
LATENT_CASES = [("docqa", 1), ("full", 1), ("verify", 5)]


def _latent_offsets(case, rng):
    if case == "docqa":      # the cell's: a quarter of the slots hold a request
        live = rng.integers(1024, 7900, 12)
        return np.concatenate([live, np.zeros(L_S - 12, np.int64)])
    if case == "full":
        return np.full(L_S, L_TMAX - 1)
    return rng.integers(0, L_TMAX - 5, L_S)


@jax.jit
def _latent_reference(q, rows, kv_b, pos):
    """float32, K and V expanded from layer LAYER's rows as stored, a slot
    at a time: no absorption."""
    hi = jax.lax.Precision.HIGHEST
    kv_b = kv_b.astype(jnp.float32)

    def one(args):
        q, held, pos = args                     # [T,H,192] [Tmax,640] [T]
        held = held.astype(jnp.float32)
        kv = jnp.einsum("sc,chn->shn", held[:, :D_C], kv_b, precision=hi)
        k = jnp.concatenate([kv[..., :D_N], jnp.broadcast_to(
            held[:, None, D_C:D_C + D_R], (L_TMAX, L_H, D_R))], -1)
        scores = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), k,
                            precision=hi) * L_SCALE
        seen = jnp.arange(L_TMAX)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, kv[..., D_N:], precision=hi)

    return jax.lax.map(one, (q, rows[LAYER], pos))


def _latent_token(kernel):
    def run(q, latent, kv_b, rows, offset):
        out = jnp.zeros(q.shape[:3] + (D_V,), jnp.float32)
        for layer in range(rows.shape[0]):
            y, rows = latent_attention.latent_attention(
                q, latent, kv_b, rows, layer, offset, d_c=D_C, d_n=D_N,
                scale=L_SCALE, kernel=kernel)
            out += y
        return out, rows
    return jax.jit(run, donate_argnums=(3,))


def latent_cases():
    ok = True
    for n, (case, T) in enumerate(LATENT_CASES):
        rng = np.random.default_rng(100 + n)
        offset = jnp.asarray(_latent_offsets(case, rng), jnp.int32)
        kq, kl, kb, kc = jax.random.split(jax.random.key(100 + n), 4)
        q = jax.random.normal(kq, (L_S, T, L_H, D_N + D_R), jnp.bfloat16)
        latent = jax.random.normal(kl, (L_S, T, D_C + D_R), jnp.bfloat16)
        kv_b = (jax.random.normal(kb, (D_C, L_H, D_N + D_V), jnp.float32)
                * D_C ** -0.5).astype(jnp.bfloat16)
        width = latent_attention.row_width(D_C, D_R)
        # large where no query may look (past each slot's new rows), zero
        # in the padding columns as the cache keeps them
        stale = jnp.where(
            jnp.arange(L_TMAX)[None, :, None] < (offset[:, None, None] + T),
            1.0, 30.0)
        pad = (jnp.arange(width) < D_C + D_R)[None, None, :]
        rows0 = (jax.random.normal(kc, (L_LAYERS, L_S, L_TMAX, width),
                                   jnp.bfloat16)
                 * (stale * pad).astype(jnp.bfloat16))
        pos = offset[:, None] + jnp.arange(T)[None]
        read = jax.jit(functools.partial(
            latent_attention.latent_attention, d_c=D_C, d_n=D_N,
            scale=L_SCALE), static_argnums=4, static_argnames=("kernel",))
        dense, rd = read(q, latent, kv_b, rows0, LAYER, offset)
        kern, rk = read(q, latent, kv_b, rows0, LAYER, offset, kernel=True)
        ref = np.asarray(_latent_reference(q, rd, kv_b, pos))
        dense, kern = (np.asarray(a, np.float32) for a in (dense, kern))
        line = {
            "op": "latent", "case": case, "T": T,
            "positions_held": int(offset.sum()),
            "reference_range": float(ref.max() - ref.min()),
            "kernel_vs_reference": float(np.abs(kern - ref).max()),
            "dense_vs_reference": float(np.abs(dense - ref).max()),
            "kernel_vs_dense": float(np.abs(kern - dense).max()),
            "kernel_within_tolerance": bool(
                np.allclose(kern, ref, rtol=RTOL, atol=ATOL)),
            "dense_within_tolerance": bool(
                np.allclose(dense, ref, rtol=RTOL, atol=ATOL)),
            "same_cache_written": bool(jnp.array_equal(rd, rk)),
            "finite": bool(np.isfinite(kern).all()),
        }
        del rd, rk
        for name, kernel in (("dense_token_ms", False),
                             ("kernel_token_ms", True)):
            token = _latent_token(kernel)
            r1 = rows0 + 0
            _, r1 = token(q, latent, kv_b, r1, offset)
            jax.block_until_ready(r1)
            t0 = time.perf_counter()
            for _ in range(20):
                out, r1 = token(q, latent, kv_b, r1, offset)
            jax.block_until_ready(out)
            line[name] = (time.perf_counter() - t0) / 20 * 1e3
            del r1
        ok &= (line["kernel_within_tolerance"] and line["finite"]
               and line["same_cache_written"])
        print(json.dumps(line), flush=True)
    return ok


# -- the cache of two depths: ops.gqa_attention -------------------------------
G_S, G_HQ, G_HKV, G_D = 32, 64, 8, 128
#: (name, layers, depth, rows held a slot)
GQA_CASES = [
    ("full_mixed", 1, 32768, lambda rng: np.where(
        rng.random(G_S) < 0.4, np.exp(rng.uniform(
            np.log(256), np.log(29000), G_S)).astype(np.int64), 0)),
    ("full_full", 1, 32768, lambda rng: np.full(G_S, 32768)),
    ("ring_mixed", 4, 128, lambda rng: np.minimum(
        rng.integers(0, 300, G_S), 128)),
    ("ring_full", 4, 128, lambda rng: np.full(G_S, 128)),
]


def _gqa_reference(q, k, v, layer, n_rows):
    """float32 attention over the rows as stored, a slot at a time (a full
    layer in float32 is 8.6 GB at once)."""
    hi = jax.lax.Precision.HIGHEST
    depth = k.shape[2]

    def slot(args):
        q, k, v, n = args
        keys = k.astype(jnp.float32).reshape(depth, G_HKV, G_D)
        values = v.astype(jnp.float32).reshape(depth, G_HKV, G_D)
        qg = q.astype(jnp.float32).reshape(G_HKV, G_HQ // G_HKV, G_D)
        scores = jnp.einsum("hgd,rhd->hgr", qg, keys,
                            precision=hi) * G_D ** -0.5
        held = jnp.arange(depth) < n
        probs = jnp.where(held, jax.nn.softmax(
            jnp.where(held, scores, -jnp.inf), -1), 0.0)
        return jnp.einsum("hgr,rhd->hgd", probs, values,
                          precision=hi).reshape(G_HQ, G_D)

    return jax.lax.map(slot, (q, k[layer], v[layer], n_rows))


def gqa_cases(only=None):
    """``only``: ``"prefill"`` skips the reads and the expert share,
    ``"share"`` everything else."""
    from pytorch_distributed_tpu.ops import gqa_attention

    if only == "share":
        return share_cases()
    ok = True
    for n, (case, layers, depth, rows_of) in enumerate(
            GQA_CASES if only is None else []):
        rng = np.random.default_rng(200 + n)
        n_rows = jnp.asarray(rows_of(rng), jnp.int32)
        kq, kk, kv = jax.random.split(jax.random.key(200 + n), 3)
        q = jax.random.normal(kq, (G_S, G_HQ, G_D), jnp.bfloat16)
        stale = jnp.where(jnp.arange(depth)[None, :, None]
                          < n_rows[:, None, None], 1.0, 30.0
                          ).astype(jnp.bfloat16)
        k = jax.random.normal(kk, (layers, G_S, depth, G_HKV * G_D),
                              jnp.bfloat16) * stale
        v = jax.random.normal(kv, (layers, G_S, depth, G_HKV * G_D),
                              jnp.bfloat16) * stale
        layer = layers - 1
        read = jax.jit(gqa_attention.cached_read,
                       static_argnames=("kernel",))
        live = np.asarray(n_rows) > 0
        ref = np.asarray(jax.jit(_gqa_reference, static_argnums=3)(
            q, k, v, layer, n_rows))[live]
        dense = np.asarray(read(q, k, v, layer, n_rows), np.float32)[live]
        kern_all = np.asarray(read(q, k, v, layer, n_rows, kernel=True),
                              np.float32)
        kern = kern_all[live]
        line = {
            "op": "gqa", "case": case, "rows_held": int(n_rows.sum()),
            "kernel_vs_reference": float(np.abs(kern - ref).max()),
            "dense_vs_reference": float(np.abs(dense - ref).max()),
            "kernel_within_tolerance": bool(
                np.allclose(kern, ref, rtol=RTOL, atol=ATOL)),
            "dense_within_tolerance": bool(
                np.allclose(dense, ref, rtol=RTOL, atol=ATOL)),
            "idle_slots_zero": bool(not np.abs(kern_all[~live]).any()),
            "finite": bool(np.isfinite(kern_all).all()),
        }
        for name, kernel in (("dense_read_ms", False),
                             ("kernel_read_ms", True)):
            def all_layers(q, k, v, n_rows, kernel=kernel):
                return sum(gqa_attention.cached_read(
                    q, k, v, i, n_rows, kernel=kernel).astype(jnp.float32)
                    for i in range(layers))
            token = jax.jit(all_layers)
            jax.block_until_ready(token(q, k, v, n_rows))
            t0 = time.perf_counter()
            for _ in range(20):
                out = token(q, k, v, n_rows)
            jax.block_until_ready(out)
            line[name] = (time.perf_counter() - t0) / 20 * 1e3
        line["kernel_roofline_pct"] = 100 * (
            layers * int(n_rows.sum()) * 2 * G_HKV * G_D * 2
            / (line["kernel_read_ms"] * 1e-3) / 819e9)
        ok &= (line["kernel_within_tolerance"] and line["finite"]
               and line["idle_slots_zero"])
        print(json.dumps(line), flush=True)
        del k, v

    # the prefill's blockwise attention: numbers at 4,096, times at 32,768
    def plain(q, k, v, window):
        T, G = q.shape[1], q.shape[2] // k.shape[2]
        hi = jax.lax.Precision.HIGHEST
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=hi) \
            * G_D ** -0.5
        s, p = jnp.arange(T)[None, :], jnp.arange(T)[:, None]
        seen = (s <= p) & ((s > p - window) if window else True)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhts,bshd->bthd", probs, v, precision=hi)

    blocks = (gqa_attention._KERNEL_QUERY_BLOCK,
              gqa_attention._KERNEL_KEY_BLOCK)
    variants = [(128, False, blocks), (None, False, blocks),
                (None, True, blocks)]
    if only == "prefill":       # other blocks, for the choice of the two
        variants += [(None, True, b) for b in (
            (256, 512), (128, 1024), (256, 256)) if b != blocks]
    for window, kernel, (bq, bk) in variants:
        gqa_attention._KERNEL_QUERY_BLOCK = bq
        gqa_attention._KERNEL_KEY_BLOCK = bk
        attend = jax.jit(functools.partial(
            gqa_attention.prefill_attention, window=window, kernel=kernel))
        line = {"op": "gqa_prefill", "window": window, "kernel": kernel}
        if kernel:
            line["blocks"] = [bq, bk]
        for T in (4096, 8192, 32768):
            kq, kk, kv = jax.random.split(jax.random.key(T), 3)
            q = jax.random.normal(kq, (1, T, G_HQ, G_D), jnp.bfloat16)
            k = jax.random.normal(kk, (1, T, G_HKV, G_D), jnp.bfloat16)
            v = jax.random.normal(kv, (1, T, G_HKV, G_D), jnp.bfloat16)
            out = jax.block_until_ready(attend(q, k, v))
            if T == 4096:
                ref = np.asarray(jax.jit(plain, static_argnums=3)(
                    q, k, v, window))
                got = np.asarray(out, np.float32)
                line["vs_reference"] = float(np.abs(got - ref).max())
                line["within_tolerance"] = bool(
                    np.allclose(got, ref, rtol=RTOL, atol=ATOL))
                ok &= line["within_tolerance"]
            t0 = time.perf_counter()
            for _ in range(5):
                out = attend(q, k, v)
            jax.block_until_ready(out)
            line[f"T{T}_ms"] = (time.perf_counter() - t0) / 5 * 1e3
        pairs = (32768 * 128 - 128 * 127 // 2 if window
                 else 32768 * 32769 // 2)
        line["T32768_pct_of_bf16_peak"] = 100 * (
            4.0 * pairs * G_HQ * G_D / (line["T32768_ms"] * 1e-3) / 197e12)
        print(json.dumps(line), flush=True)
    gqa_attention._KERNEL_QUERY_BLOCK, gqa_attention._KERNEL_KEY_BLOCK = blocks
    if only is not None:
        return ok
    return ok & share_cases()


def _uneven_reference(q, k, v, layer, n_rows, sink):
    """float32 attention over the rows as stored (K unpacked), a slot at a
    time, the sink one more column of the softmax; ``sink`` None: none."""
    from pytorch_distributed_tpu.ops import gqa_attention

    hi = jax.lax.Precision.HIGHEST
    depth = k.shape[2]
    Hq, D = q.shape[1:]
    Hkv = k.shape[3] // D

    def slot(args):
        q, k, v, n = args
        keys = gqa_attention._unpack_keys(k.astype(jnp.float32), D)
        values = v.astype(jnp.float32).reshape(depth, Hkv, -1)
        qg = q.astype(jnp.float32).reshape(Hkv, Hq // Hkv, D)
        scores = jnp.einsum("hgd,rhd->hgr", qg, keys,
                            precision=hi) * D ** -0.5
        scores = jnp.where(jnp.arange(depth) < n, scores, -jnp.inf)
        if sink is not None:
            scores = jnp.concatenate(
                [scores, sink.reshape(Hkv, -1, 1)], axis=-1)
        probs = jnp.nan_to_num(jax.nn.softmax(scores, -1))[..., :depth]
        return jnp.einsum("hgr,rhd->hgd", probs, values,
                          precision=hi).reshape(Hq, -1)

    return jax.lax.map(slot, (q, k[layer], v[layer], n_rows))


def gqa_uneven_cases():
    """``ops.gqa_attention`` at MiMo-V2.5's widths (module docstring)."""
    from pytorch_distributed_tpu.ops import gqa_attention

    ok = True
    S, Hq, D, Dv = 40, 64, 192, 128

    def some_rows(rng):          # 31 of 40 live, 3k-24k deep, one with a row
        n = np.where(rng.random(S) < 0.78, np.exp(rng.uniform(
            np.log(3000), np.log(24576), S)).astype(np.int64), 0)
        n[:3] = (1, 0, 24576)
        return n

    def ring_rows(rng):          # wrapped (128), young, empty, one row
        n = np.minimum(rng.integers(0, 400, S), 128)
        n[:4] = (1, 0, 128, 77)
        return n

    cases = [("full_mixed", 2, 24576, 4, some_rows, False),
             ("ring_mixed", 5, 128, 8, ring_rows, False),
             ("ring_mixed_sink", 5, 128, 8, ring_rows, True),
             ("ring_wrapped_sink", 5, 128, 8,
              lambda rng: np.full(S, 128), True)]
    for n, (case, layers, depth, Hkv, rows_of, with_sink) in enumerate(cases):
        rng = np.random.default_rng(490 + n)
        n_rows = jnp.asarray(rows_of(rng), jnp.int32)
        kq, kk, kv, ks = jax.random.split(jax.random.key(490 + n), 4)
        q = jax.random.normal(kq, (S, Hq, D), jnp.bfloat16)
        stale = jnp.where(jnp.arange(depth)[None, :, None]
                          < n_rows[:, None, None], 1.0, 30.0
                          ).astype(jnp.bfloat16)
        k = jax.random.normal(kk, (layers, S, depth, Hkv * D),
                              jnp.bfloat16) * stale
        v = jax.random.normal(kv, (layers, S, depth, Hkv * Dv),
                              jnp.bfloat16) * stale
        sink = jax.random.normal(ks, (Hq,)) if with_sink else None
        layer = layers - 1
        read = jax.jit(gqa_attention.cached_read,
                       static_argnames=("kernel",))
        live = np.asarray(n_rows) > 0
        ref = np.asarray(jax.jit(_uneven_reference, static_argnums=3)(
            q, k, v, layer, n_rows, sink))
        dense = np.asarray(read(q, k, v, layer, n_rows, sink=sink),
                           np.float32)
        kern = np.asarray(read(q, k, v, layer, n_rows, sink=sink,
                               kernel=True), np.float32)
        line = {
            "op": "gqa_uneven", "case": case, "rows_held": int(n_rows.sum()),
            "kernel_vs_reference": float(np.abs(kern - ref).max()),
            "dense_vs_reference": float(np.abs(dense - ref).max()),
            "kernel_within_tolerance": bool(
                np.allclose(kern, ref, rtol=RTOL, atol=ATOL)),
            "dense_within_tolerance": bool(
                np.allclose(dense, ref, rtol=RTOL, atol=ATOL)),
            "idle_slots_zero": bool(not np.abs(kern[~live]).any()),
            "finite": bool(np.isfinite(kern).all()),
        }
        if with_sink:        # and the sink is not nothing
            none = np.asarray(read(q, k, v, layer, n_rows, kernel=True),
                              np.float32)
            line["sink_moves"] = float(np.abs(kern - none).max())
            ok &= line["sink_moves"] > 2 * line["kernel_vs_reference"]
        for name, kernel in (("dense_read_ms", False),
                             ("kernel_read_ms", True)):
            def all_layers(q, k, v, n_rows, kernel=kernel):
                return sum(gqa_attention.cached_read(
                    q, k, v, i, n_rows, sink=sink,
                    kernel=kernel).astype(jnp.float32)
                    for i in range(layers))
            line[name] = _timed(jax.jit(all_layers), q, k, v, n_rows,
                                calls=20)
        line["kernel_roofline_pct"] = 100 * (
            layers * int(n_rows.sum()) * Hkv * (D + Dv) * 2
            / (line["kernel_read_ms"] * 1e-3) / 819e9)
        ok &= (line["kernel_within_tolerance"] and line["finite"]
               and line["idle_slots_zero"])
        print(json.dumps(line), flush=True)
        del k, v

    def plain(q, k, v, window, sink):
        T, G = q.shape[1], q.shape[2] // k.shape[2]
        hi = jax.lax.Precision.HIGHEST
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=hi) \
            * D ** -0.5
        s, p = jnp.arange(T)[None, :], jnp.arange(T)[:, None]
        seen = (s <= p) & ((s > p - window) if window else True)
        scores = jnp.where(seen, scores, -jnp.inf)
        if sink is not None:
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                sink[None, :, None, None], scores.shape[:3] + (1,))], -1)
        probs = jax.nn.softmax(scores, -1)[..., :T]
        return jnp.einsum("bhts,bshd->bthd", probs, v, precision=hi)

    blocks = (gqa_attention._KERNEL_QUERY_BLOCK,
              gqa_attention._KERNEL_KEY_BLOCK)
    sink = jax.random.normal(jax.random.key(49), (Hq,))
    # (window, K/V heads, sink, kernel, blocks): the full layers' two forms
    # (and the kernel at 128 positions x 512 keys a step), the band's two
    for window, Hkv, with_sink, kernel, (bq, bk) in (
            (None, 4, False, False, blocks), (None, 4, False, True, blocks),
            (None, 4, False, True, (256, 512)),
            (128, 8, False, False, blocks), (128, 8, True, False, blocks)):
        gqa_attention._KERNEL_QUERY_BLOCK = bq
        gqa_attention._KERNEL_KEY_BLOCK = bk
        b = sink if with_sink else None
        attend = jax.jit(functools.partial(
            gqa_attention.prefill_attention, window=window, kernel=kernel,
            sink=b))
        line = {"op": "gqa_uneven_prefill", "window": window,
                "kv_heads": Hkv, "sink": with_sink, "kernel": kernel}
        if kernel:
            line["blocks"] = [gqa_attention._kernel_query_block(Hq // Hkv),
                              bk]
        for T in (2048, 8192, 24576):
            kq, kk, kv = jax.random.split(jax.random.key(T), 3)
            q = jax.random.normal(kq, (1, T, Hq, D), jnp.bfloat16)
            k = jax.random.normal(kk, (1, T, Hkv, D), jnp.bfloat16)
            v = jax.random.normal(kv, (1, T, Hkv, Dv), jnp.bfloat16)
            out = jax.block_until_ready(attend(q, k, v))
            if T == 2048:
                ref = np.asarray(jax.jit(plain, static_argnums=3)(
                    q, k, v, window, b))
                got = np.asarray(out, np.float32)
                line["vs_reference"] = float(np.abs(got - ref).max())
                line["within_tolerance"] = bool(
                    np.allclose(got, ref, rtol=RTOL, atol=ATOL))
                ok &= line["within_tolerance"]
            line[f"T{T}_ms"] = _timed(attend, q, k, v, calls=5)
        pairs = (24576 * 128 - 128 * 127 // 2 if window
                 else 24576 * 24577 // 2)
        line["T24576_pct_of_bf16_peak"] = 100 * (
            2.0 * pairs * Hq * (D + Dv) / (line["T24576_ms"] * 1e-3)
            / 197e12)
        print(json.dumps(line), flush=True)
    gqa_attention._KERNEL_QUERY_BLOCK, gqa_attention._KERNEL_KEY_BLOCK = blocks
    return ok


def _timed(fn, *args, calls=10):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def share_cases():
    """A holder's share of the experts at the cell's shapes (6,144 wide, 16
    of 128 held, 8 a token): ``dropless_experts`` over the buffer of held
    pairs (``num_experts=128``) and over all ``n * 8`` pairs sorted at once
    against every held expert applied to every token under its gate or
    zero in float32, for a prefill's chunks of 2,048, 4,096 and 8,192 tokens, a
    decode step's 32, and a routing that sends EVERY pair to this holder
    (four buffers full); then three ways a pass's rows can go back to
    their tokens, alone: each token's ``k`` rows gathered and summed (the
    op's), a segment sum over the rows sorted back by token, and a gather a
    token slot in a loop."""
    from pytorch_distributed_tpu.ops.dropless_experts import (
        dropless_experts, held_share, route_sigmoid_topk, share_passes,
        share_rows)

    ok = True
    d, F, E, held, k = 6144, 2048, 128, 16, 8
    ks = jax.random.split(jax.random.key(7), 5)
    router = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.02
    w_gate, w_up = (jax.random.normal(q, (held, d, F), jnp.bfloat16) * 0.02
                    for q in ks[2:4])
    w_down = jax.random.normal(ks[4], (held, F, d), jnp.bfloat16) * 0.02
    weights = (w_gate, w_up, w_down)

    @jax.jit
    def routed(x, bias):
        return route_sigmoid_topk(x, router, bias, k, 2.5)

    @jax.jit
    def every_expert(x, experts, gates, w_gate, w_up, w_down):
        n = x.shape[0]
        dense_gates = jnp.zeros((n, E)).at[
            jnp.arange(n)[:, None], experts].set(gates)[:, :held]
        hi = jax.lax.Precision.HIGHEST
        x32 = x.astype(jnp.float32)

        def one(acc, e):
            g, u, dn, w = e
            h = jax.nn.silu(jnp.dot(x32, g.astype(jnp.float32),
                                    precision=hi)) * jnp.dot(
                x32, u.astype(jnp.float32), precision=hi)
            return acc + w[:, None] * jnp.dot(h, dn.astype(jnp.float32),
                                              precision=hi), None
        return jax.lax.scan(one, jnp.zeros((n, d)), (
            w_gate, w_up, w_down, dense_gates.T))[0]

    compacted = jax.jit(functools.partial(dropless_experts, num_experts=E))
    whole_sort = jax.jit(dropless_experts)
    for case, n, favoured in (("decode_step", 32, 0), ("chunk", 2048, 0),
                              ("chunk", 4096, 0), ("chunk", 8192, 0),
                              ("every_pair_held", 2048, held)):
        x = jax.random.normal(jax.random.key(n), (n, d), jnp.bfloat16)
        experts, gates = routed(x, jnp.zeros((E,)).at[:favoured].set(10.0))
        own = held_share(experts, gates, 0, held)
        pairs, passes = share_passes(own[0], held, E)
        want = np.asarray(every_expert(x, experts, gates, *weights))
        line = {"op": "expert_share", "case": case, "tokens": n,
                "held_pairs": int(pairs), "of_pairs": n * k,
                "buffer_rows": share_rows(n * k, held, E),
                "passes": int(passes),
                "range": float(want.max() - want.min())}
        forms = [("compacted", compacted)]
        if n <= 2048:       # 8,192 tokens' pairs are 3.2 GB a copy unsorted
            forms.append(("whole_sort", whole_sort))
        for name, form in forms:
            y, hit = form(x, *own, *weights)
            y = np.asarray(y, np.float32)
            line[f"{name}_vs_every_expert_masked"] = float(
                np.abs(y - want).max())
            line[f"{name}_finite"] = bool(np.isfinite(y).all())
            line[f"{name}_experts_hit"] = int(hit)
            line[f"{name}_ms"] = _timed(form, x, *own, *weights)
        # the program's path decides; the whole sort (PR 40's path, which
        # counts on zeros in the rows past ragged_dot's groups) is recorded
        line["within_tolerance"] = line["compacted_finite"] and (
            line["compacted_vs_every_expert_masked"] < 0.02 * line["range"])
        ok &= line["within_tolerance"]
        print(json.dumps(line), flush=True)

    # a pass's rows back to their tokens: out [rows, d] sorted by expert,
    # under the gates, into [n, d] float32
    for n in (2048, 4096, 8192):
        x = jax.random.normal(jax.random.key(n), (n, d), jnp.bfloat16)
        experts, gates = held_share(*routed(x, jnp.zeros((E,))), 0, held)
        rows = share_rows(n * k, held, E)
        order = jnp.argsort(experts.reshape(-1), stable=True)[:rows]
        live = jnp.arange(rows) < share_passes(experts, held, E)[0]
        out = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)

        def row_of_pair(order, live):       # ``rows``: the pair has none
            return jnp.full((n * k,), rows, jnp.int32).at[
                jnp.where(live, order, n * k)].set(
                    jnp.arange(rows, dtype=jnp.int32), mode="drop")

        @jax.jit
        def k_rows_a_token(out, order, live, gates):    # the op's own
            at = row_of_pair(order, live)
            back = jnp.take(out, at, axis=0, mode="fill", fill_value=0)
            return jnp.einsum("nkd,nk->nd", back.reshape(n, k, d).astype(
                jnp.float32), gates)

        @jax.jit
        def sorted_segment_sum(out, order, live, gates):
            pair = jnp.where(live, order, n * k)
            back = jnp.argsort(pair)            # pair order is token order
            pair = pair[back]
            weighted = jnp.where(
                (pair < n * k)[:, None], out[back].astype(jnp.float32)
                * gates.reshape(-1)[jnp.minimum(pair, n * k - 1)][:, None],
                0.0)
            return jax.ops.segment_sum(weighted, pair // k, num_segments=n,
                                       indices_are_sorted=True)

        @jax.jit
        def gather_per_slot(out, order, live, gates):
            at = row_of_pair(order, live)
            at, by_slot = jax.lax.sort((at.reshape(n, k), gates),
                                       dimension=-1, num_keys=1)
            padded = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])

            def slot(s, y):
                row = jax.lax.dynamic_index_in_dim(at, s, 1, keepdims=False)
                g = jax.lax.dynamic_index_in_dim(by_slot, s, 1,
                                                 keepdims=False)
                return y + padded[row].astype(jnp.float32) * jnp.where(
                    row < rows, g, 0.0)[:, None]
            return jax.lax.fori_loop(0, (at < rows).sum(-1).max(), slot,
                                     jnp.zeros((n, d), jnp.float32))

        want = np.asarray(k_rows_a_token(out, order, live, gates))
        line = {"op": "expert_share_combine", "tokens": n, "rows": rows}
        for form in (k_rows_a_token, sorted_segment_sum, gather_per_slot):
            name = form.__name__
            line[f"{name}_vs_k_rows_a_token"] = float(np.abs(
                np.asarray(form(out, order, live, gates)) - want).max())
            line[f"{name}_ms"] = _timed(form, out, order, live, gates)
            ok &= line[f"{name}_vs_k_rows_a_token"] < 1e-3
        print(json.dumps(line), flush=True)
    return ok


def kda_cases():
    """``ops.kda``'s two forms of the gated delta rule on the chip at the
    shapes of the ``kimi-linear-48b-a3b.serve-long-answer`` cell (32 heads
    of 128, bfloat16 q / k / v, float32 state): the chunked form (chunks of
    64) against the recurrent form over a prompt of 4,096 tokens, values and
    final state, with the decay drawn as the model's initialiser draws it,
    with ``a`` near 1 and with ``a`` near 0; a bucket of 4,096 holding 2,500
    real tokens against the recurrent form over those alone; then both
    forms timed, a layer: a prompt of 4,096 (chunked) and a decode step of
    128 slots (recurrent, the states donated)."""
    from pytorch_distributed_tpu.ops import kda

    H, d, T = 32, 128, 4096
    ok = True

    def operands(seed, decay, B=1, T=T):
        ks = jax.random.split(jax.random.key(seed), 7)
        bf = jnp.bfloat16
        q = kda._unit(jax.random.normal(ks[0], (B, T, H, d), bf).astype(
            jnp.float32)) * d ** -0.5
        k = kda._unit(jax.random.normal(ks[1], (B, T, H, d), bf).astype(
            jnp.float32))
        v = jax.random.normal(ks[2], (B, T, H, d), bf).astype(jnp.float32)
        if decay == "model":        # A_log, dt_bias as models.kimi_linear
            rate = jax.random.uniform(ks[3], (H, 1), minval=1.0, maxval=16.0)
            dt = jnp.exp(jax.random.uniform(ks[4], (H, d)) * np.log(100.0)
                         + np.log(1e-3))
            log_a = -rate * jax.nn.softplus(
                dt + jnp.log(-jnp.expm1(-dt))
                + 0.05 * jax.random.normal(ks[5], (B, T, H, d)))
        else:
            lo, hi = decay
            log_a = jnp.log(jax.random.uniform(ks[3], (B, T, H, d),
                                               minval=lo, maxval=hi))
        beta = jax.nn.sigmoid(jax.random.normal(ks[6], (B, T, H)))
        return q, k, v, log_a, beta, jnp.zeros((B, H, d, d), jnp.float32)

    recurrent = jax.jit(kda.gated_delta_rule)
    chunked = jax.jit(functools.partial(kda.gated_delta_rule,
                                        chunk=kda.CHUNK))
    masked = jax.jit(lambda *x, valid: kda.gated_delta_rule(
        *x, chunk=kda.CHUNK, valid=valid))
    for n, (name, decay, real) in enumerate([
            ("model", "model", T), ("a_near_1", (0.99, 0.99999), T),
            ("a_near_0", (1e-4, 1e-2), T), ("pad", "model", 2500)]):
        x = operands(n, decay)
        if real < T:
            o, state = masked(*x, valid=jnp.arange(T)[None] < real)
            want_o, want_state = recurrent(*(a[:, :real] for a in x[:5]),
                                           x[5])
            o = o[:, :real]
        else:
            o, state = chunked(*x)
            want_o, want_state = recurrent(*x)
        o, state, want_o, want_state = (np.asarray(a) for a in (
            o, state, want_o, want_state))
        line = {
            "case": f"kda_{name}", "T": T, "real": real, "heads": H, "d": d,
            "o_range": float(want_o.max() - want_o.min()),
            "state_range": float(want_state.max() - want_state.min()),
            "chunked_vs_recurrent_o": float(np.abs(o - want_o).max()),
            "chunked_vs_recurrent_state": float(
                np.abs(state - want_state).max()),
            "finite": bool(np.isfinite(o).all() and np.isfinite(state).all()),
        }
        # float32 at Precision.HIGHEST on both sides: orders of sums differ
        line["within_tolerance"] = bool(
            line["chunked_vs_recurrent_o"] <= 1e-4 * max(line["o_range"], 1)
            and line["chunked_vs_recurrent_state"]
            <= 1e-4 * max(line["state_range"], 1))
        ok &= line["within_tolerance"] and line["finite"]
        print(json.dumps(line), flush=True)
    x = operands(9, "model")
    line = {"case": "kda_times", "prefill_4096_chunked_ms_layer":
            _timed(chunked, *x, calls=5)}
    S = 128
    q, k, v, log_a, beta, _ = operands(10, "model", B=S, T=1)
    step = jax.jit(kda.gated_delta_rule, donate_argnums=5)
    state = jnp.ones((S, H, d, d), jnp.float32)
    _, state = step(q, k, v, log_a, beta, state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(20):
        out, state = step(q, k, v, log_a, beta, state)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / 20 * 1e3
    line["decode_128_slots_recurrent_ms_layer"] = ms
    line["decode_state_gb_s"] = 2 * S * H * d * d * 4 / ms / 1e6
    print(json.dumps(line), flush=True)
    return ok


#: (cell, regime, rows m, held rows, E, d, F): the grouped products of the
#: three serving configurations at their real shapes
GROUPED = [
    ("k-exaone", "decode", 128, 40, 16, 6144, 2048),
    ("k-exaone", "prefill", 8192, 4250, 16, 6144, 2048),
    ("xing4.0", "decode", 192, 192, 64, 3584, 1024),
    ("xing4.0", "prefill", 32768, 32768, 64, 3584, 1024),
    ("kimi-linear", "decode", 512, 200, 64, 2304, 1024),
    ("kimi-linear", "prefill", 16384, 8192, 64, 2304, 1024),
]
#: other tiles than ``grouped_matmul`` takes by itself, timed by ``grouped
#: sweep`` only: (rows of a tile, bytes of a matrix block)
SWEEP = [(256, 8 << 20), (128, 4 << 20), (128, 16 << 20), (64, 8 << 20)]


def grouped_cases(sweep=None):
    """``ops.grouped_matmul`` against ``jax.lax.ragged_dot`` (float32 sums,
    cast back) at the cells' shapes: both products of an expert (``[m, d] x
    [E, d, F]`` and ``[m, F] x [E, F, d]``), group sizes drawn as a routing
    draws them (multinomial over the experts; a decode step's held rows are
    few and leave experts empty), NaN planted in the operand rows past the
    last group. Each is held to the float32 product of the same bfloat16
    operands: the kernel may lie no further from it than ``ragged_dot``
    does, and its rows past the groups are exactly 0."""
    from pytorch_distributed_tpu.ops import grouped_matmul as gm

    @jax.jit
    def ragged(rows, w, sizes):
        return jax.lax.ragged_dot(
            rows, w, sizes, preferred_element_type=jnp.float32
        ).astype(rows.dtype)

    @jax.jit
    def exact(rows, w, sizes):
        return jax.lax.ragged_dot(
            rows.astype(jnp.float32), w.astype(jnp.float32), sizes,
            precision=jax.lax.Precision.HIGHEST)

    kernel = jax.jit(gm.grouped_matmul)
    ok = True
    for n, (cell, regime, m, held, E, d, F) in enumerate(GROUPED):
        rng = np.random.default_rng(n)
        sizes = jnp.asarray(rng.multinomial(held, np.ones(E) / E), jnp.int32)
        hit = int((np.asarray(sizes) > 0).sum())
        for product, (K, N) in (("gate", (d, F)), ("down", (F, d))):
            kr, kw = jax.random.split(jax.random.key(2 * n + (K < N)))
            rows = jax.random.normal(kr, (m, K), jnp.bfloat16)
            w = jax.random.normal(kw, (E, K, N), jnp.bfloat16) * K ** -0.5
            planted = rows.at[held:].set(jnp.nan)
            ref = np.asarray(exact(rows, w, sizes))[:held]
            out = np.asarray(kernel(planted, w, sizes), np.float32)
            old = np.asarray(ragged(rows, w, sizes), np.float32)[:held]
            tiles = (gm.row_tile(m),) + gm._column_tiles(K, N, 2)
            line = {
                "case": f"grouped/{cell}/{regime}/{product}",
                "m": m, "K": K, "N": N, "E": E, "held_rows": held,
                "groups_hit": hit, "tiles": list(tiles),
                "kernel_vs_float32": float(np.abs(out[:held] - ref).max()),
                "ragged_dot_vs_float32": float(np.abs(old - ref).max()),
                "kernel_vs_ragged_dot": float(np.abs(out[:held] - old).max()),
                "rows_past_groups_zero": bool((out[held:] == 0).all()),
                "finite": bool(np.isfinite(out).all()),
                "kernel_ms": _timed(kernel, planted, w, sizes, calls=20),
                "ragged_dot_ms": _timed(ragged, rows, w, sizes, calls=20),
            }
            line["kernel_matrix_gb_s"] = (hit * K * N * 2 / line["kernel_ms"]
                                          / 1e6)
            line["kernel_tflop_s"] = (2 * held * K * N / line["kernel_ms"]
                                      / 1e9)
            line["ok"] = (line["finite"] and line["rows_past_groups_zero"]
                          and line["kernel_vs_float32"]
                          <= line["ragged_dot_vs_float32"] * 1.0001 + 1e-6)
            ok &= line["ok"]
            for tm, block in (SWEEP if sweep == "sweep" else []):
                other = (tm,) + gm._column_tiles(K, N, 2, block)
                if m % tm or other == tiles:
                    continue
                call = functools.partial(gm._grouped_call, tiles=other)
                line["ms_at_" + "x".join(map(str, other))] = _timed(
                    call, gm.grouped_schedule(sizes, m, tm), planted, w,
                    calls=20)
            print(json.dumps(line), flush=True)
    return ok


# -- the paged latent pool: ops.latent_paged_attention ------------------------
P_S, P_H, P_LAYERS, P_PAGES, P_PAGE, P_MAX = 24, 64, 5, 4096, 128, 256


def _expanded_reference(q, rows, kv_b, pos, scale):
    """float32 attention of queries ``q [T, H, 192]`` at positions ``pos
    [T]`` over ``rows [n, 640]`` (a chain's rows in order), K and V EXPANDED
    from them: no absorption, no block, no page."""
    hi = jax.lax.Precision.HIGHEST
    rows = rows.astype(jnp.float32)
    seen = jnp.arange(rows.shape[0])[None, :] <= pos[:, None]
    out = []
    for first in range(0, q.shape[1], 8):    # 575 x 28,735 x 64 scores: 4 GB
        heads = slice(first, first + 8)
        kv = jnp.einsum("sc,chn->shn", rows[:, :D_C],
                        kv_b[:, heads].astype(jnp.float32), precision=hi)
        k = jnp.concatenate([kv[..., :D_N], jnp.broadcast_to(
            rows[:, None, D_C:D_C + D_R], (rows.shape[0], 8, D_R))], -1)
        scores = jnp.einsum("thd,shd->hts", q[:, heads].astype(jnp.float32),
                            k, precision=hi) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", probs, kv[..., D_N:],
                              precision=hi))
    return jnp.concatenate(out, axis=1)


def latent_paged_cases():
    """``ops.latent_paged_attention`` at the ``sarvam-105b.serve-doc-sessions``
    cell's shapes (64 heads, a pool ``[5, 4096, 128, 640]`` bf16, 24 slots of
    256 table entries): the paged read kernel and its dense twin over chains
    of 1, 37 and 256 pages (and idle slots; page ids shuffled, two slots
    sharing a prefix, every row past a chain's length large and stale)
    against the float32 expanded reference; the cold prompt's attention
    against the T x T softmax at 2,048 tokens and timed at 32,768; a tail of
    575 in a bucket of 1,024 behind 28,160 cached rows."""
    from pytorch_distributed_tpu.ops import latent_paged_attention as paged

    ok = True
    scale = latent_attention.yarn_softmax_scale(D_N + D_R, 40.0, 1.0)
    rng = np.random.default_rng(54)
    ids = rng.permutation(np.arange(1, P_PAGES))
    lengths = np.zeros(P_S, np.int64)
    lengths[:9] = [100, 128, 127, 37 * 128 - 1, 37 * 128, 37 * 128 - 64,
                   P_MAX * 128 - 1, P_MAX * 128 - 300, 20000]
    tables = np.zeros((P_S, P_MAX), np.int32)
    at = 0
    for s in range(P_S):
        n = -(-(int(lengths[s]) + 1) // P_PAGE) if lengths[s] else 0
        n = min(n, P_MAX)
        tables[s, :n] = ids[at:at + n]
        at += n
    tables[8, :100] = tables[7, :100]          # a shared prefix
    kq, kl, kb, kc = jax.random.split(jax.random.key(54), 4)
    kv_b = (jax.random.normal(kb, (D_C, P_H, D_N + D_V), jnp.float32)
            * D_C ** -0.5).astype(jnp.bfloat16)
    width = latent_attention.row_width(D_C, D_R)
    pool = jax.random.normal(kc, (P_LAYERS, P_PAGES, P_PAGE, width),
                             jnp.bfloat16)
    pool = pool * (jnp.arange(width) < D_C + D_R).astype(jnp.bfloat16)
    # large where no query may look: past each chain's length, page by page
    for s in range(P_S):
        last = int(lengths[s]) // P_PAGE
        if lengths[s] and last < P_MAX and s != 7:
            keep = (jnp.arange(P_PAGE) <= lengths[s] % P_PAGE)[:, None]
            pool = pool.at[:, tables[s, last]].multiply(
                jnp.where(keep, 1.0, 30.0).astype(jnp.bfloat16))
    q = jax.random.normal(kq, (P_S, 1, P_H, D_N + D_R), jnp.bfloat16)
    latent = jax.random.normal(kl, (P_S, 1, D_C + D_R), jnp.bfloat16)
    offset = jnp.asarray(np.minimum(lengths, P_MAX * P_PAGE - 1), jnp.int32)
    tables_d = jnp.asarray(tables)
    read = jax.jit(functools.partial(
        paged.paged_read, d_c=D_C, d_n=D_N, scale=scale),
        static_argnums=5, static_argnames=("kernel",))
    dense, pd = read(q, latent, kv_b, pool, tables_d, LAYER, offset)
    kern, pk = read(q, latent, kv_b, pool, tables_d, LAYER, offset,
                    kernel=True)
    worst = {"kernel": 0.0, "dense": 0.0}
    within = True
    for s in np.flatnonzero(lengths):
        n = int(offset[s]) + 1
        chain = pd[LAYER][tables_d[s]].reshape(-1, width)[:n]
        ref = np.asarray(_expanded_reference(
            q[s], chain, kv_b, offset[s][None], scale))
        for name, got in (("kernel", kern), ("dense", dense)):
            diff = np.abs(np.asarray(got[s], np.float32) - ref).max()
            worst[name] = max(worst[name], float(diff))
        within &= bool(np.allclose(np.asarray(kern[s], np.float32), ref,
                                   rtol=RTOL, atol=ATOL))
    line = {"op": "latent_paged_read", "slots_live": int((lengths > 0).sum()),
            "positions_held": int(lengths.sum()),
            "kernel_vs_reference": worst["kernel"],
            "dense_vs_reference": worst["dense"],
            "kernel_vs_dense": float(np.abs(
                np.asarray(kern, np.float32)
                - np.asarray(dense, np.float32)).max()),
            "kernel_within_tolerance": within,
            "same_pool_written": bool(jnp.array_equal(pd, pk)),
            "finite": bool(np.isfinite(np.asarray(kern, np.float32)).all())}
    del pd, pk

    def token(kernel):
        def all_layers(q, latent, kv_b, pool, tables, offset):
            total = 0.0          # every layer's read is used: none is dead
            for layer in range(P_LAYERS):
                out, pool = paged.paged_read(
                    q, latent, kv_b, pool, tables, layer, offset, d_c=D_C,
                    d_n=D_N, scale=scale, kernel=kernel)
                total = total + out.astype(jnp.float32)
            return total, pool
        return jax.jit(all_layers, donate_argnums=3)

    for name, kernel in (("dense_token_ms", False), ("kernel_token_ms", True)):
        step = token(kernel)
        p1 = pool + 0
        _, p1 = step(q, latent, kv_b, p1, tables_d, offset)
        jax.block_until_ready(p1)
        t0 = time.perf_counter()
        for _ in range(20):
            out, p1 = step(q, latent, kv_b, p1, tables_d, offset)
        jax.block_until_ready(out)
        line[name] = (time.perf_counter() - t0) / 20 * 1e3
        del p1
    line["bytes_must_read_a_token"] = int(
        P_LAYERS * (lengths[lengths > 0] + 1).sum() * (D_C + D_R) * 2)
    line["kernel_pct_of_hbm_peak"] = 100 * line[
        "bytes_must_read_a_token"] / (line["kernel_token_ms"] * 1e-3) / 819e9
    ok &= (line["kernel_within_tolerance"] and line["finite"]
           and line["same_pool_written"])
    print(json.dumps(line), flush=True)

    # -- a cold prompt's attention ------------------------------------------
    cold = jax.jit(functools.partial(paged.cold_prefill, d_c=D_C, d_n=D_N,
                                     scale=scale))
    for T, check in ((2048, True), (32768, False)):
        k1, k2 = jax.random.split(jax.random.key(T))
        qT = jax.random.normal(k1, (1, T, P_H, D_N + D_R), jnp.bfloat16)
        rows = jax.random.normal(k2, (1, T, width), jnp.bfloat16) * (
            jnp.arange(width) < D_C + D_R).astype(jnp.bfloat16)
        line = {"op": "latent_cold_prefill", "T": T,
                "ms_a_layer": _timed(cold, qT, rows, kv_b, calls=3)}
        line["pct_of_bf16_peak"] = 100 * (
            T * (T + 1) * P_H * (D_N + D_R + D_V)) / (
                line["ms_a_layer"] * 1e-3) / 197e12
        if check:
            got = np.asarray(cold(qT, rows, kv_b)[0], np.float32)
            ref = np.asarray(_expanded_reference(
                qT[0], rows[0], kv_b, jnp.arange(T), scale))
            line.update(
                vs_reference=float(np.abs(got - ref).max()),
                reference_range=float(ref.max() - ref.min()),
                within_tolerance=bool(np.allclose(got, ref, rtol=RTOL,
                                                  atol=ATOL)))
            ok &= line["within_tolerance"]
        print(json.dumps(line), flush=True)
        del qT, rows

    # -- a tail behind cached rows ------------------------------------------
    start, n_new, T = 220 * P_PAGE, 575, 1024
    # pages no slot above holds: plain rows, none of the large stale ones
    chain = jnp.asarray(np.concatenate(
        [ids[at:at + P_MAX - 16], np.zeros(16, np.int64)]).astype(np.int32))
    qT = jax.random.normal(jax.random.key(575), (1, T, P_H, D_N + D_R),
                           jnp.bfloat16)
    tail = jax.jit(functools.partial(paged.tail_prefill, d_c=D_C, d_n=D_N,
                                     scale=scale), static_argnums=4)
    got = np.asarray(tail(qT, kv_b, pool, chain, LAYER, jnp.int32(start),
                          jnp.int32(n_new))[0, :n_new], np.float32)
    rows = pool[LAYER][chain].reshape(-1, width)[:start + n_new]
    ref = np.asarray(_expanded_reference(
        qT[0, :n_new], rows, kv_b, start + jnp.arange(n_new), scale))
    line = {"op": "latent_tail_prefill", "T": T, "n_new": n_new,
            "start": start, "vs_reference": float(np.abs(got - ref).max()),
            "reference_range": float(ref.max() - ref.min()),
            "within_tolerance": bool(np.allclose(got, ref, rtol=RTOL,
                                                 atol=ATOL)),
            "ms_a_layer": _timed(tail, qT, kv_b, pool, chain, LAYER,
                                 jnp.int32(start), jnp.int32(n_new), calls=5)}
    ok &= line["within_tolerance"]
    print(json.dumps(line), flush=True)
    return ok



def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"{device.platform}: the "
                          "kernel's arithmetic exists only on a TPU"}))
        return 1
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which in ("gqa", "gqa_uneven", "kda", "grouped", "latent_paged"):
        ok = {"gqa": gqa_cases, "gqa_uneven": gqa_uneven_cases,
              "kda": kda_cases, "latent_paged": latent_paged_cases,
              "grouped": grouped_cases}[which](*sys.argv[2:3])
        print(json.dumps({"ok": ok, "device": {
            "platform": device.platform, "kind": device.device_kind}}))
        return 0 if ok else 1
    ok = latent_cases() if which in ("latent", "both") else True
    for n, (case, T, H, L) in enumerate(CASES if which != "latent" else []):
        C = H * D
        rng = np.random.default_rng(n)
        offset = jnp.asarray(_offsets(case, rng), jnp.int32)
        key = jax.random.key(n)
        kq, kk, kv, kc, kd = jax.random.split(key, 5)
        q, k_new, v_new = (
            jax.random.normal(k, (S, T, H, D), jnp.bfloat16)
            for k in (kq, kk, kv))
        # large where no query may look: past each slot's new rows
        stale = jnp.where(
            jnp.arange(TMAX)[None, :, None] < (offset[:, None, None] + T),
            1.0, 30.0).astype(jnp.bfloat16)
        k0 = jax.random.normal(kc, (L, S, TMAX, C), jnp.bfloat16) * stale
        v0 = jax.random.normal(kd, (L, S, TMAX, C), jnp.bfloat16) * stale
        pos = offset[:, None] + jnp.arange(T)[None]

        read = jax.jit(cached_attention, static_argnums=5,
                       static_argnames=("kernel",))
        dense, kd1, vd1 = read(q, k_new, v_new, k0, v0, LAYER, offset)
        kern, kk1, vk1 = read(q, k_new, v_new, k0, v0, LAYER, offset,
                              kernel=True)
        ref = np.asarray(_reference(q, kd1, vd1, pos))
        dense, kern = (np.asarray(a, np.float32) for a in (dense, kern))
        line = {
            "case": case, "T": T, "heads": H, "layers": L,
            "positions_held": int(offset.sum()),
            "reference_range": float(ref.max() - ref.min()),
            "kernel_vs_reference": float(np.abs(kern - ref).max()),
            "dense_vs_reference": float(np.abs(dense - ref).max()),
            "kernel_vs_dense": float(np.abs(kern - dense).max()),
            "kernel_within_tolerance": bool(
                np.allclose(kern, ref, rtol=RTOL, atol=ATOL)),
            "dense_within_tolerance": bool(
                np.allclose(dense, ref, rtol=RTOL, atol=ATOL)),
            "same_cache_written": bool(
                jnp.array_equal(kd1, kk1) and jnp.array_equal(vd1, vk1)),
            "finite": bool(np.isfinite(kern).all()),
        }
        del kd1, vd1, kk1, vk1
        for name, kernel in (("dense_token_ms", False),
                             ("kernel_token_ms", True)):
            token = _token(kernel)
            k1, v1 = k0 + 0, v0 + 0
            _, k1, v1 = token(q, k_new, v_new, k1, v1, offset)
            jax.block_until_ready(k1)
            t0 = time.perf_counter()
            for _ in range(20):
                out, k1, v1 = token(q, k_new, v_new, k1, v1, offset)
            jax.block_until_ready(out)
            line[name] = (time.perf_counter() - t0) / 20 * 1e3
            del k1, v1
        ok &= (line["kernel_within_tolerance"] and line["finite"]
               and line["same_cache_written"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": device.platform, "kind": device.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
