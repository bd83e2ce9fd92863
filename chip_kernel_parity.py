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

    chiprun --chips 1 -- python3 chip_kernel_parity.py [slotted|latent]

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


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"{device.platform}: the "
                          "kernel's arithmetic exists only on a TPU"}))
        return 1
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
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
