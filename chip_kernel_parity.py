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
Run it BEFORE a cell, after any change to the kernel:

    chiprun --chips 1 -- python3 chip_kernel_parity.py

One JSON line a case, then ``{"ok": ...}``; exit 1 where a case fails or
the backend is not a TPU. The times are whole-token times of the
attention alone (every layer's write and read), host clock around 20 calls.
"""

import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

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


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"{device.platform}: the "
                          "kernel's arithmetic exists only on a TPU"}))
        return 1
    ok = True
    for n, (case, T, H, L) in enumerate(CASES):
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
