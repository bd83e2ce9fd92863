"""Scratch: the expert share's pieces alone on the chip (not committed)."""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from pytorch_distributed_tpu.ops.dropless_experts import (
    dropless_experts, held_share, route_sigmoid_topk, share_rows)

d, F, E, held, k = 6144, 2048, 128, 16, 8
ks = jax.random.split(jax.random.key(7), 6)
router = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.02
w_gate, w_up = (jax.random.normal(q, (held, d, F), jnp.bfloat16) * 0.02 for q in ks[2:4])
w_down = jax.random.normal(ks[4], (held, F, d), jnp.bfloat16) * 0.02


def timed(f, *a, reps=10):
    out = jax.block_until_ready(f(*a))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def routed(x):
    return held_share(*route_sigmoid_topk(x, router, jnp.zeros((E,)), k, 2.5), 0, held)


for n in (32, 2048, 4096, 8192):
    x = jax.random.normal(jax.random.key(n), (n, d), jnp.bfloat16)
    ex, g = jax.jit(routed)(x)
    line = {"what": "share", "n": n, "cap": share_rows(n * k, held, E),
            "held": int((ex < held).sum())}
    if n <= 2048:
        line["whole_sort_ms"] = timed(jax.jit(lambda x, e, g: dropless_experts(x, e, g, w_gate, w_up, w_down)), x, ex, g)
    line["compacted_ms"] = timed(jax.jit(lambda x, e, g: dropless_experts(x, e, g, w_gate, w_up, w_down, num_experts=E)), x, ex, g)
    line["route_ms"] = timed(jax.jit(routed), x)
    print(json.dumps(line), flush=True)

    if n < 2048:
        continue
    # the pieces of a pass
    cap = share_rows(n * k, held, E)
    flat = ex.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    pairs = order[:cap]
    nheld = int((ex < held).sum())
    live = jnp.arange(cap) < nheld
    sizes = jnp.zeros((held,), jnp.int32).at[flat].add(1)
    rows = x[pairs // k]
    out = jax.random.normal(ks[5], (cap, d), jnp.bfloat16)
    gflat = g.reshape(-1)
    line = {"what": "pieces", "n": n, "cap": cap}
    line["argsort_pairs_ms"] = timed(jax.jit(lambda f: jnp.argsort(f, stable=True)), flat)
    line["argsort_cap_ms"] = timed(jax.jit(lambda f: jnp.argsort(f, stable=True)), flat[:cap])
    line["sizes_scatter_ms"] = timed(jax.jit(lambda f: jnp.zeros((held,), jnp.int32).at[f].add(1)), flat)
    line["gather_rows_ms"] = timed(jax.jit(lambda x, p: x[p // k]), x, pairs)
    for name, dt in (("f32_then_cast", jnp.float32), ("bf16_out", jnp.bfloat16)):
        line[f"ragged_up_{name}_ms"] = timed(jax.jit(lambda r, s: jax.lax.ragged_dot(r, w_up, s, preferred_element_type=dt).astype(jnp.bfloat16)), rows, sizes)
        hid = jax.random.normal(ks[5], (cap, F), jnp.bfloat16)
        line[f"ragged_down_{name}_ms"] = timed(jax.jit(lambda r, s: jax.lax.ragged_dot(r, w_down, s, preferred_element_type=dt).astype(jnp.bfloat16)), hid, sizes)

    def seg(out, pairs, live):
        r = jnp.where(live[:, None], out.astype(jnp.float32) * gflat[pairs][:, None], 0.0)
        return jax.ops.segment_sum(r, jnp.where(live, pairs // k, n), num_segments=n)

    def unsort_seg(out, pairs, live):
        key = jnp.where(live, pairs, n * k)
        perm = jnp.argsort(key)
        p = key[perm]
        r = jnp.where((p < n * k)[:, None], out[perm].astype(jnp.float32) * gflat[jnp.minimum(p, n * k - 1)][:, None], 0.0)
        return jax.ops.segment_sum(r, p // k, num_segments=n, indices_are_sorted=True)

    def slots(out, pairs, live):
        pos = jnp.full((n * k,), cap, jnp.int32).at[jnp.where(live, pairs, n * k)].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        pos, gs = jax.lax.sort((pos.reshape(n, k), g), dimension=-1, num_keys=1)
        most = (pos < cap).sum(-1).max()
        padded = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])

        def one(s, y):
            at = jax.lax.dynamic_index_in_dim(pos, s, 1, keepdims=False)
            gg = jax.lax.dynamic_index_in_dim(gs, s, 1, keepdims=False)
            return y + padded[at].astype(jnp.float32) * jnp.where(at < cap, gg, 0.0)[:, None]
        return jax.lax.fori_loop(0, most, one, jnp.zeros((n, d), jnp.float32)), most

    def unsort_pairs(out, pairs, live):   # the parent's form over n * k rows
        pos = jnp.full((n * k,), cap, jnp.int32).at[jnp.where(live, pairs, n * k)].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        padded = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])
        return jnp.einsum("nkd,nk->nd", padded[pos].reshape(n, k, d).astype(jnp.float32), g)

    a = jax.jit(seg)(out, pairs, live)
    b = jax.jit(unsort_seg)(out, pairs, live)
    c, most = jax.jit(slots)(out, pairs, live)
    e = jax.jit(unsort_pairs)(out, pairs, live)
    line["combine_agree"] = [float(jnp.abs(a - b).max()), float(jnp.abs(a - c).max()), float(jnp.abs(a - e).max())]
    line["slots_most"] = int(most)
    line["combine_segment_sum_ms"] = timed(jax.jit(seg), out, pairs, live)
    line["combine_unsort_sorted_segment_sum_ms"] = timed(jax.jit(unsort_seg), out, pairs, live)
    line["combine_gather_per_slot_ms"] = timed(jax.jit(slots), out, pairs, live)
    if n <= 4096:
        line["combine_gather_all_pairs_ms"] = timed(jax.jit(unsort_pairs), out, pairs, live)
    print(json.dumps(line), flush=True)
