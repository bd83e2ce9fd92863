"""Scratch: the cell's prefill programs alone on the chip, a bucket at a
time: python3 .scratch/prefill_micro.py <expert chunk> [buckets]"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from chipbench import cells
from chipbench.drivers import serve_open_loop as base
from pytorch_distributed_tpu.models import exaone_moe as M

chunk = int(sys.argv[1])
buckets = [int(b) for b in sys.argv[2].split(",")] if len(sys.argv) > 2 else [2048, 4096, 8192, 16384, 32768]
M._EXPERT_CHUNK = chunk
cell = cells.resolve(cells.load_benchmark(), "k-exaone-236b-a23b.serve-mixed-len")
dev = jax.devices()
engine, variables, family = base.build_engine(cell, 12345, dev)
cache = engine.init_cache()
rng = np.random.default_rng(0)
line = {"expert_chunk": chunk, "has_chunk": hasattr(M, "share_rows")}
for b in buckets:
    prompt = rng.integers(0, cell.config["vocab_size"], size=b - 7).astype(np.int32)
    t0 = time.perf_counter()
    cache, tok = engine.prefill(cache, 0, prompt)
    first = time.perf_counter() - t0
    ts = []
    for i in range(3):
        t0 = time.perf_counter()
        cache, tok = engine.prefill(cache, i + 1, prompt)
        ts.append((time.perf_counter() - t0) * 1e3)
    line[f"prefill_{b}_ms"] = min(ts)
    line[f"compile_{b}_s"] = first
    line[f"stats_{b}"] = np.asarray(cache.step_stats).tolist()
    for s in range(4):
        cache = engine.evict(cache, s) if hasattr(engine, "evict") else cache.evict(s)
# a decode step at 16 live slots
for s in range(16):
    cache, tok = engine.prefill(cache, s, rng.integers(0, 19200, size=1000 + 100 * s).astype(np.int32))
last = np.zeros((32,), np.int32); active = np.arange(32) < 16
cache, toks = engine.decode(cache, last, active)
t0 = time.perf_counter()
for _ in range(20):
    cache, toks = engine.decode(cache, last, active)
line["decode_16_live_ms"] = (time.perf_counter() - t0) / 20 * 1e3
line["decode_stats"] = np.asarray(cache.step_stats).tolist()
line["memory"] = {k: v for k, v in dev[0].memory_stats().items() if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "bytes_limit")}
print(json.dumps(line), flush=True)
