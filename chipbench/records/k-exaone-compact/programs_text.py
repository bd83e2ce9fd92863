"""The serving programs of the old cells' two served models, lowered for a
described v5e from the tree this file is run in (``PYTHONPATH``), WITHOUT
their debug information: PR 41 edits ``ops/dropless_experts.py``, which
Xing4.0's programs call, so the source lines in their text move; what the
programs compute is the text without them (and JAX leaves op metadata out of
the compile cache's key).

    PYTHONPATH=<tree> JAX_PLATFORMS=cpu python3 programs_text.py <tree>

prints one line a program: its name and the SHA-256 of its text.
``programs_identical.sh`` runs it in the parent commit and in the change and
compares (``../k-exaone/programs_text.py`` is the form with debug
information, which PR 40 could use because it touched no file they trace)."""

import hashlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies

from pytorch_distributed_tpu.models import GPT2, GPT2Config, Xing4, Xing4Config
from pytorch_distributed_tpu.ops import decode_attention
from pytorch_distributed_tpu.serving import InferenceEngine

root = os.path.realpath(sys.argv[1])
decode_attention._platform = lambda: "tpu"
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = jax.sharding.SingleDeviceSharding(topo.devices[0])


def described(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)


def programs(name, model, n_slots, max_len, buckets):
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=n_slots, max_len=max_len)
    cache = described(jax.eval_shape(engine.init_cache))
    rng = described(jax.eval_shape(lambda: jax.random.key(0)))
    params = described(params)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
    lowered = {"decode": engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=dev),
        jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=dev), rng)}
    for b in buckets:
        lowered[f"prefill/{b}"] = engine._prefill.lower(
            params, cache, jax.ShapeDtypeStruct((1, b), jnp.int32,
                                                sharding=dev), i32, i32, rng)
    for which, low in lowered.items():
        text = low.as_text().replace(root, "<tree>")
        print(f"{name} {which} lines={text.count(chr(10))} "
              f"kernels={text.count('tpu_custom_call')} "
              f"sha256={hashlib.sha256(text.encode()).hexdigest()}",
              flush=True)


# gpt2-125m.serve-chat: 64 slots x 1,024, bf16; buckets 32..512
programs("gpt2-125m", GPT2(GPT2Config(dtype=jnp.bfloat16)), 64, 1024,
         (32, 64, 128, 256, 512))
# xing4.0-29b-a4b.serve-docqa: 48 slots x 8,192, 6 layers, bf16
programs("xing4.0-29b-a4b", Xing4(Xing4Config(
    n_layer=6, first_k_dense_replace=1, dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16)), 48, 8192, (1024, 2048, 4096, 8192))
