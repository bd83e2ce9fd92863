"""The main path's Pallas kernels compile for the chip — without the chip.

The TPU compiler is installed in the sandbox and compiles for a topology
that is DESCRIBED, not attached (on-chip-measurement guide, section 2):
every case lowers one kernel at a real width with ``interpret=False`` for
one device of a ``v5e:2x2`` and asserts Mosaic accepted it (the compiled
module holds a ``tpu_custom_call``). Interpret mode — what every other
kernel test here runs — cannot see a misaligned block, too much VMEM or an
unsupported op; this can. Nothing executes: a pass is a compile result,
never a chip run.

Shapes are GPT-2 125M's (H=12, D=64, bf16): the flash forward and
forward+backward in both kernel families — the grid-pruned static-causal
one, and the positional one ring attention hops through
(``q_pos``/``kv_pos``; different ``pallas_call``s) — at T=1024 and at one
long T=8192, and ``paged_decode_attention`` at page sizes 16 and 128.

One whole program is held the same way: the serving engine's decode step at
the shapes of the ``gpt2-125m.serve-chat`` cell must write the slotted KV
cache where it lies (PERF.md, PR 25) — the compiled module is the counter
of that mechanism, so it engages always or the test fails.
"""

import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp

H, D = 12, 64


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a described v5e:2x2, or skip where the installed
    stack cannot describe it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # compiles for a described device can be written to the persistent
    # cache but never read back without the chip; conftest turns it off
    assert not jax.config.jax_enable_compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, device):
    args = [jax.ShapeDtypeStruct(s, d, sharding=device) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled module"


def _flash(q, k, v, *pos, causal, grad):
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q_pos, kv_pos = pos if pos else (None, None)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=causal, q_pos=q_pos,
                               kv_pos=kv_pos, interpret=False)

    if not grad:
        return attend(q, k, v)
    return jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("positions", [False, True],
                         ids=["causal_pruned", "ring_positions"])
@pytest.mark.parametrize("B,T", [(8, 1024), (1, 8192)],
                         ids=["T1024", "T8192"])
def test_flash_attention_compiles_for_v5e(v5e_device, B, T, positions, grad):
    qkv = [((B, T, H, D), jnp.bfloat16)] * 3
    pos = [((T,), jnp.int32)] * 2 if positions else []
    _compile(
        functools.partial(_flash, causal=not positions, grad=grad),
        qkv + pos, v5e_device,
    )


def test_flash_attention_noncausal_compiles_for_v5e(v5e_device):
    _compile(
        functools.partial(_flash, causal=False, grad=False),
        [((8, 1024, H, D), jnp.bfloat16)] * 3, v5e_device,
    )


@pytest.mark.parametrize("page_size,n_pages", [(16, 512), (128, 64)],
                         ids=["page16", "page128"])
def test_paged_decode_attention_compiles_for_v5e(v5e_device, page_size,
                                                 n_pages):
    from pytorch_distributed_tpu.ops.paged_attention import (
        paged_decode_attention,
    )

    slots, max_pages = 8, 1024 // page_size
    pool = ((n_pages, page_size, H, D), jnp.bfloat16)
    _compile(
        functools.partial(paged_decode_attention, interpret=False),
        [((slots, 1, H, D), jnp.bfloat16), pool, pool,
         ((slots, max_pages), jnp.int32), ((slots,), jnp.int32)],
        v5e_device,
    )


def _computations(hlo_text):
    """``{computation: [(name, opcode, elements, line), ...]}`` of a
    compiled module's text, and the names of the computations that are
    bodies of fusions."""
    fused = set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", hlo_text))
    found, body = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
            body = found.setdefault(head.group(1), []) if head else None
            continue
        inst = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
            line)
        if inst and body is not None:
            name, dims, opcode = inst.groups()
            elements = 1
            for d in filter(None, dims.split(",")):
                elements *= int(d)
            body.append((name, opcode, elements, line))
    return found, fused


def test_decode_program_writes_the_cache_in_place_for_v5e(v5e_device):
    """64 slots x 1024 positions of GPT-2 125M in bf16, the cache donated:
    the step keeps under a tenth of the cache's bytes in temporaries,
    aliases every cache leaf to an output, and moves nothing the size of
    a layer's slab or of the cache except the 2 x 12 in-place row writes:
    no ``copy``, no ``transpose``, no slab sliced out or rebuilt."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices
    from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_tpu.serving import InferenceEngine

    slots, max_len = 64, 1024
    model = GPT2(GPT2Config(dtype=jnp.bfloat16, param_dtype=jnp.float32))
    cfg = model.cfg

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=slots, max_len=max_len)
    cache = described(jax.eval_shape(engine.init_cache))
    compiled = engine._decode.lower(
        described(params), cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device),
        described(jax.eval_shape(lambda: jax.random.key(0))),
    ).compile()

    slab = slots * max_len * cfg.n_embd
    leaves = jax.tree_util.tree_leaves(cache)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < cache_bytes / 10, memory
    # k, v and lengths (the arguments after the weights) each alias an
    # output: the donation took
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    assert aliased_param_indices(text) == list(
        range(first, first + len(leaves)))

    computations, fused = _computations(text)
    relayouts = [line for body in computations.values()
                 for _, opcode, elements, line in body
                 if opcode in ("copy", "transpose") and elements >= slab]
    assert not relayouts, relayouts[:3]
    # what the step materialises at a slab's size or more, outside fusions
    big = [(name, opcode, line)
           for c, body in computations.items() if c not in fused
           for name, opcode, elements, line in body if elements >= slab
           and opcode not in ("parameter", "get-tuple-element", "tuple",
                              "bitcast")]
    assert len(big) == 2 * cfg.n_layer, [name for name, *_ in big]
    for name, opcode, line in big:
        called = re.search(r"calls=%?([\w.\-]+)", line)
        assert opcode == "fusion" and called, line
        # a row write into the whole (aliased) cache and nothing else big
        ops = [op for _, op, n, _ in computations[called.group(1)]
               if n >= slab and op not in ("parameter", "bitcast")]
        assert ops == ["scatter"], (name, ops)
