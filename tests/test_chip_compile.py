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
long T=8192, ``paged_decode_attention`` at page sizes 16 and 128, and the
slotted cache's lengths-aware read (``ops.decode_attention``) over the
serve-chat cell's whole cache at T=1 (decode) and T=5 (speculative verify),
and the same at GPT-2 large's 20 heads.

One whole program is held the same way: the serving engine's decode step at
the shapes of the ``gpt2-125m.serve-chat`` cell must write the slotted KV
cache where it lies (PERF.md, PR 25) and read it with one kernel a layer
and no fusion over a layer's slab (PR 32) — the compiled module is the
counter of both mechanisms, so they engage always or the test fails.

And one train step: ``Trainer`` under ``FullyShardedDataParallel`` on the
``(1, 4)`` mesh of all four described chips, at GPT-2 large's widths cut
to two layers, must gather parameters and never an activation (PERF.md,
PR 29); on a ``(1, 1)`` mesh the same code must lower to the text it
lowers to with the pin taken out by hand.
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


@pytest.mark.parametrize("T", [1, 5], ids=["decode_T1", "verify_T5"])
@pytest.mark.parametrize("heads", [H, 20], ids=["gpt2_125m", "gpt2_large"])
def test_decode_attention_kernel_compiles_for_v5e(v5e_device, heads, T):
    """The read of the rows a slot holds, over the cell's whole cache
    ``[12, 64, 1024, 768]`` left in HBM, with the row writes beside it;
    and at GPT-2 large's 20 heads of 1,280-wide rows, which take two row
    tiles a token where 12 heads take one."""
    from pytorch_distributed_tpu.ops.decode_attention import cached_attention

    new = ((64, T, heads, D), jnp.bfloat16)
    cache = ((12, 64, 1024, heads * D), jnp.bfloat16)
    _compile(
        lambda q, k, v, kc, vc, offset: cached_attention(
            q, k, v, kc, vc, 3, offset, kernel=True, interpret=False),
        [new, new, new, cache, cache, ((64,), jnp.int32)], v5e_device,
    )


def _step_rng():
    """The shapes of a serving program's last argument, as
    ``InferenceEngine._next_rng`` makes it: (the engine's base key, the
    step's counter), which the program folds into the step's key."""
    return (jax.eval_shape(lambda: jax.random.key(0)),
            jax.ShapeDtypeStruct((), jnp.uint32))


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


def test_decode_program_writes_the_cache_in_place_for_v5e(v5e_device,
                                                         monkeypatch):
    """64 slots x 1024 positions of GPT-2 125M in bf16, the cache donated:
    the step keeps under a tenth of the cache's bytes in temporaries,
    aliases every cache leaf to an output, and moves nothing the size of
    a layer's slab or of the cache except the 2 x 12 in-place row writes:
    no ``copy``, no ``transpose``, no slab sliced out or rebuilt. K and V
    are read by one Mosaic kernel a layer and by no fusion."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices
    from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    # the described chip's program: ``jax.devices()`` here still says CPU
    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")

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
        described(_step_rng()),
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
    # the read of K and V: one lengths-aware kernel a layer over the cache
    # left in HBM, and no fusion but the row writes takes an operand the
    # size of a layer's slab (the dense read sliced one out of the cache)
    assert text.count('custom_call_target="tpu_custom_call"') == cfg.n_layer
    readers = [c for c in fused
               if any(op == "parameter" and n >= slab
                      for _, op, n, _ in computations[c])
               and not any(op == "scatter" for _, op, _, _ in computations[c])]
    assert not readers, readers


# -- Xing4.0 at the serve-docqa cell's shapes: the latent cache ---------------

#: ``memory_stats()["bytes_limit"]`` of one v5e chip (my chip run, PR 33)
V5E_BYTES_LIMIT = 16_909_336_064


def _xing4_engine(v5e_device, monkeypatch, n_layer):
    """The engine of ``xing4.0-29b-a4b.serve-docqa`` (published widths,
    bfloat16, 48 slots x 8,192 positions) over described shapes, cut to
    ``n_layer`` layers (one of them dense)."""
    from pytorch_distributed_tpu.models import Xing4, Xing4Config
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    model = Xing4(Xing4Config(n_layer=n_layer, first_k_dense_replace=1,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=48, max_len=8192)
    cache = described(jax.eval_shape(engine.init_cache))
    rng = described(_step_rng())
    return engine, described(params), cache, rng


def _bytes(tree):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _grouped_kernels(text, layers):
    """The compiled module multiplies the experts' groups with
    ``ops.grouped_matmul``'s kernel alone: three products a trace of the
    experts' function a layer, each under its layer's ``moe/experts``
    scope (what ``decode_moe_ms_step`` and ``prefill_moe_ms_p50`` read)."""
    assert "ragged-dot" not in text and "ragged_dot" not in text
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if "tpu_custom_call" in line and "grouped_matmul" in line]
    assert calls and len(calls) % (3 * layers) == 0, calls
    by_layer = {}
    for op_name in calls:
        layer = re.search(r"layer_(\d+)_\S*?/moe/experts/", op_name)
        assert layer and op_name.endswith("/pallas_call"), op_name
        by_layer[layer.group(1)] = by_layer.get(layer.group(1), 0) + 1
    assert len(by_layer) == layers and len(set(by_layer.values())) == 1
    return calls


def test_latent_decode_program_writes_the_cache_in_place_for_v5e(
        v5e_device, monkeypatch):
    """Six layers (4.79 G parameters), the cache donated: the step keeps
    under a tenth of the 3.0 GB cache in temporaries, aliases every cache
    leaf to an output, moves nothing the size of a layer's slab but the six
    in-place row writes, and reads the rows with six calls of ONE Mosaic
    kernel and with no fusion."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, rng = _xing4_engine(v5e_device, monkeypatch, 6)
    assert _bytes(params) == 9_596_580_368
    slots, max_len, width = 48, 8192, 640
    assert cache.rows.shape == (6, slots, max_len, width)
    compiled = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < _bytes(cache) / 10, memory
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    # the rows and the lengths (the arguments after the weights) alias an
    # output; the step's counts are made anew
    assert aliased_param_indices(text) == [first, first + 1]

    slab = slots * max_len * width
    computations, fused = _computations(text)
    relayouts = [line for body in computations.values()
                 for _, opcode, elements, line in body
                 if opcode in ("copy", "transpose") and elements >= slab]
    assert not relayouts, relayouts[:3]
    big = [(name, opcode, line)
           for c, body in computations.items() if c not in fused
           for name, opcode, elements, line in body if elements >= slab
           and opcode not in ("parameter", "get-tuple-element", "tuple",
                              "bitcast")]
    assert len(big) == 6, [name for name, *_ in big]
    for name, opcode, line in big:
        called = re.search(r"calls=%?([\w.\-]+)", line)
        assert opcode == "fusion" and called, line
        ops = [op for _, op, n, _ in computations[called.group(1)]
               if n >= slab and op not in ("parameter", "bitcast")]
        assert ops == ["scatter"], (name, ops)
    kernels = re.findall(r"[^\n]*latent_attention_read/pallas_call[^\n]*",
                         text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 6
    # one jit of the kernel shared by the layers: the lowered module
    # defines the kernel's function once and calls it six times
    lowered = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).as_text()
    assert len(re.findall(r"func.func private @_kernel_read", lowered)) == 1
    assert len(re.findall(r"call @_kernel_read", lowered)) == 6
    # five layers of experts, every pair held: one trace, three products
    assert len(_grouped_kernels(text, layers=5)) == 15
    # no fusion but the row writes takes the cache or a layer's slab of it
    # (weights this large there are: the 131,072-row embedding and head)
    readers = [c for c in fused
               if any(op == "parameter" and "48,8192,640]" in line
                      for _, op, _, line in computations[c])
               and not any(op == "scatter" for _, op, _, _ in computations[c])]
    assert not readers, readers


def test_latent_prefill_bucket_8192_fits_beside_the_resident_state_for_v5e(
        v5e_device, monkeypatch):
    """The longest prefill bucket. Its temporaries do not depend on the
    depth (a layer's buffers are the next one's), so the program is compiled
    at two layers (one dense, one of experts) and laid beside the six-layer
    cell's resident state: weights, cache, the one-slot block."""
    engine, params, cache, rng = _xing4_engine(v5e_device, monkeypatch, 2)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    compiled = engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=v5e_device),
        i32, i32, rng).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    resident = 9_596_580_368 + 6 * 48 * 8192 * 640 * 2
    assert temp < 3.4e9, temp
    assert resident + temp < V5E_BYTES_LIMIT - 0.5e9, (resident, temp)
    _grouped_kernels(compiled.as_text(), layers=1)


# -- the FSDP train step: parameters are gathered, activations are not -------

@pytest.fixture(scope="module")
def v5e_topology(v5e_device):
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _fsdp_step(topology, mesh_shape, loss_fn, n_layer=2, batch=16, seq=1024):
    """The runner's step program of GPT-2 at 1280 wide / 20 heads under
    FSDP (``min_shard_size=8``, AdamW, policy bf16: the four-chip cell's),
    lowered for described devices: ``(lowered, model config, state)``."""
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.pipeline_exec.metric_ring import MetricRing
    from pytorch_distributed_tpu.trainer import Trainer

    n = int(np.prod(mesh_shape))
    mesh = ptd.init_device_mesh(mesh_shape, ("dp", "fsdp"),
                                devices=topology.devices[:n])
    strategy = FullyShardedDataParallel(mesh, min_shard_size=8)
    cfg = GPT2Config(n_embd=1280, n_layer=n_layer, n_head=20,
                     dtype=jnp.bfloat16, param_dtype=jnp.float32)
    trainer = Trainer(GPT2(cfg), optax.adamw(3e-4), strategy,
                      loss_fn=loss_fn, policy="bf16")
    blank = np.zeros((1, seq), np.int32)
    shapes = jax.eval_shape(
        lambda k: trainer.init(k, (blank, blank)), jax.random.key(0))

    def described(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    replicated = NamedSharding(mesh.jax_mesh, P())
    state = described(shapes, trainer.state_shardings)
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=NamedSharding(mesh.jax_mesh, strategy.batch_pspec()))
    key = jax.eval_shape(lambda: jax.random.key(0))
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=replicated)
    runner = AsyncRunner(trainer)
    step = runner._build(state, (tokens, tokens), rng)
    ring = jax.eval_shape(
        lambda: MetricRing.create(runner._names, runner.drain_every))
    ring = described(ring, jax.tree.map(lambda a: replicated, ring))
    return step.lower(state, ring, (tokens, tokens), rng), cfg, state


#: temporaries of the same two-layer step before the pin, when the program
#: ran as tensor parallelism and gathered the whole batch's logits on every
#: chip (compile result, PR 29, the parent commit)
PARENT_TEMP_BYTES = 4.82e9


@pytest.mark.parametrize("loss", ["lm_loss", "chunked"])
def test_fsdp_step_gathers_parameters_not_activations_for_v5e(
        v5e_topology, loss):
    """The census of ``FullyShardedDataParallel.collective_signature()``
    on the program the TPU compiler makes for four chips: gathers of the
    parameters' own shapes are there; no collective of any family (a ring
    step's collective-permute included) moves anything but a parameter, a
    gradient or a shard of one, bar the embedding's row exchange; the
    gathered logits are gone from the temporaries."""
    from pytorch_distributed_tpu.analysis.ir.hlo import (
        activation_collectives, collective_inventory,
        parameter_element_counts,
    )
    from pytorch_distributed_tpu.trainer import lm_loss, make_chunked_lm_loss

    n_chunks = 8
    loss_fn = (lm_loss if loss == "lm_loss"
               else make_chunked_lm_loss(n_chunks))
    lowered, cfg, state = _fsdp_step(v5e_topology, (1, 4), loss_fn)
    compiled = lowered.compile()
    ops = [op for op in collective_inventory(compiled.as_text())
           if not op.scalar]

    d = cfg.n_embd
    # the head's operand: ``wte`` whole, or the chunked loss's slice of it
    head = ((cfg.vocab_size, d) if loss == "lm_loss"
            else (-(-cfg.vocab_size // n_chunks), d))
    gathered = {op.shape for op in ops if op.family == "all-gather"}
    for shape in [(d, 3 * d), (d, d), (d, 4 * d), (4 * d, d), head]:
        assert shape in gathered, (shape, sorted(gathered))

    shapes = [leaf.shape for leaf in jax.tree.leaves(state.params)]
    counts = parameter_element_counts(shapes + [head], [4])
    moved = activation_collectives(ops, counts)
    # the embedding's row exchange: ``wte`` is sharded on its 1280 columns
    # (50257 rows do not divide by four), so each chip looks up ALL the
    # batch's tokens (their ids gathered: 64 KB) in its 320 columns and
    # one all-to-all each way hands the rows to the chips that own the
    # sequences: 10 MB forward (bf16), 21 MB backward (f32), where a
    # gather of the table is 129 MB. Kept. The chunked loss looks the
    # targets' rows up in the same table (x . W_y) and exchanges those too.
    exchange = [op for op in moved
                if op.family == "all-to-all" or op.dtype == "s32"]
    assert (len([op for op in exchange if op.family == "all-to-all"])
            <= (2 if loss == "lm_loss" else 4))
    rest = [op.describe() for op in moved if op not in exchange]
    assert not rest, rest
    # and nothing at all with the sequence length next to the model width
    # or the vocabulary in its shape, whatever its size
    seq = 1024
    for op in ops:
        if op in exchange or len(op.shape) < 3:
            continue
        assert seq not in op.shape, op.describe()

    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < PARENT_TEMP_BYTES / 2, memory


def test_fsdp_step_on_one_device_lowers_as_without_the_pin(
        v5e_topology, monkeypatch):
    """On a mesh whose batch axes have size 1 the strategy states no
    layout and nothing is emitted: the lowered step is, to the byte, the
    one built with the pin taken out by hand (so the one-chip cells load
    the parent's compile-cache entries)."""
    from pytorch_distributed_tpu.parallel import ShardingStrategy
    from pytorch_distributed_tpu.trainer import lm_loss

    with_pin, _, _ = _fsdp_step(v5e_topology, (1, 1), lm_loss, batch=4)
    monkeypatch.setattr(ShardingStrategy, "activation_pin",
                        lambda self, specs: None)
    without, _, _ = _fsdp_step(v5e_topology, (1, 1), lm_loss, batch=4)
    assert with_pin.as_text() == without.as_text()


def test_fsdp_pin_changes_the_four_chip_step(v5e_topology, monkeypatch):
    """The other side of the test above: on ``(1, 4)`` the pin IS in the
    lowered text (one constraint after the embedding, one after each
    block, the hidden state, the logits, and their cotangents)."""
    from pytorch_distributed_tpu.parallel import ShardingStrategy
    from pytorch_distributed_tpu.trainer import lm_loss

    with_pin, cfg, _ = _fsdp_step(v5e_topology, (1, 4), lm_loss)
    monkeypatch.setattr(ShardingStrategy, "activation_pin",
                        lambda self, specs: None)
    without, _, _ = _fsdp_step(v5e_topology, (1, 4), lm_loss)
    extra = (with_pin.as_text().count("sharding_constraint")
             - without.as_text().count("sharding_constraint"))
    assert extra >= cfg.n_layer + 3, extra


# -- the cache of two depths: K-EXAONE's cell at its real sizes ---------------

def _exaone_engine(v5e_device, monkeypatch):
    """The engine of ``k-exaone-236b-a23b.serve-mixed-len`` (the
    configuration file as it is: published widths, bfloat16, 5 layers, 16 of
    128 experts; the traffic file's slots and positions) over described
    shapes."""
    from chipbench import cells
    from chipbench.families import exaone_moe as family
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    cell = cells.resolve(cells.load_benchmark(),
                         "k-exaone-236b-a23b.serve-mixed-len")
    model = family.build_model(cell.config)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=cell.traffic["n_slots"],
                             max_len=cell.traffic["max_len"])
    cache = described(jax.eval_shape(engine.init_cache))
    rng = described(_step_rng())
    return engine, described(params), cache, rng


def test_windowed_decode_program_reads_what_the_slots_hold_for_v5e(
        v5e_device, monkeypatch):
    """3.71 G parameters, the cache of one full layer (4.29 GB) and four
    rings (67 MB) donated: the step keeps under a hundredth of the cache in
    temporaries, aliases every K/V leaf and the lengths to an output, and
    reads with five calls of ONE Mosaic kernel traced once a depth."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, rng = _exaone_engine(v5e_device, monkeypatch)
    assert _bytes(params) == 7_430_349_312
    slots = cache.k_full.shape[1]
    assert cache.k_full.shape == (1, slots, 32768, 1024)
    assert cache.k_ring.shape == (4, slots, 128, 1024)
    # a KVCache of five layers would be 21.5 GB at 32 slots
    assert _bytes(cache) < 1.02 * 2 * slots * 1024 * 2 * (32768 + 4 * 128)
    compiled = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < _bytes(cache) / 100
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    # k_full, v_full, k_ring, v_ring, lengths; the step's counts are new
    assert aliased_param_indices(text) == list(range(first, first + 5))
    kernels = re.findall(r"[^\n]*gqa_attention_read/pallas_call[^\n]*", text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 5
    # four layers of experts: the first pass in line, the others in a loop
    assert len(_grouped_kernels(text, layers=4)) == 24
    slab = slots * 32768 * 1024
    computations, _ = _computations(text)
    relayouts = [line for body in computations.values()
                 for _, opcode, elements, line in body
                 if opcode in ("copy", "transpose") and elements >= slab]
    assert not relayouts, relayouts[:3]


def test_windowed_prefill_bucket_32768_fits_beside_the_resident_state_for_v5e(
        v5e_device, monkeypatch):
    """The longest bucket: its temporaries beside the weights and the
    cache leave a gigabyte of the chip free, the full layer attends by the
    Mosaic kernel (once) and no array of scores has 32,768 x 32,768
    elements."""
    engine, params, cache, rng = _exaone_engine(v5e_device, monkeypatch)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    compiled = engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=v5e_device),
        i32, i32, rng).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    resident = _bytes(params) + _bytes(cache)
    assert resident > 10e9
    assert resident + temp < V5E_BYTES_LIMIT - 1.0e9, (resident, temp)
    text = compiled.as_text()
    kernels = re.findall(r"[^\n]*gqa_attention_prefill/pallas_call[^\n]*",
                         text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 1
    _grouped_kernels(text, layers=4)
    computations, _ = _computations(text)
    # (the cache's full layer has that many elements itself, in bfloat16)
    assert not [line for body in computations.values()
                for _, _, elements, line in body
                if elements >= 32768 * 32768 and "= f32[" in line]


def _kimi_engine(v5e_device, monkeypatch):
    """The engine of ``kimi-linear-48b-a3b.serve-long-answer`` (the
    configuration file as it is: published widths, bfloat16, layers 1-8, 64
    of 256 experts; the traffic file's slots and positions) over described
    shapes."""
    from chipbench import cells
    from chipbench.families import kimi_linear as family
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    cell = cells.resolve(cells.load_benchmark(),
                         "kimi-linear-48b-a3b.serve-long-answer")
    model = family.build_model(cell.config)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=cell.traffic["n_slots"],
                             max_len=cell.traffic["max_len"])
    cache = described(jax.eval_shape(engine.init_cache))
    rng = described(_step_rng())
    return engine, described(params), cache, rng


def test_hybrid_decode_program_rewrites_the_states_where_they_lie_for_v5e(
        v5e_device, monkeypatch):
    """3.77 G parameters (the issue's arithmetic), six float32 states of
    268 MB and two layers of latent rows donated: every leaf of the cache
    but the step's counts is aliased to an output, the step's temporaries
    are under a fiftieth of the cache (no second copy of a state), the MLA
    layers read with two calls of the latent cache's Mosaic kernel, and a
    KDA layer passes over its state in two fusions (the reduction over k,
    then the update with the output's reduction), never more."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, rng = _kimi_engine(v5e_device, monkeypatch)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 3_772_368_832
    slots = cache.n_slots
    assert {s.shape for s in cache.state} == {(slots, 32, 128, 128)}
    assert {t.shape for t in cache.tail} == {(slots, 3, 12288)}
    assert cache.latent.rows.shape == (2, slots, 6144, 640)
    assert cache.slot_state_bytes() == 25_608_192
    compiled = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < _bytes(cache) / 50
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    leaves = len(jax.tree_util.tree_leaves(cache))
    # 6 states, 6 tails, rows, lengths and the latent cache's (unused)
    # counts pass through; the step's own counts are new
    assert leaves == 16
    assert len(aliased_param_indices(text)) == 15
    assert set(aliased_param_indices(text)) <= set(
        range(first, first + leaves))
    kernels = re.findall(r"[^\n]*latent_attention_read/pallas_call[^\n]*",
                         text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 2
    assert len(_grouped_kernels(text, layers=7)) == 42
    # every read of a state is one of two fusions a layer
    reads = [line for line in text.splitlines()
             if " fusion(" in line and "%cache_state_" in line]
    assert len(reads) == 12 and all("pdt.kda.decode" in r for r in reads)
    computations, _ = _computations(text)
    state = slots * 32 * 128 * 128
    assert not [line for body in computations.values()
                for _, opcode, elements, line in body
                if opcode in ("copy", "transpose") and elements >= state]


def test_hybrid_prefill_bucket_4096_fits_beside_the_resident_state_for_v5e(
        v5e_device, monkeypatch):
    """The traffic's longest bucket: its temporaries (the chunked scan's a
    chunk at a time, the MLA layers' blocks of scores, 16,384 expert rows)
    beside 11.2 GB of weights and cache leave three gigabytes free, and the
    scan over chunks is there once a KDA layer."""
    engine, params, cache, rng = _kimi_engine(v5e_device, monkeypatch)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    compiled = engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=v5e_device),
        i32, i32, rng).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    resident = _bytes(params) + _bytes(cache)
    assert 11.1e9 < resident < 11.4e9
    assert resident + temp < V5E_BYTES_LIMIT - 3.0e9, (resident, temp)
    text = compiled.as_text()
    loops = set(re.findall(
        r"layer_(\d)_attn/pdt\.kda\.prefill/[^\"]*while", text))
    assert loops == {"0", "1", "2", "4", "5", "6"}
    _grouped_kernels(text, layers=7)


# -- K and V heads of unequal width: MiMo-V2.5's cell at its real sizes -------

def _mimo_engine(v5e_device, monkeypatch):
    """The engine of ``mimo-v2.5.serve-code-agent`` (the configuration file
    as it is: published widths, bfloat16, 7 layers, 16 of 256 experts; the
    traffic file's slots and positions) over described shapes."""
    from chipbench import cells
    from chipbench.families import mimo_v2 as family
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    cell = cells.resolve(cells.load_benchmark(), "mimo-v2.5.serve-code-agent")
    model = family.build_model(cell.config)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(model, params, n_slots=cell.traffic["n_slots"],
                             max_len=cell.traffic["max_len"])
    cache = described(jax.eval_shape(engine.init_cache))
    rng = described(_step_rng())
    return engine, described(params), cache, rng


def test_uneven_decode_program_reads_what_the_slots_hold_for_v5e(
        v5e_device, monkeypatch):
    """3.43 G parameters, two full layers of 768 | 512-wide rows (5.03 GB at
    40 slots) and five rings of 1,536 | 1,024 (131 MB) donated: the step
    keeps under a hundredth of the cache in temporaries, aliases every K/V
    leaf and the lengths to an output, and reads with seven calls of ONE
    Mosaic kernel traced once a depth and sink."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, rng = _mimo_engine(v5e_device, monkeypatch)
    assert _bytes(params) == 6_872_497_408
    slots = cache.k_full.shape[1]
    assert cache.k_full.shape == (2, slots, 24576, 768)
    assert cache.v_full.shape == (2, slots, 24576, 512)
    assert cache.k_ring.shape == (5, slots, 128, 1536)
    assert cache.v_ring.shape == (5, slots, 128, 1024)
    # held as KVCache holds rows, seven layers of 5,120 B would be 35 GB
    assert _bytes(cache) < 1.001 * slots * (2 * 24576 * 2560 + 5 * 128 * 5120)
    compiled = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < _bytes(cache) / 100
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    assert aliased_param_indices(text) == list(range(first, first + 5))
    kernels = re.findall(r"[^\n]*gqa_attention_read/pallas_call[^\n]*", text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 7
    slab = slots * 24576 * 512
    computations, _ = _computations(text)
    relayouts = [line for body in computations.values()
                 for _, opcode, elements, line in body
                 if opcode in ("copy", "transpose") and elements >= slab]
    assert not relayouts, relayouts[:3]


def test_uneven_prefill_bucket_24576_fits_beside_the_resident_state_for_v5e(
        v5e_device, monkeypatch):
    """The longest bucket at the slots the traffic file states: its
    temporaries beside the weights and the cache leave a gigabyte of the
    chip free, both full layers attend by the Mosaic kernel at 16 query
    heads a K/V head and no array of scores has 24,576 x 24,576 elements."""
    engine, params, cache, rng = _mimo_engine(v5e_device, monkeypatch)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    compiled = engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, 24576), jnp.int32, sharding=v5e_device),
        i32, i32, rng).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    resident = _bytes(params) + _bytes(cache)
    assert 12.0e9 < resident < 12.1e9
    assert resident + temp < V5E_BYTES_LIMIT - 1.0e9, (resident, temp)
    text = compiled.as_text()
    kernels = re.findall(r"[^\n]*gqa_attention_prefill/pallas_call[^\n]*",
                         text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 2
    _grouped_kernels(text, layers=6)
    computations, _ = _computations(text)
    assert not [line for body in computations.values()
                for _, _, elements, line in body
                if elements >= 24576 * 24576 and "= f32[" in line]



# -- the experts' grouped products: one kernel, lowered once a shape ----------

#: (the engine of a cell, the prefill bucket lowered beside the decode
#: program, the traces of the experts' function the decode and the prefill
#: program hold: a holder of a share multiplies its first pass in line and
#: the later ones in a loop's body, and JAX lowers a jitted function called
#: from both places twice (it rewrites an in-line call's jaxpr when it
#: prunes a program's unused inputs and not one inside a loop, so its cache
#: of lowered functions sees two), copying the ONE lowered kernel into each.
#: A bucket of several chunks has both calls inside the loop over chunks,
#: whose bound is a value since PR 51 (a ``while``, which nothing prunes):
#: one trace)
_GROUPED = {
    "xing4": (functools.partial(_xing4_engine, n_layer=2), 2048, (1, 1)),
    "exaone": (_exaone_engine, 8192, (2, 1)),
    "kimi": (_kimi_engine, 4096, (2, 2)),
    "mimo": (_mimo_engine, 8192, (2, 1)),
}


def _mosaic_lowerings(monkeypatch):
    """The calls of Pallas's Mosaic lowering from here on, as a list that
    grows: what a process pays a program whether its compile cache is warm
    or not."""
    from jax._src.pallas.mosaic import pallas_call_registration as reg

    calls = []
    lower = reg.lowering.lower_jaxpr_to_module

    def counted(ctx, grid_mapping, jaxpr, **kwargs):
        calls.append(str(jaxpr.debug_info.func_src_info))  # function at file
        return lower(ctx, grid_mapping, jaxpr, **kwargs)

    monkeypatch.setattr(reg.lowering, "lower_jaxpr_to_module", counted)
    return calls


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("family", sorted(_GROUPED))
def test_grouped_products_lower_one_kernel_a_shape_for_v5e(
        v5e_device, monkeypatch, family, program):
    """Every grouped product of every layer of a serving program is
    ``ops.grouped_matmul``'s kernel, never ``ragged_dot``, and the kernel
    is LOWERED (Pallas to a Mosaic module: what a process pays with a warm
    compile cache too) once a distinct ``(m, K, N)``, two a program,
    whatever the layers: the lowered text holds each shape's
    ``tpu_custom_call`` once a trace of the experts' function, called from
    every layer, where a bare ``pallas_call`` would be one a call site (24
    in cell 6's programs). Counts, not times: this fails here when a later
    edit lets the call sites multiply, not on the chip as a set-up 5 s
    longer (PR 47)."""
    build, bucket, traces = _GROUPED[family]
    traces = traces[program == "prefill"]
    engine, params, cache, rng = build(v5e_device, monkeypatch)
    lowerings = _mosaic_lowerings(monkeypatch)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    if program == "decode":
        slots = engine.n_slots
        lowered = engine._decode.lower(
            params, cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device),
            rng)
    else:
        lowered = engine._prefill.lower(
            params, cache,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=v5e_device),
            i32, i32, rng)
    text = lowered.as_text()
    assert "ragged_dot" not in text
    # operands (schedule, rows, matrices) and result of each call of it
    names = re.findall(r'kernel_name = "grouped_matmul"[^\n]*(tensor<\d+x\d+x'
                       r'\w+>, tensor<\d+x\d+x\d+x\w+>\) -> tensor<[\dx]+\w+>)',
                       text)
    shapes = sorted(set(names))
    # [m, d] x [E, d, F] (gate and up alike) and [m, F] x [E, F, d]
    assert len(shapes) == 2, shapes
    assert [names.count(s) for s in shapes] == [traces, traces], names
    ours = [n for n in lowerings if "ops/grouped_matmul.py" in n]
    assert len(ours) == len(shapes), lowerings


# -- a paged latent pool: sarvam-105b's cell at its real sizes ----------------

def _sarvam_engine(v5e_device, monkeypatch):
    """The engine of ``sarvam-105b.serve-doc-sessions`` (the configuration
    file as it is: published widths, bfloat16, 5 layers, 32 of 128 experts;
    the traffic file's slots, pages and buckets) over described shapes."""
    from chipbench import cells
    from chipbench.families import sarvam_mla as family
    from pytorch_distributed_tpu.ops import decode_attention
    from pytorch_distributed_tpu.serving import InferenceEngine

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    cell = cells.resolve(cells.load_benchmark(),
                         "sarvam-105b.serve-doc-sessions")
    traffic = cell.traffic
    model = family.build_model(cell.config)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_device), tree)

    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    engine = InferenceEngine(
        model, params, n_slots=traffic["n_slots"], max_len=traffic["max_len"],
        cache_kind="paged", page_size=traffic["page_size"],
        n_pages=traffic["n_pages"], tail_len=traffic["tail_len"],
        prefill_buckets=traffic["prefill_buckets"])
    cache = described(jax.eval_shape(engine.init_cache))
    return engine, described(params), cache, described(_step_rng())


def test_paged_latent_decode_program_reads_through_the_tables_for_v5e(
        v5e_device, monkeypatch):
    """4.54 G parameters beside 3,584 pages of 128 x 640 in five layers
    (2.94 GB) donated: the step keeps under a twentieth of the pool in
    temporaries, aliases the pool to an output, and reads with five calls
    of ONE Mosaic kernel (``latent_paged_read``)."""
    from pytorch_distributed_tpu.analysis.ir.hlo import aliased_param_indices

    engine, params, cache, rng = _sarvam_engine(v5e_device, monkeypatch)
    assert abs(_bytes(params) - 9.07e9) < 0.01e9
    assert cache.rows.shape == (5, 3584, 128, 640)
    assert cache.block_tables.shape == (24, 256)
    slots = engine.n_slots
    compiled = engine._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_device),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e_device), rng,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < _bytes(cache) / 20
    text = compiled.as_text()
    first = len(jax.tree_util.tree_leaves(params))
    assert first in aliased_param_indices(text)          # the pool's rows
    kernels = re.findall(r"[^\n]*latent_paged_read/pallas_call[^\n]*", text)
    assert len([k for k in kernels if "tpu_custom_call" in k]) == 5
    assert all("mla/" in k and "read_paged" in k for k in kernels)
    assert len(_grouped_kernels(text, layers=4)) == 24
    # no copy of the pool, nor of a layer of it
    computations, _ = _computations(text)
    layer = 3584 * 128 * 640
    assert not [line for body in computations.values()
                for _, opcode, elements, line in body
                if opcode in ("copy", "transpose") and elements >= layer]


@pytest.mark.parametrize("bucket,scope,room", [
    (32768, "prefill", None), (1024, "tail", 2.5e9)])
def test_paged_latent_prefill_buckets_fit_beside_the_pool_for_v5e(
        v5e_device, monkeypatch, bucket, scope, room):
    """The cold prompt's longest bucket and the tail's: each COMPILES for
    the described chip beside 12.01 GB of weights and pool (the TPU compiler
    refuses a program that does not fit), the cold one with little room to
    spare (64 heads' expanded K, V and Q of 32,768 positions go four heads
    at a time for it), and each runs its attention under its own scope:
    the Pallas prefill kernel in the first, no kernel in the second."""
    engine, params, cache, rng = _sarvam_engine(v5e_device, monkeypatch)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_device)
    compiled = engine._prefill.lower(
        params, cache,
        jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=v5e_device),
        i32, i32, i32, rng).compile()
    resident = _bytes(params) + _bytes(cache)
    assert 12.00e9 < resident < 12.02e9
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "gqa_attention_prefill" in line]
    if scope == "prefill":
        assert len(kernels) == 5 * 16 and all("mla/" in k and "/prefill/"
                                              in k for k in kernels)
        assert "mla/layer_0_attn/tail" not in text
    else:
        assert not kernels and "mla/layer_0_attn/tail" in text
        assert "/prefill/" not in text
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert resident + temp < V5E_BYTES_LIMIT - room, (resident, temp)
    _grouped_kernels(text, layers=4)
