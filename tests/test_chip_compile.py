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
"""

import functools
import os

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
