"""``ops.grouped_matmul``: the Pallas kernel in the interpreter against
``jax.lax.ragged_dot`` (float32 sums, cast back), which is what it replaces
on the chip and what every other backend keeps. The cells' shapes are
scaled down by whole tiles; what the chip's compiler says of the real ones
is ``tests/test_chip_compile.py``'s, what the chip computes
``chip_kernel_parity.py grouped``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops import decode_attention
from pytorch_distributed_tpu.ops import dropless_experts as de
from pytorch_distributed_tpu.ops import grouped_matmul as gm


def _multinomial(held, E, seed=0):
    return np.random.default_rng(seed).multinomial(held, np.ones(E) / E)


def _few_rows(E, seed=0):
    return np.random.default_rng(seed).integers(2, 4, size=E)


def _one(E, at, rows):
    sizes = np.zeros(E, np.int64)
    sizes[at] = rows
    return sizes


#: name -> (m, K, N, sizes, tiles or None for the kernel's own)
CASES = {
    # k-exaone: d 6144, F 2048, 16 held experts; decode 128 rows, prefill 8,192
    "exaone_decode_gate": (128, 768, 256, _multinomial(40, 16), None),
    "exaone_decode_down": (128, 256, 768, _multinomial(40, 16, 1), None),
    "exaone_prefill": (1024, 768, 256, _multinomial(530, 16, 2), None),
    # xing4.0: d 3584, F 1024, every one of 64 experts held; 192 rows a step
    "xing4_decode_192_rows": (192, 512, 256, _multinomial(192, 64, 3), None),
    "xing4_prefill_every_row_held": (1024, 512, 256,
                                     _multinomial(1024, 64, 4), None),
    # kimi-linear: d 2304 = 18 lane tiles, F 1024, 64 held; 512 rows a step
    "kimi_decode_tiny_groups": (512, 256, 128, _few_rows(64, 5), None),
    "kimi_prefill": (2048, 256, 128, _multinomial(1024, 64, 6), None),
    "one_group_of_many": (256, 128, 128, _one(8, 3, 200), None),
    "all_rows_in_the_last_group": (256, 128, 128, _one(8, 7, 256), None),
    "tile_straddles_40_groups": (128, 128, 128, _few_rows(40, 7), None),
    "rows_past_the_groups": (512, 128, 256, _multinomial(130, 4, 8), None),
    "no_group_has_a_row": (128, 128, 128, np.zeros(4, np.int64), None),
    "k_in_tiles": (256, 512, 128, _multinomial(200, 8, 9), (64, 128, 128)),
    "rows_of_16": (48, 128, 128, _multinomial(30, 4, 10), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_ragged_dot(case):
    """Every held row is ``ragged_dot``'s to a bfloat16 rounding of the
    same float32 sum; every row past the last group is exactly 0, though
    the operand rows there hold NaN."""
    m, K, N, sizes, tiles = CASES[case]
    E, held = len(sizes), int(sizes.sum())
    kr, kw = jax.random.split(jax.random.key(sorted(CASES).index(case)))
    rows = jax.random.normal(kr, (m, K), jnp.bfloat16)
    w = jax.random.normal(kw, (E, K, N), jnp.bfloat16) * K ** -0.5
    sizes = jnp.asarray(sizes, jnp.int32)
    planted = rows.at[held:].set(jnp.nan)
    if tiles is None:
        out = gm.grouped_matmul(planted, w, sizes, interpret=True)
    else:
        out = gm._grouped_call(gm.grouped_schedule(sizes, m, tiles[0]),
                               planted, w, tiles=tiles, interpret=True)
    ref = jax.lax.ragged_dot(rows, w, sizes,
                             preferred_element_type=jnp.float32)
    assert out.shape == (m, N) and out.dtype == jnp.bfloat16
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    assert (out[held:] == 0).all()
    ref = np.asarray(ref)[:held]
    # one bfloat16 rounding (2^-9 of the value) of sums that differ in the
    # order of their float32 additions
    np.testing.assert_allclose(out[:held], ref, rtol=2 ** -7, atol=1e-3)
    if held:
        assert np.abs(out[:held]).max() > 0.1


@pytest.mark.parametrize("m,tm", [(128, 128), (192, 64), (512, 128),
                                  (8192, 128), (48, 16)])
def test_row_tile_follows_from_the_rows(m, tm):
    assert gm.row_tile(m) == tm


def test_column_tiles_take_k_whole_where_a_block_fits():
    """The cells' six products: ``K`` whole, the widest whole-lane-tile
    divisor of ``N`` under 8 MiB a block; a ``K`` too long is cut."""
    assert gm._column_tiles(6144, 2048, 2) == (6144, 512)
    assert gm._column_tiles(2048, 6144, 2) == (2048, 2048)
    assert gm._column_tiles(3584, 1024, 2) == (3584, 1024)
    assert gm._column_tiles(1024, 3584, 2) == (1024, 3584)
    assert gm._column_tiles(2304, 1024, 2) == (2304, 1024)
    assert gm._column_tiles(1024, 2304, 2) == (1024, 2304)
    assert gm._column_tiles(65536, 256, 2) == (32768, 128)


def test_schedule_visits_every_tile_and_every_group_in_row_order():
    """Groups of 100, 0, 30, 0 rows over 256 rows in tiles of 64: group 0
    owns tiles 0-1, group 2 rows 100-129 (tiles 1-2), the rows past them
    tiles 2-3, which read the last real visit's rows and matrix again."""
    s = gm.grouped_schedule(jnp.asarray([100, 0, 30, 0], jnp.int32), 256, 64)
    v = int(s.visits)
    assert v == 6
    assert s.group[:v].tolist() == [0, 0, 2, 2, 4, 4]
    assert s.tile[:v].tolist() == [0, 1, 1, 2, 2, 3]
    assert s.rows_tile[:v].tolist() == [0, 1, 1, 2, 2, 2]
    assert s.matrix[:v].tolist() == [0, 0, 2, 2, 2, 2]
    assert s.bounds.tolist() == [0, 100, 100, 130, 130, 256]
    assert s.group.shape == (256 // 64 + 4,)


def test_kernel_takes_only_what_mosaic_can_tile(monkeypatch):
    rows = jax.ShapeDtypeStruct((128, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16)
    assert not gm.kernel_groups(rows, w)                # the CPU
    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    assert gm.kernel_groups(rows, w)
    S = jax.ShapeDtypeStruct
    assert not gm.kernel_groups(S((120, 256), jnp.bfloat16), w)
    assert not gm.kernel_groups(S((128, 200), jnp.bfloat16),
                                S((4, 200, 128), jnp.bfloat16))
    assert not gm.kernel_groups(rows, S((4, 256, 96), jnp.bfloat16))
    assert not gm.kernel_groups(S((128, 256), jnp.float32), w)


def _experts(n, d, F, E, count, k, seed, crowd=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (n, d), jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(key, (count, d, F), jnp.bfloat16)
                    * d ** -0.5 for key in ks[1:3])
    w_down = jax.random.normal(ks[3], (count, F, d), jnp.bfloat16) * F ** -0.5
    router = jax.random.normal(ks[4], (d, E), jnp.float32)
    experts, gates = de.held_share(*de.route_sigmoid_topk(
        x, router, jnp.where(crowd & (jnp.arange(E) < count), 10.0, 0.0), k,
        2.5), 0, count)
    return x, experts, gates, w_gate, w_up, w_down


@pytest.mark.parametrize("n,E,count,k", [
    (64, 128, 16, 8),       # a holder of 16 of 128: the buffer of held pairs
    (32, 8, 8, 2),          # a holder of every expert: every pair sorted
    (256, 32, 2, 8),        # every token on both held: the buffer goes round
], ids=["holds_16_of_128", "holds_every_expert", "spills"])
def test_dropless_experts_through_the_kernel_is_its_ragged_dot_path(
        monkeypatch, n, E, count, k):
    """The whole layer: route, sort, three grouped products, un-sort, sum
    under the gates. With the kernel taken (a TPU named, Pallas
    interpreted) the program holds the kernel and no ``ragged_dot``, and
    gives what the ``ragged_dot`` path gives on the same operands, a second
    pass over the buffer (``spills``) included."""
    args = _experts(n, 128, 256, E, count, k, seed=n, crowd=n == 256)
    layer = lambda *a: de.dropless_experts(*a, num_experts=E)  # noqa: E731
    plain, plain_hit = jax.jit(layer)(*args)
    assert "ragged_dot" in str(jax.make_jaxpr(layer)(*args))

    monkeypatch.setattr(decode_attention, "_platform", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        text = str(jax.make_jaxpr(layer)(*args))
        assert "pallas_call" in text and "ragged_dot" not in text
        ours, hit = jax.jit(layer)(*args)
    assert int(hit) == int(plain_hit)
    if de.share_rows(n * k, count, E) < n * k:
        held, passes = de.share_passes(args[1], count, E)
        assert (int(passes) > 1) == (n == 256)
    np.testing.assert_allclose(np.asarray(ours, np.float32),
                               np.asarray(plain, np.float32),
                               rtol=2 ** -6, atol=2 ** -6)
    assert np.abs(np.asarray(plain, np.float32)).max() > 0.1
