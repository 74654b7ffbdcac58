"""Pallas kernel validation: shape/dtype/variant sweep vs the pure-jnp
oracle (bit-exact within one K block; accumulation-order tolerance across
K blocks), including the pad-to-tile path."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitops import round_up
from repro.core.config import Backend, DaismConfig, Variant
from repro.kernels.daism_matmul import daism_matmul_kernel
from repro.kernels.ops import col_tile, daism_matmul_pallas, row_tile
from repro.kernels.ref import daism_matmul_ref

VARIANTS = [Variant.FLA, Variant.HLA, Variant.PC2, Variant.PC3,
            Variant.PC2_TR, Variant.PC3_TR]

SHAPES = [
    (8, 128, 128),     # exactly one tile
    (16, 128, 256),    # multi-tile N
    (24, 256, 128),    # multi-tile K (accumulation loop)
    (5, 70, 33),       # ragged -> pad path
    (1, 1, 1),         # degenerate
]


def _data(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)
    return a, w


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("variant", VARIANTS)
def test_kernel_matches_oracle(shape, variant):
    m, k, n = shape
    a, w = _data(m, k, n)
    cfg = DaismConfig(variant=variant, backend=Backend.PALLAS)
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    ref = np.asarray(daism_matmul_ref(a, w, variant))
    # the kernel and the reference differ only in f32 summation order
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape", SHAPES)
def test_exact_kernel_matches_matmul(shape):
    m, k, n = shape
    a, w = _data(m, k, n, seed=1)
    cfg = DaismConfig(variant=Variant.EXACT, backend=Backend.PALLAS)
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    ref = np.asarray(a, np.float32) @ np.asarray(w, np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_block_shape_invariance():
    """Different BlockSpec tilings must agree (modulo accumulation order)."""
    a, w = _data(16, 256, 256, seed=2)
    outs = []
    for bm, bk, bn in [(8, 128, 128), (16, 256, 128), (8, 256, 256)]:
        cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS,
                          block_m=bm, block_k=bk, block_n=bn)
        outs.append(np.asarray(daism_matmul_pallas(a, w, cfg)))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,block_m,want", [
    (1, 32, 8), (4, 32, 8), (17, 32, 24), (32, 32, 32), (33, 32, 32),
    (128, 32, 32), (128, 16, 16), (4, 4, 4)])
def test_row_tile_fits_m_under_block_m(m, block_m, want):
    assert row_tile(m, block_m) == want


@pytest.mark.parametrize("n,bm,want", [
    (24576, 8, 512), (24576, 16, 256), (24576, 24, 128), (24576, 32, 128),
    (384, 8, 384),      # 3 blocks of 128: widened 3x, not 4x
    (640, 8, 128),      # 5 blocks: no width above 1 divides it
    (33, 8, 128)])      # N pads to one block_n, as at a full row tile
def test_col_tile_widens_as_far_as_the_row_tile_shrank(n, bm, want):
    assert col_tile(n, bm, 32, 128) == want


@pytest.mark.parametrize("n,bm,want", [
    (6144, 32, 512), (24576, 32, 512), (49152, 32, 512), (512, 32, 512),
    (6144, 8, 2048), (24576, 8, 2048), (49152, 8, 2048), (512, 8, 512)])
def test_col_tile_at_the_default_cap(n, bm, want):
    """StarCoder2-15B's GEMM widths at the default ``block_n``: 512 lanes
    at a full row tile (prefill), 2048 at 8 rows (decode), and no wider
    than N's own 512."""
    cfg = DaismConfig()
    assert col_tile(n, bm, cfg.block_m, cfg.block_n) == want


@pytest.mark.parametrize("bm", [8, 16, 32])
@pytest.mark.parametrize("n", [33, 64, 640])
def test_col_tile_pads_n_to_128_lanes_only(n, bm):
    """A narrow or ragged N keeps a tile within its 128-padded extent, so
    N pads no further than to 128 lanes whatever the cap."""
    cfg = DaismConfig()
    bn = col_tile(n, bm, cfg.block_m, cfg.block_n)
    assert bn % 128 == 0 and round_up(n, 128) % bn == 0


@pytest.mark.parametrize("m", [1, 4, 7, 8, 9, 17, 31, 32, 33, 128])
def test_row_tile_is_bitwise_equal_to_full_block(m):
    """Tiles fitted to M change no output bit: the K order of the
    accumulation is the one of a (32, 128) tile on the 32-row padded
    input. At the default cap N = 512 runs on 512-wide tiles at every
    M."""
    k, n = 256, 512
    a, w = _data(m, k, n, seed=4)
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    a_p = jnp.pad(a, ((0, round_up(m, 32) - m), (0, 0)))
    full = daism_matmul_kernel(a_p, w, variant=Variant.PC3_TR, block_m=32,
                               block_k=128, block_n=128)
    np.testing.assert_array_equal(got, np.asarray(full)[:m])


def test_prefill_tile_is_bitwise_equal_to_narrow_tile():
    """A 128-row GEMM at the default config runs (32, 512) tiles, bitwise
    equal to the (32, 128, 128) kernel: the N tile does not change the K
    order of the accumulation."""
    m, k, n = 128, 256, 1024
    a, w = _data(m, k, n, seed=5)
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    assert col_tile(n, row_tile(m, cfg.block_m), cfg.block_m,
                    cfg.block_n) == 512
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    narrow = daism_matmul_kernel(a, w, variant=Variant.PC3_TR, block_m=32,
                                 block_k=128, block_n=128)
    np.testing.assert_array_equal(got, np.asarray(narrow))


def test_zero_padding_is_semantics_preserving():
    a, w = _data(5, 70, 33, seed=3)
    cfg = DaismConfig(variant=Variant.FLA, backend=Backend.PALLAS)
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    ref = np.asarray(daism_matmul_ref(a, w, Variant.FLA))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-5)


def test_f32_inputs_rejected():
    a = jnp.zeros((8, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    with pytest.raises(ValueError):
        daism_matmul_pallas(a, w, cfg)
