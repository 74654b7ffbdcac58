"""Float multiply + bit (de)composition tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev extra")
from hypothesis import given, settings, strategies as st

from repro.core import Variant, approx_mul_to_f32
from repro.core.bitops import (compose_bf16, compose_f32, decompose_bf16,
                               decompose_f32)
from repro.core.lut import mantissa_product_table

VARIANTS = [Variant.FLA, Variant.HLA, Variant.PC2, Variant.PC3,
            Variant.PC2_TR, Variant.PC3_TR]


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5000,)) * np.exp(rng.normal(size=(5000,)) * 3)
    w = rng.normal(size=(5000,)) * np.exp(rng.normal(size=(5000,)) * 3)
    return x, w


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("variant", VARIANTS)
def test_magnitude_bound_and_sign(operands, dtype, variant):
    x = jnp.asarray(operands[0], dtype)
    w = jnp.asarray(operands[1], dtype)
    exact = np.asarray(x.astype(jnp.float32) * w.astype(jnp.float32))
    ap = np.asarray(approx_mul_to_f32(x, w, variant))
    assert (np.abs(ap) <= np.abs(exact) * (1 + 1e-6)).all()
    nz = (ap != 0)
    assert (np.sign(ap[nz]) == np.sign(exact[nz])).all()
    # bounded relative error (paper: worst case < 50% for FLA)
    rel = np.abs(exact - ap) / np.maximum(np.abs(exact), 1e-30)
    assert rel.max() < 0.51


def test_zero_handling():
    for dtype in (jnp.bfloat16, jnp.float32):
        z = jnp.zeros((4,), dtype)
        w = jnp.asarray([1.5, -2.0, 3.0, 1e10], dtype)
        for variant in (Variant.FLA, Variant.PC3_TR):
            out = np.asarray(approx_mul_to_f32(z, w, variant))
            np.testing.assert_array_equal(out, 0.0)
            out = np.asarray(approx_mul_to_f32(w, z, variant))
            np.testing.assert_array_equal(out, 0.0)


def test_subnormal_flush():
    tiny = jnp.asarray([1e-42], jnp.float32)  # subnormal f32
    w = jnp.asarray([2.0], jnp.float32)
    out = np.asarray(approx_mul_to_f32(tiny, w, Variant.PC3))
    np.testing.assert_array_equal(out, 0.0)


def test_lut_bit_identical():
    """``mantissa_product_table`` (what ``shrinkage_factor`` averages)
    holds the product ``approx_mul_to_f32`` computes, for all 128 x 128
    bf16 mantissa pairs in [1, 2), per variant."""
    frac = np.arange(128) / 128
    x = jnp.asarray(1 + frac[None, :], jnp.bfloat16)   # columns: mx
    w = jnp.asarray(1 + frac[:, None], jnp.bfloat16)   # rows: mw
    for variant in VARIANTS:
        t = mantissa_product_table(variant).astype(np.int64)  # 16-bit
        top = t >> 15                  # 1 where the product is in [2, 4)
        want = (t >> (7 + top)) * 2.0 ** (top - 7)  # truncated to 8 bits
        got = np.asarray(approx_mul_to_f32(x, w, variant))
        np.testing.assert_array_equal(got, want)


def test_exact_variant_is_exact(operands):
    x = jnp.asarray(operands[0], jnp.bfloat16)
    w = jnp.asarray(operands[1], jnp.bfloat16)
    got = np.asarray(approx_mul_to_f32(x, w, Variant.EXACT))
    ref = np.asarray(x.astype(jnp.float32) * w.astype(jnp.float32))
    np.testing.assert_array_equal(got, ref)


@settings(max_examples=300, deadline=None)
@given(bits=st.integers(0, 0xFFFF))
def test_prop_bf16_roundtrip(bits):
    x = jax.lax.bitcast_convert_type(jnp.uint16(bits), jnp.bfloat16)
    s, e, m = decompose_bf16(x)
    y = compose_bf16(s, e, m)
    xf = float(x.astype(jnp.float32))
    yf = float(y.astype(jnp.float32))
    if np.isnan(xf):
        return  # NaN mantissa payloads are not preserved (flushed path)
    if 0 < int(e):  # normal numbers round-trip exactly (inf included)
        assert xf == yf or (np.isinf(xf) and np.isinf(yf))
    else:  # subnormals flush to (signed) zero
        assert yf == 0.0


@settings(max_examples=300, deadline=None)
@given(bits=st.integers(0, 0xFFFFFFFF))
def test_prop_f32_roundtrip(bits):
    x = jax.lax.bitcast_convert_type(jnp.uint32(bits), jnp.float32)
    s, e, m = decompose_f32(x)
    y = compose_f32(s, e, m)
    xf, yf = float(x), float(y)
    if np.isnan(xf):
        return
    if 0 < int(e):
        assert xf == yf or (np.isinf(xf) and np.isinf(yf))
    else:
        assert yf == 0.0


@settings(max_examples=200, deadline=None)
@given(xs=st.floats(allow_nan=False, allow_infinity=False, width=32),
       ws=st.floats(allow_nan=False, allow_infinity=False, width=32),
       v=st.sampled_from(VARIANTS))
def test_prop_float_mul_invariants(xs, ws, v):
    x = jnp.float32(xs)
    w = jnp.float32(ws)
    ap = float(approx_mul_to_f32(x, w, v))
    exact = float(x * w)  # f32 semantics (overflow -> inf, like hardware)
    if np.isinf(exact) or exact == 0:
        return
    assert abs(ap) <= abs(exact) * (1 + 1e-6)
    if ap != 0:
        assert np.sign(ap) == np.sign(exact)
        assert abs(exact - ap) / abs(exact) < 0.51
