"""Approximate GEMM semantics: chunking, autodiff, conv-via-im2col."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Backend, DaismConfig, Variant, conv2d_im2col,
                        daism_dot, daism_matmul)


def _ab(m=16, k=96, n=32, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(m, k)), dtype),
            jnp.asarray(rng.normal(size=(k, n)), dtype))


def test_k_chunk_invariance():
    a, w = _ab(8, 70, 16)
    base = DaismConfig(variant=Variant.PC3_TR)
    outs = [np.asarray(daism_matmul(a, w, base.replace(k_chunk=c)))
            for c in (7, 32, 70)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_backends_agree():
    a, w = _ab(8, 64, 16, seed=1)
    cfgs = [DaismConfig(variant=Variant.PC3_TR, backend=b)
            for b in (Backend.JNP, Backend.PALLAS)]
    outs = [np.asarray(daism_matmul(a, w, c)) for c in cfgs]
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-6, atol=1e-6)


def test_systematic_shrinkage():
    """Approx products are one-sided (|approx| <= |exact|): a GEMM of
    positive operands must come out strictly below the exact result."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(np.abs(rng.normal(size=(8, 128))) + 0.1, jnp.bfloat16)
    w = jnp.asarray(np.abs(rng.normal(size=(128, 8))) + 0.1, jnp.bfloat16)
    exact = np.asarray(a, np.float32) @ np.asarray(w, np.float32)
    for v in (Variant.FLA, Variant.PC3_TR):
        ap = np.asarray(daism_matmul(a, w, DaismConfig(variant=v)))
        assert (ap <= exact + 1e-3).all()
        assert ap.mean() < exact.mean()


def test_ste_gradients_match_exact():
    a, w = _ab(4, 32, 8, seed=3)
    cfg = DaismConfig(variant=Variant.PC3_TR, backward="ste")

    g_approx = jax.grad(lambda w: (daism_matmul(a, w, cfg) ** 2).sum())(w)
    # STE backward uses exact matmul grads of the approx forward output
    out = daism_matmul(a, w, cfg)
    g_manual = jnp.matmul(a.astype(jnp.float32).T, 2 * out)
    # grads are returned in the weight dtype (bf16): compare at bf16 eps
    np.testing.assert_allclose(np.asarray(g_approx, np.float32),
                               np.asarray(g_manual, np.float32),
                               rtol=0.05, atol=0.2)


def test_approx_backward_runs_and_is_finite():
    a, w = _ab(4, 32, 8, seed=4)
    cfg = DaismConfig(variant=Variant.PC3_TR, backward="approx")
    g = jax.grad(lambda w: (daism_matmul(a, w, cfg) ** 2).sum())(w)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_daism_dot_batched_shapes():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 3, 24)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(24, 8)), jnp.bfloat16)
    cfg = DaismConfig(variant=Variant.PC3_TR)
    out = daism_dot(x, w, cfg)
    assert out.shape == (2, 3, 8)
    flat = daism_matmul(x.reshape(-1, 24), w, cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 8),
                               np.asarray(flat), rtol=1e-6)


@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_conv_im2col_exact_mode_matches_lax(padding):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 10, 10, 3)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 3, 3, 5)), jnp.float32)
    exact_cfg = DaismConfig(variant=Variant.EXACT, backend=Backend.EXACT)
    ref = conv2d_im2col(x, k, exact_cfg, padding=padding)
    # approximate path with EXACT variant (exercises im2col + GEMM route)
    cfg = DaismConfig(variant=Variant.EXACT, backend=Backend.JNP)
    got = conv2d_im2col(x, k, cfg, padding=padding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_conv_approx_close_to_exact():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(3, 3, 3, 4)) * 0.2, jnp.bfloat16)
    ce = np.asarray(conv2d_im2col(
        x, k, DaismConfig(variant=Variant.EXACT, backend=Backend.EXACT)),
        np.float32)
    ca = np.asarray(conv2d_im2col(x, k, DaismConfig(variant=Variant.PC3_TR)))
    rel = np.abs(ce - ca).mean() / np.abs(ce).mean()
    assert rel < 0.1


def test_calibration_reduces_bias():
    """Beyond-paper shrinkage calibration: dividing by E[approx/exact]
    removes the one-sided bias (~4x mean-error cut for FLA)."""
    from repro.core.lut import shrinkage_factor

    rng = np.random.default_rng(12)
    a = jnp.asarray(np.abs(rng.normal(size=(16, 128))) + 0.1, jnp.bfloat16)
    w = jnp.asarray(np.abs(rng.normal(size=(128, 16))) + 0.1, jnp.bfloat16)
    ref = np.asarray(a, np.float32) @ np.asarray(w, np.float32)
    for v in (Variant.FLA, Variant.PC3_TR):
        f = shrinkage_factor(v)
        assert 0.8 < f < 1.0
        e_plain = np.abs(np.asarray(daism_matmul(
            a, w, DaismConfig(variant=v))) - ref).mean()
        e_cal = np.abs(np.asarray(daism_matmul(
            a, w, DaismConfig(variant=v, calibrated=True))) - ref).mean()
        assert e_cal < 0.55 * e_plain, (v, e_plain, e_cal)
