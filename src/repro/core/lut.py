"""bf16 mantissa-product table and the calibration statistic read from it.

For bfloat16, the effective mantissa is 8 bits with the MSB always set, so
there are only 128 x 128 = 16,384 distinct mantissa pairs. The full 16-bit
approximate product for every pair is computed once per variant into a
(128, 128) int32 table; ``shrinkage_factor`` averages it into the GEMM's
calibration constant (``DaismConfig.calibrated``).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from .config import Variant
from .multiplier import approx_mul_uint


@functools.lru_cache(maxsize=None)
def mantissa_product_table(variant: Variant) -> np.ndarray:
    """(128, 128) int32 table: T[mw-128, mx-128] = approx product (16-bit)."""
    import jax

    variant = Variant(variant)
    # force eager evaluation even if first requested inside a jit trace
    with jax.ensure_compile_time_eval():
        mw = jnp.arange(128, 256, dtype=jnp.int32)[:, None]
        mx = jnp.arange(128, 256, dtype=jnp.int32)[None, :]
        t = approx_mul_uint(mw, mx, 8, variant, msb_always_set=True)
        return np.asarray(t, dtype=np.int32)


@functools.lru_cache(maxsize=None)
def shrinkage_factor(variant: Variant) -> float:
    """E[approx/exact] over uniform mantissa pairs (beyond-paper calibration).

    DAISM products are one-sided (approx <= exact): a GEMM output is
    systematically shrunk by ~E[ratio]. Dividing outputs by this constant
    (folded into any output scale for free) removes the bias; tests show it
    cuts mean GEMM error ~2x for FLA and improves end-to-end logit fidelity
    (tests/test_gemm.py::test_calibration_reduces_bias).
    """
    t = mantissa_product_table(Variant(variant)).astype(np.float64)
    mw = np.arange(128, 256, dtype=np.float64)[:, None]
    mx = np.arange(128, 256, dtype=np.float64)[None, :]
    return float((t / (mw * mx)).mean())
