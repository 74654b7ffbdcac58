"""Shared DAISM approximate-product primitives for the Pallas kernels.

The bf16 decomposition, the Table-1 approximate mantissa product (the SRAM
wired-OR read mapped to shift/OR chains on int32 VPU lanes), and the f32
re-composition live here so both the GEMM kernel (daism_matmul.py) and the
fused flash-attention kernel (flash_attention.py) share one implementation —
both must stay bit-exact against kernels/ref.py.

:func:`approx_matmul_tile` is the fused tile contraction: instead of
materializing the full (bm, bk, bn) product tensor and reducing afterwards,
it sweeps K in :data:`K_FUSE`-wide sub-chunks, runs the shift-plane product
on each (K_FUSE, bm, bn) slab, and folds the slab straight into the (bm, bn)
f32 accumulator. The sweep is a rolled loop (one sub-chunk of code, however
large bk), which keeps both the Mosaic program and its VMEM stack small.
Peak live intermediate drops from O(bm*bk*bn) to O(bm*K_FUSE*bn), which is
what lets the GEMM kernel raise its M tile and the attention kernel keep
scores + products VMEM-resident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import Variant

_BIAS = 127

# K-dim sub-chunk width of the fused plane sweep. 8 keeps the live
# (K_FUSE, bm, bn) slabs at VPU-sublane granularity: at the GEMM's
# (32, 512) and (8, 2048) tiles a slab holds 8 x 16384 elements, and the
# ~3 live int32/f32 temporaries total ~1.5 MiB, independent of block_k.
# Each sub-chunk also pays a fixed cost (the a-fields' transpose and lane
# broadcast), which a wider bn spreads over more lanes.
K_FUSE = 8


def decompose_bf16_i32(x):
    """bf16 -> (sign, exponent, mantissa-with-hidden-1) int32 fields."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    sign = bits >> 15
    exp = (bits >> 7) & 0xFF
    man = jnp.where(exp > 0, (bits & 0x7F) | 0x80, 0)
    return sign, exp, man


def _bit(b, i):
    return (b >> i) & 1


def approx_mantissa_product(mw, mx, variant: Variant):
    """8-bit mantissa approximate product (int32), float mode (MSB set)."""
    base = variant.base
    if base is Variant.EXACT:
        out = mw * mx
    elif base is Variant.FLA:
        out = jnp.zeros_like(mw)
        for i in range(8):
            out = out | jnp.where(_bit(mx, i) == 1, mw << i, 0)
    elif base is Variant.HLA:
        even = jnp.zeros_like(mw)
        odd = jnp.zeros_like(mw)
        for i in range(0, 8, 2):
            even = even | jnp.where(_bit(mx, i) == 1, mw << i, 0)
        for i in range(1, 8, 2):
            odd = odd | jnp.where(_bit(mx, i) == 1, mw << i, 0)
        out = even + odd
    elif base in (Variant.PC2, Variant.PC3):
        k = 2 if base is Variant.PC2 else 3
        w = _bit(mx, 7) | 1  # float mode: A always active
        for j in range(1, k):
            w = 2 * w + _bit(mx, 7 - j)
        out = (mw * w) << (8 - k)
        for i in range(0, 8 - k):
            out = out | jnp.where(_bit(mx, i) == 1, mw << i, 0)
    else:  # pragma: no cover
        raise ValueError(variant)
    if variant.truncated:
        out = out & (0xFF << 8)
    return out


def product_block_f32(a_tile, w_tile, variant: Variant):
    """(bm, bk) x (bk, bn) bf16 -> (bm, bk, bn) f32 approximate products."""
    sx, ex, mx = decompose_bf16_i32(a_tile)   # input = multiplier
    sw, ew, mw = decompose_bf16_i32(w_tile)   # weight = multiplicand
    return compose_products_f32(
        (sx[:, :, None], ex[:, :, None], mx[:, :, None]),
        (sw[None, :, :], ew[None, :, :], mw[None, :, :]), variant)


def compose_products_f32(x_fields, w_fields, variant: Variant):
    """Broadcast (sign, exp, man) field triples -> f32 approximate products.

    The mantissa product uses the variant's shift-plane chain; normalization,
    exponent add, subnormal-flush, and saturation compose the f32 directly
    from integer fields (bit-exact vs core.floatmul / kernels/ref.py).
    """
    sx3, ex3, mx3 = x_fields
    sw3, ew3, mw3 = w_fields
    prod = approx_mantissa_product(mw3, mx3, variant)
    top = (prod >> 15) & 1
    man = jnp.where(top == 1, prod >> 8, prod >> 7) & 0xFF

    sign = sx3 ^ sw3
    exp = ex3 + ew3 - _BIAS + top
    zero = (mx3 == 0) | (mw3 == 0)
    exp = jnp.where(zero, 0, exp)
    man = jnp.where(zero, 0, man)
    is_zero = (man == 0) | (exp <= 0)
    is_inf = exp >= 255
    bits = (
        (sign.astype(jnp.uint32) << 31)
        | (jnp.clip(exp, 0, 254).astype(jnp.uint32) << 23)
        | ((man << 16) & 0x7FFFFF).astype(jnp.uint32)
    )
    bits = jnp.where(is_zero, sign.astype(jnp.uint32) << 31, bits)
    bits = jnp.where(is_inf & ~is_zero,
                     (sign.astype(jnp.uint32) << 31) | jnp.uint32(0x7F800000),
                     bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def approx_matmul_tile(a_tile, w_tile, variant: Variant, *,
                       k_fuse: int = K_FUSE) -> jnp.ndarray:
    """(bm, bk) @ (bk, bn) bf16 -> (bm, bn) f32, fused shift-plane sweep.

    The K reduction is folded into the plane loop: each ``k_fuse``-wide
    sub-chunk's products are composed and summed into the accumulator before
    the next sub-chunk's planes are formed, so no (bm, bk, bn) tensor ever
    exists. Operand decomposition is hoisted out of the sweep (amortized
    over bn for ``a`` and over bm for ``w``).
    """
    bm, bk = a_tile.shape
    bn = w_tile.shape[1]
    kf = min(k_fuse, bk)
    assert bk % kf == 0, (bk, kf)
    x_fields = [f.T for f in decompose_bf16_i32(a_tile)]   # (bk, bm) each
    w_fields = decompose_bf16_i32(w_tile)                  # (bk, bn) each

    # Mosaic slices a value only statically and a VMEM ref dynamically only
    # along sublanes, so the fields are staged K-major in scoped VMEM and the
    # sweep is a rolled loop over sublane windows. Each slab is laid out
    # (kf, bm, bn): the window's a-columns are broadcast along lanes, its
    # w-rows along sublanes, and the slab is summed over the leading axis.
    def cols(f):     # (bm, kf) -> (kf, bm, 1)
        return jnp.stack([jax.lax.slice_in_dim(f, j, j + 1, axis=1)
                          for j in range(kf)])

    def rows(f):     # (kf, bn) -> (kf, 1, bn)
        return jnp.stack([jax.lax.slice_in_dim(f, j, j + 1, axis=0)
                          for j in range(kf)])

    def sweep(*refs):
        for ref, f in zip(refs, (*x_fields, *w_fields)):
            ref[...] = f

        def body(i, acc):
            lo = pl.multiple_of(i * kf, kf)
            slab = compose_products_f32(
                tuple(cols(r[pl.ds(lo, kf), :].T) for r in refs[:3]),
                tuple(rows(r[pl.ds(lo, kf), :]) for r in refs[3:]), variant)
            return acc + slab.sum(axis=0)

        return jax.lax.fori_loop(0, bk // kf, body,
                                 jnp.zeros((bm, bn), jnp.float32))

    return pl.run_scoped(sweep, *[pltpu.VMEM((bk, bm), jnp.int32)] * 3,
                         *[pltpu.VMEM((bk, bn), jnp.int32)] * 3)
