"""Pallas TPU kernel for the DAISM approximate matmul (bfloat16).

This is the paper's compute hot spot mapped to the TPU memory hierarchy
(DESIGN.md §2): the SRAM wired-OR read becomes a bit-parallel shift/OR chain
on int32 VPU lanes; the pre-computed PC2/PC3 head lines become constant-
folded selected adds; truncation is a free column mask (carry-free).

Tiling: grid (M/bm, N/bn, K/bk) with K innermost so the f32 accumulator tile
stays resident in VMEM across the K sweep (revisiting semantics). The inner
tile contraction is the *fused* shift-plane sweep from
:mod:`~repro.kernels.approx_product`: K is consumed in
:data:`~repro.kernels.approx_product.K_FUSE`-wide sub-chunks whose products
fold straight into the (bm, bn) accumulator, so the (bm, bk, bn) product
tensor of the original kernel never materializes. Working set per step:

    a tile (bm, bk) bf16 + w tile (bk, bn) bf16          (streamed from HBM)
    decomposed int32 fields + (K_FUSE, bm, bn) slabs     (VMEM)
    out tile (bm, bn) f32                                 (resident)

DaismConfig defaults (bm=32, bk=128, bn=512): the fusion removed the
bm*bk*bn term, so the M tile rises 8 -> 32 (4x fewer grid steps) while
peak VMEM stays ~ 3 * 32*8*512 * 4 B of live slab temporaries + tiles
≈ 2.5 MiB — comfortable within a 16 MiB VMEM budget, with MXU-aligned
(multiple-of-128) N/K tile edges for the exact-baseline comparison kernel.
Both M and N tiles are upper bounds that the ops.py wrapper fits to the
GEMM: the M tile to M (``ops.row_tile``: 8 rows for a 4-row decode), since
every emulated product costs the same whether its row is live or padding,
and the N tile to the widest multiple of 128 lanes that divides N's
128-padded extent, up to bn widened as far as the M tile shrank
(``ops.col_tile``: 512 at 32 rows, 2048 at 8). Each K sub-chunk of the
sweep pays a fixed cost (operand transposes and lane broadcasts) besides
its slab, and a wide N tile spreads it over more lanes: on a v5e a
128-row 6144x24576 GEMM takes 268 ms at (32, 512) against 580 ms at
(32, 128), and a 4-row one 20.7 ms at (8, 2048) against 26.5 at (8, 512).

Validated in interpret mode on CPU against kernels/ref.py (bit-exact
per-element products; f32 accumulation-order tolerance).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.config import DaismConfig, Variant

from .approx_product import approx_matmul_tile


def _kernel(a_ref, w_ref, o_ref, *, variant: Variant):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a_tile = a_ref[...]
    w_tile = w_ref[...]
    if variant is Variant.EXACT:
        # Exact-baseline kernel: straight MXU matmul on the same tiling.
        o_ref[...] += jnp.dot(
            a_tile.astype(jnp.float32), w_tile.astype(jnp.float32),
            preferred_element_type=jnp.float32)
    else:
        o_ref[...] += approx_matmul_tile(a_tile, w_tile, variant)


def daism_matmul_kernel(
    a: jnp.ndarray,
    w: jnp.ndarray,
    *,
    variant: Variant = Variant.PC3_TR,
    block_m: int = 32,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(M, K) @ (K, N) -> (M, N) f32 via the DAISM Pallas kernel.

    Requires M % block_m == K % block_k == N % block_n == 0 (the ops.py
    wrapper pads). bf16 inputs only (f32 uses the dual-plane jnp path).
    ``interpret=None`` resolves through
    :func:`repro.policy.dispatch.auto_interpret` (explicit setting wins,
    else interpret on CPU, compiled on TPU) so direct callers never silently
    benchmark interpret mode on hardware.
    """
    from repro.policy.dispatch import auto_interpret

    m, k = a.shape
    k2, n = w.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    grid = (m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(_kernel, variant=Variant(variant))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=auto_interpret(interpret),
        name="daism_matmul",
    )(a, w)
