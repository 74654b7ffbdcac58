"""DAISM configuration objects.

A :class:`DaismConfig` fully determines the numerics of the approximate
multiplier (paper Table 1) plus the execution backend used to realize it.
It is a frozen, hashable dataclass so it can be passed as a static argument
through ``jax.jit`` boundaries.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class Variant(str, enum.Enum):
    """Multiplier variants from paper Table 1 (+ exact baseline)."""

    EXACT = "exact"    # carry-propagating baseline multiplier
    FLA = "fla"        # full lines activation: OR of all selected partial products
    HLA = "hla"        # half lines activation: 2 reads (even/odd shifts), exact add
    PC2 = "pc2"        # pre-computed A+B head line
    PC3 = "pc3"        # pre-computed combos of A,B,C head line
    PC2_TR = "pc2_tr"  # PC2 + truncation to top-n columns
    PC3_TR = "pc3_tr"  # PC3 + truncation to top-n columns

    @property
    def truncated(self) -> bool:
        return self in (Variant.PC2_TR, Variant.PC3_TR)

    @property
    def base(self) -> "Variant":
        return {
            Variant.PC2_TR: Variant.PC2,
            Variant.PC3_TR: Variant.PC3,
        }.get(self, self)

    @property
    def memory_reads(self) -> int:
        """Paper Table 1: number of SRAM reads per multiplication."""
        return 2 if self is Variant.HLA else 1


class Backend(str, enum.Enum):
    """Execution strategy for the approximate GEMM."""

    JNP = "jnp"              # pure-jnp vectorized bit ops (reference / oracle)
    PALLAS = "pallas"        # Pallas TPU kernel (interpret=True on CPU)
    EXACT = "exact"          # plain MXU matmul (deployment path)


_MANTISSA_BITS = {"bfloat16": 8, "float32": 24}


@dataclasses.dataclass(frozen=True)
class DaismConfig:
    """Static numerics + backend configuration.

    Attributes:
      variant: which approximate multiplier (paper Table 1).
      backend: how to execute it.
      integer_drop_lsb: in *integer* PC2 mode, whether the LSB partial-product
        line ``H`` is sacrificed to make room for the pre-computed ``A+B``
        line (faithful to paper Fig 3). Float mode never drops lines because
        the mantissa MSB is always 1 (paper 3.4).
      accum_dtype: exact accumulator dtype used by the GEMM reduction
        (DAISM's accumulator is exact; paper 4.1).
      backward: 'ste' uses exact gradients (straight-through), 'approx'
        routes the backward GEMMs through the approximate multiplier too
        (paper 5.1.2: "The model can also be trained to use these
        approximations").
      k_chunk: K-dim chunk size used by the jnp backend to bound the
        materialized (M, Kc, N) intermediate.
      attn_kernel: how attention-score sites (OpKind.ATTN_QK) execute.
        'jnp' keeps the production online-softmax path (always exact
        numerics — neither attention operand is SRAM-stationary); 'flash'
        dispatches to the Pallas flash-attention kernel, which fuses this
        config's approximate QK/PV products with the online-softmax
        accumulator in VMEM (exact configs run the flash kernel with MXU
        contractions). Ignored by every other OpKind.
    """

    variant: Variant = Variant.PC3_TR
    backend: Backend = Backend.JNP
    integer_drop_lsb: bool = True
    accum_dtype: str = "float32"
    backward: str = "ste"  # 'ste' | 'approx'
    calibrated: bool = False  # beyond-paper: unbias the one-sided shrinkage
    k_chunk: int = 64
    # Pallas tiling knobs (block sizes for the kernel); defaults chosen so the
    # working set fits a 16 MiB VMEM budget with headroom (see kernels/).
    # bm=32 relies on the fused shift-plane sweep: the kernel's peak live
    # intermediate is (K_FUSE, bm, bn), not (bm, bk, bn). block_m is the
    # upper bound of the M tile: fewer rows take a tile of M rounded up to
    # 8 (kernels.ops.row_tile). block_n caps the N tile at a full M tile,
    # and the cap widens as far as the M tile shrank (kernels.ops.col_tile:
    # 512 at 32 rows, 2048 at 8); N still pads to 128 lanes only. A wide
    # N tile spreads each K sub-chunk's fixed cost over more lanes
    # (kernels/daism_matmul.py gives the v5e timings).
    block_m: int = 32
    block_n: int = 512
    block_k: int = 128
    interpret: Optional[bool] = None  # None -> auto (True on CPU)
    attn_kernel: str = "jnp"  # 'jnp' | 'flash' (attention-score sites only)

    def __post_init__(self) -> None:
        if self.backward not in ("ste", "approx"):
            raise ValueError(f"backward must be 'ste'|'approx', got {self.backward}")
        if self.attn_kernel not in ("jnp", "flash"):
            raise ValueError(
                f"attn_kernel must be 'jnp'|'flash', got {self.attn_kernel!r}")
        if self.accum_dtype not in _MANTISSA_BITS:
            raise ValueError(
                f"accum_dtype must be one of {sorted(_MANTISSA_BITS)}, got "
                f"{self.accum_dtype!r}")
        if self.k_chunk < 1:
            raise ValueError(f"k_chunk must be >= 1, got {self.k_chunk}")
        if min(self.block_m, self.block_n, self.block_k) < 1:
            raise ValueError(
                "pallas block sizes must be >= 1, got "
                f"(block_m={self.block_m}, block_n={self.block_n}, "
                f"block_k={self.block_k})")
        if (self.backend is Backend.PALLAS and not self.exact
                and self.backward == "approx"):
            raise ValueError(
                "backend 'pallas' has no approximate backward kernel; use "
                "backward='ste' (exact gradients) or backend='jnp'")

    def validate_for_dtype(self, dtype, *, site: str = "") -> None:
        """Check this config can run on ``dtype`` operands (actionable error
        instead of a deep-kernel failure); see policy.dispatch."""
        from repro.policy.dispatch import validate_for_dtype

        validate_for_dtype(self, dtype, site=site)

    @property
    def exact(self) -> bool:
        return self.variant is Variant.EXACT or self.backend is Backend.EXACT

    def replace(self, **kw) -> "DaismConfig":
        return dataclasses.replace(self, **kw)


def mantissa_bits(dtype) -> int:
    """Effective mantissa width (including the implicit leading 1)."""
    import jax.numpy as jnp

    d = jnp.dtype(dtype)
    name = d.name
    if name not in _MANTISSA_BITS:
        raise ValueError(f"DAISM supports bfloat16/float32, got {name}")
    return _MANTISSA_BITS[name]


# Canonical configs used throughout benchmarks/tests (paper Table 1 order).
ALL_VARIANTS = (
    Variant.FLA,
    Variant.HLA,
    Variant.PC2,
    Variant.PC3,
    Variant.PC2_TR,
    Variant.PC3_TR,
)
