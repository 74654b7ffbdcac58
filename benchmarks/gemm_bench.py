"""DAISM GEMM micro-bench: backends (jnp / LUT / Pallas-interpret) across
shapes, CPU wall time + derived TPU-roofline estimates for the kernel.

Wall times on this CPU container measure *relative* backend overheads; the
derived column estimates the TPU v5e VPU-bound time for the DAISM kernel
(8 shift/OR int32 steps per MAC on the VPU at ~4 Top/s int32) vs the exact
MXU matmul (197 TFLOP/s) — quantifying the honest deployment trade-off
documented in DESIGN.md §2.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Backend, DaismConfig, Variant, daism_matmul
from repro.roofline.analysis import chip_peaks

# the derived columns model this chip (estimates, not measurements)
TARGET_KIND = "TPU v5 lite"
VPU_INT32_OPS = 4e12     # ~per chip
MXU_FLOPS = chip_peaks(TARGET_KIND).bf16_flops
# int32 VPU op-equivalents per MAC, per backend, from each backend's actual
# op mix (previously one shared constant made the derived column identical
# for all three approximate backends — it distinguished nothing):
#
#  * PALLAS — fused shift-plane kernel (kernels/approx_product
#    .approx_matmul_tile). Operand decomposition is hoisted out of the K
#    sweep (amortized over the opposite tile edge, ~0 per MAC) and the
#    K-sum folds into the plane loop:
#      pre-computed 3-bit head line: mul + shift               = 2
#      5 remaining planes x (select + shift + or)              = 15
#      truncation column mask                                  = 1
#      f32 re-composition (normalize shift/select, exponent
#      add + flush/saturate selects, sign/bit assembly)        = 6  -> 24
#  * JNP — unfused elementwise reference: every MAC pays the full chain,
#    decompose (4) + 8x(select/or/shift) + normalize + compose  -> 30
#  * LUT — gather-bound (core/lut.approx_mul_to_f32_lut): the 8-step chain
#    collapses into one 32 KiB VMEM table read, but per-MAC decompose and
#    re-composition remain and the gather itself runs at ~1/4 ALU
#    throughput on the VPU:
#      decompose (4) + index form max/shift/or (3) + gather (~4
#      ALU-op equivalents) + top/man normalize (4) + compose (6) -> 21
OPS_PER_MAC = {
    Backend.PALLAS: 24,
    Backend.JNP: 30,
    Backend.LUT: 21,
}

# claims guarded by ``run.py --check`` (direction = which way is better)
REGRESSION_CLAIMS = {
    "daism_tpu_slowdown_vs_mxu": "lower",
    "derived_tpu_us_distinct_across_backends": "bool",
}
# deployed-kernel count (Pallas fused shift-plane), used for the headline
# slowdown claim; the pre-fusion JNP mix is the 30 above
DAISM_OPS_PER_MAC = OPS_PER_MAC[Backend.PALLAS]


def _time(fn, *args, iters=3):
    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    rows = []
    rng = np.random.default_rng(0)
    shapes = [(128, 512, 512), (256, 1024, 512)]
    for (m, k, n) in shapes:
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)
        macs = m * k * n
        tpu_exact_us = 2 * macs / MXU_FLOPS * 1e6
        for backend in (Backend.EXACT, Backend.JNP, Backend.LUT,
                        Backend.PALLAS):
            variant = Variant.EXACT if backend is Backend.EXACT \
                else Variant.PC3_TR
            cfg = DaismConfig(variant=variant, backend=backend)
            fn = jax.jit(lambda a, w, c=cfg: daism_matmul(a, w, c))
            us = _time(fn, a, w)
            derived = (tpu_exact_us if backend is Backend.EXACT
                       else macs * OPS_PER_MAC[backend]
                       / VPU_INT32_OPS * 1e6)
            rows.append({
                "name": f"gemm_{m}x{k}x{n}_{backend.value}",
                "us_per_call": round(us, 1),
                "derived_tpu_us": round(derived, 2),
            })
    claims = {
        "daism_tpu_slowdown_vs_mxu": round(
            DAISM_OPS_PER_MAC / VPU_INT32_OPS / (2 / MXU_FLOPS), 1),
        # the derived column must actually distinguish the backends it
        # claims to model — the regression this bench once shipped
        "derived_tpu_us_distinct_across_backends": len(
            set(OPS_PER_MAC.values())) == len(OPS_PER_MAC),
    }
    return rows, claims


if __name__ == "__main__":
    rows, claims = run()
    for r in rows:
        print(r)
    print(claims)
