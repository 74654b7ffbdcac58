"""Quickstart: the DAISM approximate multiplier in 30 lines.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ALL_VARIANTS, Backend, DaismConfig, Variant,
                        approx_mul, daism_matmul)

# 1. scalar approximate multiplication (paper core concept) -------------
x = jnp.bfloat16(1.375)
w = jnp.bfloat16(-2.5)
print(f"exact        : {float(x) * float(w):+.6f}")
for v in ALL_VARIANTS:
    print(f"{v.value:8s}     : {float(approx_mul(x, w, v)):+.6f}")

# 2. approximate GEMM with exact accumulation ---------------------------
rng = np.random.default_rng(0)
a = jnp.asarray(rng.normal(size=(8, 64)), jnp.bfloat16)
b = jnp.asarray(rng.normal(size=(64, 8)), jnp.bfloat16)
exact = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
for backend in (Backend.JNP, Backend.PALLAS):
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=backend)
    out = np.asarray(daism_matmul(a, b, cfg))
    rel = np.abs(out - exact).mean() / np.abs(exact).mean()
    print(f"GEMM {backend.value:6s}: mean rel err vs exact = {rel:.4f}")

# 3. it differentiates (straight-through backward) ----------------------
cfg = DaismConfig(variant=Variant.PC3_TR)
g = jax.grad(lambda w: (daism_matmul(a, w, cfg) ** 2).sum())(b)
print("grad ok:", g.shape, bool(jnp.isfinite(g.astype(jnp.float32)).all()))
