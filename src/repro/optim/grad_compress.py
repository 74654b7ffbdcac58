"""Gradient compression for the data-parallel all-reduce.

At 1000+ nodes the gradient all-reduce dominates the collective term for
small models (EXPERIMENTS.md §Roofline). Two honest, HLO-visible modes:

* ``bf16``: cast gradients to bf16 before the psum — exactly 2x less
  all-reduce traffic than f32, loss-free in practice for gradients that are
  consumed by Adam normalization.
* ``int8``: two-phase — (1) pmax the per-leaf scale across replicas,
  (2) quantize with the *global* scale and psum the int8 payload widened to
  int32 for overflow-safe accumulation. The on-wire format is whatever the
  backend emits for the psum operand; we do not claim a 4x wire win blindly —
  the roofline harness parses the actual collective operand bytes from the
  compiled HLO, so the measured collective term reflects reality.

Quantization error is zero-mean and <1 % cosine distortion on Adam-scale
gradients (tests/test_optim.py).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def quantize_int8(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8)


def compressed_psum(grads: Any, axis_names, mode: str = "int8") -> Any:
    """Mean-reduce a gradient pytree across ``axis_names`` with compression.

    Must be called inside a ``jax.shard_map`` context where the axes are
    bound.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    n = 1
    for a in axis_names:
        n = n * lax.psum(1, a)

    def psum_all(x):
        for a in axis_names:
            x = lax.psum(x, a)
        return x

    def one(g):
        if mode == "none":
            return psum_all(g.astype(jnp.float32)) / n
        if mode == "bf16":
            return (psum_all(g.astype(jnp.bfloat16)).astype(jnp.float32) / n
                    ).astype(g.dtype)
        # int8: global scale first (tiny scalar all-reduce), then payload.
        s = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0 + 1e-12
        for a in axis_names:
            s = lax.pmax(s, a)
        q = quantize_int8(g, s).astype(jnp.int32)
        total = psum_all(q)
        return (total.astype(jnp.float32) * s / n).astype(g.dtype)

    return jax.tree.map(one, grads)
