"""Approximate GEMM: exact accumulation of DAISM-approximate scalar products.

This is the paper's contribution lifted to the operation DNNs actually need:
``out[m, n] = sum_k approx(a[m, k] * w[k, n])`` where the per-element product
uses one of the Table-1 multiplier variants and the reduction is exact
(DAISM's accumulator is an exact adder, paper §4.1).

Backends
  * ``jnp``    — vectorized bit ops, K-chunked to bound the (M, Kc, N)
                 intermediate. The reference semantics; differentiable via
                 ``custom_vjp``.
  * ``pallas`` — VMEM-tiled TPU kernel (kernels/daism_matmul.py).
  * ``exact``  — plain MXU matmul (deployment path).

Autodiff: the forward pass uses the approximate product. The backward pass is
straight-through (exact matmul gradients) by default, or routed through the
approximate GEMM as well with ``backward='approx'`` (paper §5.1.2 notes models
can be *trained* under the approximation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .bitops import round_up as _round_up
from .config import Backend, DaismConfig, Variant
from .floatmul import approx_mul_to_f32


def _matmul_chunked(a: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    """(M, K) x (K, N) -> (M, N) f32, chunking K to bound peak memory."""
    m, k = a.shape
    k2, n = w.shape
    assert k == k2, (a.shape, w.shape)
    prod = functools.partial(approx_mul_to_f32, variant=cfg.variant)
    kc = min(cfg.k_chunk, k)
    k_pad = _round_up(k, kc)
    if k_pad != k:  # zero-padding is exact: approx(0 * w) == 0
        a = jnp.pad(a, ((0, 0), (0, k_pad - k)))
        w = jnp.pad(w, ((0, k_pad - k), (0, 0)))
    steps = k_pad // kc
    a3 = a.reshape(m, steps, kc).transpose(1, 0, 2)     # (steps, M, Kc)
    w3 = w.reshape(steps, kc, n)                         # (steps, Kc, N)

    def body(acc, operands):
        ac, wc = operands
        p = prod(ac[:, :, None], wc[None, :, :])         # (M, Kc, N) f32
        return acc + p.sum(axis=1), None

    acc0 = jnp.zeros((m, n), jnp.float32)
    out, _ = lax.scan(body, acc0, (a3, w3))
    return out


def _matmul_fwd_impl(a: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    if cfg.exact:
        return jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32))
    if cfg.backend is Backend.PALLAS:
        from repro.kernels import ops as kops  # local import: avoid cycle

        return kops.daism_matmul_pallas(a, w, cfg)
    return _matmul_chunked(a, w, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _daism_matmul(a: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    return _matmul_fwd_impl(a, w, cfg)


def _fwd(a, w, cfg):
    return _matmul_fwd_impl(a, w, cfg), (a, w)


def _bwd(cfg, res, g):
    a, w = res
    g = g.astype(jnp.float32)
    if cfg.backward == "approx" and not cfg.exact:
        bcfg = cfg  # same approximate numerics for the gradient GEMMs
        da = _matmul_fwd_impl(g.astype(a.dtype), w.T.astype(a.dtype), bcfg)
        dw = _matmul_fwd_impl(a.T.astype(a.dtype), g.astype(a.dtype), bcfg)
    else:  # straight-through: exact gradients
        da = jnp.matmul(g, w.astype(jnp.float32).T)
        dw = jnp.matmul(a.astype(jnp.float32).T, g)
    return da.astype(a.dtype), dw.astype(w.dtype)


_daism_matmul.defvjp(_fwd, _bwd)


def daism_matmul(a: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    """2-D approximate matmul, (M, K) @ (K, N) -> (M, N) in ``cfg.accum_dtype``."""
    out = _daism_matmul(a, w, cfg)
    if cfg.calibrated and not cfg.exact:
        from .lut import shrinkage_factor  # bf16-table statistic

        out = out * (1.0 / shrinkage_factor(cfg.variant))
    return out.astype(cfg.accum_dtype)


def daism_dot(x: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    """``x @ w`` over the last axis of ``x``: (..., K) @ (K, N) -> (..., N).

    The deployment path (cfg.exact) preserves input dtype semantics of
    ``jnp.dot``; approximate paths accumulate in f32 then cast to
    ``cfg.accum_dtype``.
    """
    if cfg.exact:
        return jnp.dot(x, w)
    lead = x.shape[:-1]
    k = x.shape[-1]
    out = daism_matmul(x.reshape(-1, k), w, cfg)
    return out.reshape(*lead, w.shape[-1])


def conv2d_im2col(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    cfg: DaismConfig,
    *,
    stride: int = 1,
    padding: str = "SAME",
) -> jnp.ndarray:
    """NHWC conv via im2col + DAISM GEMM — how the accelerator executes convs
    (kernels flattened into SRAM rows, paper Fig 4). kernel: (kh, kw, cin, cout).
    """
    kh, kw, cin, cout = kernel.shape
    if cfg.exact:
        return lax.conv_general_dilated(
            x, kernel, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (N, Ho, Wo, kh*kw*cin) with feature dim ordered (cin, kh, kw)
    nb, ho, wo, feat = patches.shape
    # conv_general_dilated_patches orders features as (cin, kh, kw); reorder
    # the kernel to match.
    kmat = kernel.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)
    out = daism_matmul(patches.reshape(-1, feat), kmat, cfg)
    return out.reshape(nb, ho, wo, cout)
