"""Flash attention Pallas kernel with fused DAISM approximate products.

The dry-run roofline shows every attention-bearing cell is MEMORY-bound, and
the dominant traffic is the materialized (B, H, Sq, Skv-chunk) score/weight
tensors of the jnp online-softmax path (EXPERIMENTS.md §Perf: tinyllama
train_4k memory term 5.81 s vs 0.22 s compute). This kernel keeps scores in
VMEM: HBM traffic collapses to q+k+v+o, removing the score tensors entirely.
The (m, l, acc) online-softmax accumulators are VMEM *scratch*
(``scratch_shapes``) — they never touch HBM and carry no cross-batch
aliasing hazard (an earlier revision emitted them as outputs indexed only by
the query tile, silently shared across the batch grid axis).

DAISM fusion (the paper's approximate multiplier inside attention): with
``variant`` set, the QK and PV contractions run the shared shift-plane
approximate product (:mod:`~repro.kernels.approx_product`) instead of the
MXU dot — scores *and* approximate products stay VMEM-resident, which is
the only regime where the in-SRAM multiplier's data-movement win survives
(PIM-DRAM: in-memory GEMM loses if the dataflow materializes
intermediates). P is cast to bf16 before the PV product (the multiplier is
an 8-bit-mantissa device); products are bit-exact vs ``kernels/ref.py``.

Tiling: grid (B*H, Sq/bq, Skv/bk), KV innermost with the (m, l, acc)
scratch resident across the KV sweep. Causal masking by absolute position;
KV padding is masked explicitly from the true key length, so non-causal
(cross/encoder) attention works for ragged sequence lengths. Fully-masked
tiles still execute (structural simplicity; the index-map skip is a further
2x — noted in §Perf).

Validated in interpret mode against models.layers.attend (the production
online-softmax), a naive softmax oracle, and ``daism_matmul_ref`` composed
with a naive softmax in tests/test_flash_attention.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import Variant

from .approx_product import approx_matmul_tile

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, kv_steps: int,
            kv_len: int, variant: Optional[Variant]):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                      # (bq, d)
    k = k_ref[0]                                      # (bk, d)
    v = v_ref[0]
    if variant is None:
        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T)
    else:                                             # fused DAISM product
        s = approx_matmul_tile(q, k.T, variant)       # (bq, bk) in VMEM only
    s = s * scale

    mask = None
    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = kv_i * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        mask = k_pos <= q_pos
    if kv_len < kv_steps * bk:  # ragged KV: mask padded keys explicitly
        k_pos = kv_i * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        valid = k_pos < kv_len
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    if mask is not None:
        # exp(-1e30 - m) underflows to 0 once any real key has been seen,
        # but a tile where *every* key so far is masked has m == -1e30 and
        # p == 1; zero masked lanes explicitly so such rows stay empty.
        p = jnp.where(mask, p, 0.0)
    l_new = l_prev * corr + p.sum(-1)
    if variant is None:
        pv = jnp.dot(p, v.astype(jnp.float32))
    else:
        pv = approx_matmul_tile(p.astype(jnp.bfloat16), v, variant)
    acc_new = acc_prev * corr[:, None] + pv
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(kv_i == kv_steps - 1)
    def _finalize():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, kv_len: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    variant: Optional[Variant] = None,
                    interpret: Optional[bool] = None
                    ) -> jnp.ndarray:
    """q: (BH, Sq, D), k/v: (BH, Skv, D) -> (BH, Sq, D).

    Sq % block_q == Skv % block_k == 0 (wrapper pads); ``kv_len`` is the
    true (pre-padding) key length — keys at positions >= kv_len are masked
    out, so non-causal attention is correct for ragged lengths. Scores and
    the online-softmax state never touch HBM: per-step working set = q,k,v
    tiles + (bq, bk) scores + (bq, D) scratch acc — < 1 MiB at D=128.
    ``variant`` switches the QK/PV contractions to the DAISM approximate
    product (bf16 operands only). ``interpret=None`` resolves through
    :func:`repro.policy.dispatch.auto_interpret`.
    """
    from repro.policy.dispatch import auto_interpret

    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % block_q == 0 and skv % block_k == 0
    if variant is not None:
        variant = Variant(variant)
        if variant is Variant.EXACT:
            variant = None
        elif q.dtype != jnp.bfloat16:
            raise ValueError(
                "flash attention with a DAISM variant is bfloat16-only "
                f"(got {jnp.dtype(q.dtype).name}); run the site exact or "
                "switch the compute dtype")
    kv_len = kv_len or skv
    grid = (bh, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(d), causal=causal, bq=block_q,
        bk=block_k, kv_steps=grid[2], kv_len=kv_len, variant=variant)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),           # m
            pltpu.VMEM((block_q,), jnp.float32),           # l
            pltpu.VMEM((block_q, d), jnp.float32),         # acc
        ],
        interpret=auto_interpret(interpret),
        name="flash_attention",
    )(q, k, v)


def flash_attention_bhsd(q, k, v, *, causal=True,
                         variant: Optional[Variant] = None,
                         interpret: Optional[bool] = None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """(B, S, H, D) layout wrapper with GQA head repeat + padding."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    pq = (-sq) % block_q
    pk = (-skv) % block_k
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, pq), (0, 0)))
    if pk:  # padded keys are masked inside the kernel via kv_len
        kt = jnp.pad(kt, ((0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pk), (0, 0)))
    out = flash_attention(qt, kt, vt, causal=causal, kv_len=skv,
                          block_q=block_q, block_k=block_k, variant=variant,
                          interpret=interpret)
    return out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
