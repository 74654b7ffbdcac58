"""Plain reference forward pass of a pre-LayerNorm decoder LM.

This is the yardstick that decides ``correct``. It imports nothing of the
program under test and takes nothing it made: the weights are made again
from the seed, one matrix at a time, by :mod:`bench.weights`, and upcast
to float32 only while that matrix is in use, so the reference fits beside
nothing on one chip even where the float32 model would not (18.5 GB for
the Nemotron-4 layer).

The architecture is the one both configurations state (StarCoder2,
Nemotron-4): token embedding; per layer ``x += Attn(LN1(x))`` and
``x += MLP(LN2(x))`` with biases on every projection; GQA attention with
rotary position embedding on the first ``rotary_fraction`` of each head
(rotate-half form) and causal softmax; a non-gated MLP (tanh GELU or
squared ReLU); a final LayerNorm and an untied ``lm_head``.

Sequences are packed into one row axis of fixed length ``T`` with a
segment id and a position per row, so every seed runs the same shapes.

GEMM modes (``gemm``), one per kind of cell:

* ``exact``: float32 operands, float32 products and sums
  (``Precision.HIGHEST``).
* ``pc3_tr``: the DAISM approximate multiplier, PC3 with truncation
  (arXiv:2305.07376, Table 1), on bfloat16 operands, as the configuration's
  approximate tier states. Each product is ``sign * 2^(Ex+Ew) * G[fx, fw]``
  with ``G`` the approximate product of the two 8-bit mantissas (see
  :func:`pc3_tr_mantissa`), summed in float32. The GEMM is computed exactly as
  ``sum_j A_j @ B_j`` over the 128 values ``j`` of the weight's fraction
  (:func:`approx_dot`); both operands are exact in bfloat16, so every
  product the MXU forms is exact in float32.

``quant="fp8"`` rounds both operands of every weight GEMM to float8 e4m3
(power-of-two scale per input row and per weight column) before the
product: the control, one precision step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ROW_BLOCK = 512     # rows per block of a GEMM, attention or the lm_head


# ---------------------------------------------------------------------------
# The approximate multiplier (PC3_TR on 8-bit mantissas)
# ---------------------------------------------------------------------------

def pc3_tr_mantissa(b, a):
    """Approximate product of the mantissas ``1.fx`` and ``1.fw``, given as
    the 8-bit integers ``b = 128 + fx`` (input, the multiplier that drives
    the word lines) and ``a = 128 + fw`` (weight, the multiplicand stored in
    SRAM), as a value in [1, 4). Integer arithmetic only, on numpy or JAX
    arrays alike.

    PC3 (paper 3.3): the three top bits of the multiplier select one
    pre-computed line holding ``a * (b7 b6 b5)`` (exact, ``b7`` is the
    implicit 1), shifted by 5; the five lower lines ``a << i`` for set bits
    ``b_i`` join it by wired OR. TR keeps only the top 8 of the 16 product
    columns. The result is renormalised to 8 bits by its top bit.
    """
    head = (a * (4 + 2 * ((b >> 6) & 1) + ((b >> 5) & 1))) << 5
    low = 0
    for i in range(5):
        low = low | (((b >> i) & 1) * (a << i))
    p = (head | low) & 0xFF00
    top = (p >> 15) & 1
    man = (p >> (7 + top)) & 0xFF
    return man * (1 + top) / 128.0


def pc3_tr_table() -> np.ndarray:
    """``G[fx, fw]``: :func:`pc3_tr_mantissa` over all 128 x 128 pairs."""
    b = (128 + np.arange(128, dtype=np.int64))[:, None]   # multiplier
    a = (128 + np.arange(128, dtype=np.int64))[None, :]   # multiplicand
    return pc3_tr_mantissa(b, a).astype(np.float32)


def _split_bf16(v):
    """bf16 array -> (signed power of two as bf16, 7-bit fraction index).
    Zeros and subnormals give a power of 0 (they multiply to 0)."""
    bits = jax.lax.bitcast_convert_type(v.astype(jnp.bfloat16), jnp.uint16)
    bits = bits.astype(jnp.int32)
    exp = (bits >> 7) & 0xFF
    pow_bits = (bits & 0x8000) | (exp << 7)
    pow2 = jax.lax.bitcast_convert_type(pow_bits.astype(jnp.uint16),
                                        jnp.bfloat16)
    pow2 = jnp.where(exp > 0, pow2, jnp.zeros_like(pow2))
    return pow2, bits & 0x7F


def approx_dot(x, w):
    """(T, K) @ (K, N) -> (T, N) float32 under the PC3_TR multiplier.

    Swept over the weight's fraction ``j``: ``A_j = sign * 2^Ex * G[fx, j]``
    (computed from the small input's bits) and ``B_j`` holds ``sign * 2^Ew``
    where the weight's fraction is ``j``, so the large operand is only
    selected, never gathered."""
    xp, xf = _split_bf16(x)
    wp, wf = _split_bf16(w)
    zero = jnp.zeros_like(wp)

    def body(j, acc):
        g = pc3_tr_mantissa(128 + xf, 128 + j).astype(jnp.bfloat16)
        b_j = jnp.where(wf == j, wp, zero)
        return acc + jnp.dot(xp * g, b_j, preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, 128, body,
                             jnp.zeros((x.shape[0], w.shape[1]), jnp.float32))


# ---------------------------------------------------------------------------
# float8 rounding (the control)
# ---------------------------------------------------------------------------

def to_fp8(v, axis: int):
    """Round to float8 e4m3 with a power-of-two scale along ``axis``."""
    v = v.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, 2.0 ** jnp.ceil(jnp.log2(amax / 448.0)), 1.0)
    q = (v / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------

def _gemm(x, w, gemm: str, quant: str):
    """x (T, K) float32, w (K, N) as stored -> (T, N) float32."""
    if quant == "fp8":
        x, w = to_fp8(x, 1), to_fp8(w, 0)
    if gemm == "pc3_tr":
        return approx_dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _rows(fn, x, *rest):
    """Apply ``fn(x_block, *rest)`` over row blocks of ``x``."""
    t = x.shape[0]
    blocks = x.reshape(t // ROW_BLOCK, ROW_BLOCK, *x.shape[1:])
    out = jax.lax.map(lambda xb: fn(xb, *rest), blocks)
    return out.reshape(t, *out.shape[2:])


def layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def rotary(x, pos, theta: float, fraction: float):
    """Rotate-half RoPE on the first ``fraction`` of the head dim.
    x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    rd = int(d * fraction)
    freqs = 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float32) / rd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, x[..., rd:]], -1)


def attention(q, k, v, seg, pos):
    """Causal GQA attention within each packed segment.
    q (T, H, D), k/v (T, KH, D)."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(d)

    def block(args):
        qb, sb, pb = args
        s = jnp.einsum("qhd,khd->hqk", qb * scale, k, precision=HIGHEST)
        mask = (sb[:, None] == seg[None, :]) & (pos[None, :] <= pb[:, None])
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    nb = t // ROW_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, ROW_BLOCK, h, d),
                              seg.reshape(nb, ROW_BLOCK),
                              pos.reshape(nb, ROW_BLOCK)))
    return out.reshape(t, h, d)


def activation(h, act: str):
    if act == "gelu_tanh":
        return jax.nn.gelu(h, approximate=True)
    if act == "relu2":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(act)


@functools.partial(jax.jit, static_argnames=("arch", "gemm", "quant"))
def _attn_block(x, seg, pos, w, *, arch, gemm, quant):
    arch = dict(arch)
    t = x.shape[0]
    nh, kh, hd = arch["n_heads"], arch["kv_heads"], arch["head_dim"]
    y = layer_norm(x, w["ln1_scale"], w["ln1_bias"])

    def proj(name, n_out):
        out = _rows(lambda xb, m: _gemm(xb, m, gemm, quant), y, w[name])
        return out + w[name + "_b"].astype(jnp.float32)

    q = proj("wq", nh * hd).reshape(t, nh, hd)
    k = proj("wk", kh * hd).reshape(t, kh, hd)
    v = proj("wv", kh * hd).reshape(t, kh, hd)
    q = rotary(q, pos, arch["rope_theta"], arch["rotary_fraction"])
    k = rotary(k, pos, arch["rope_theta"], arch["rotary_fraction"])
    o = attention(q, k, v, seg, pos).reshape(t, nh * hd)
    o = _rows(lambda ob, m: _gemm(ob, m, gemm, quant), o, w["wo"])
    return x + o + w["wo_b"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("arch", "gemm", "quant"))
def _mlp_block(x, w, *, arch, gemm, quant):
    arch = dict(arch)

    def block(xb, wi, wi_b, wo, wo_b):
        y = layer_norm(xb, w["ln2_scale"], w["ln2_bias"])
        h = _gemm(y, wi, gemm, quant) + wi_b.astype(jnp.float32)
        h = activation(h, arch["act"])
        return _gemm(h, wo, gemm, quant) + wo_b.astype(jnp.float32)

    return x + _rows(block, x, w["wi"], w["wi_b"], w["wo"], w["wo_b"])


@functools.partial(jax.jit, static_argnames=("gemm", "quant"))
def _head(x, scale, bias, lm_head, targets, *, gemm, quant):
    """Final LayerNorm and lm_head by row blocks. Returns, per row, the
    best logit, the logit of each target column and the argmax."""

    def block(xb, tb):
        logits = _gemm(layer_norm(xb, scale, bias), lm_head, gemm, quant)
        best = logits.max(-1)
        at = jnp.take_along_axis(logits, tb, axis=1)
        return best, at, jnp.argmax(logits, -1).astype(jnp.int32)

    t = x.shape[0]
    nb = t // ROW_BLOCK
    best, at, top = jax.lax.map(
        lambda a: block(*a), (x.reshape(nb, ROW_BLOCK, -1),
                              targets.reshape(nb, ROW_BLOCK, -1)))
    return (best.reshape(t), at.reshape(t, -1), top.reshape(t))


_EMBED = jax.jit(lambda e, tokens: jnp.take(e, tokens, axis=0).astype(
    jnp.float32))

ATTN_LEAVES = ("ln1_scale", "ln1_bias", "wq", "wq_b", "wk", "wk_b", "wv",
               "wv_b", "wo", "wo_b")
MLP_LEAVES = ("ln2_scale", "ln2_bias", "wi", "wi_b", "wo", "wo_b")


def forward(arch: Dict, get: Callable, tokens, seg, pos, targets, *,
            gemm: str = "exact", quant: str = "none"):
    """Reference logits summary for packed rows.

    ``get(path, layer)`` returns one weight as stored (a layer's slice for
    the stacked ``blocks`` leaves), made from the seed. ``targets`` (T, n)
    are token ids whose logits are read at each row. Returns numpy
    ``(best (T,), at (T, n), argmax (T,))``.
    """
    arch_key = tuple(sorted(arch.items()))
    x = _EMBED(get(("embedding",), None), jnp.asarray(tokens))
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    for layer in range(arch["n_layers"]):
        w = {n: get(("blocks", "attn", n), layer) for n in ATTN_LEAVES}
        x = _attn_block(x, seg, pos, w, arch=arch_key, gemm=gemm, quant=quant)
        del w
        w = {n: get(("blocks", "ffn", n), layer) for n in MLP_LEAVES}
        x = _mlp_block(x, w, arch=arch_key, gemm=gemm, quant=quant)
        del w
    out = _head(x, get(("final_ln_scale",), None),
                get(("final_ln_bias",), None), get(("lm_head",), None),
                jnp.asarray(targets), gemm=gemm, quant=quant)
    return tuple(np.asarray(o) for o in out)


def reference_arch(arch: Dict) -> Dict:
    """The keys of a configuration's ``model`` section that the reference
    reads (hashable values only)."""
    keys = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "act", "rope_theta", "rotary_fraction")
    return {k: arch[k] for k in keys}
