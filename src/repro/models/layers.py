"""Common neural layers with pluggable (exact | DAISM) matmul backend.

Every parameter GEMM routes through :func:`dense`, which resolves its
numerics per op-site through the architecture's injectable approximation
policy (``cfg.approx_policy``, see :mod:`repro.policy`) — the paper's
technique as a first-class framework feature, addressable per layer
(DESIGN.md §2). Dynamic attention GEMMs (qk^T, att@v) default to exact —
DAISM multiplies a *stationary* SRAM-resident operand against streamed
inputs, and neither attention operand is stationary — but a policy rule
carrying the ``:flash`` token (``*/attn/*=pc3_tr:flash``) opts the
``.../attn/kernel`` site (OpKind.ATTN_QK) into the fused Pallas
flash-attention kernel, where scores and (optionally approximate) products
stay VMEM-resident. Cached decode shapes always fall back to the exact jnp
path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.parallel.sharding import constrain, current_sharder
from repro.parallel.unroll import unroll_for
from repro.policy import OpKind, attention_kernel, policy_dot, resolve_site

from .common import ArchConfig
from .module import Ctx, lecun_init, normal_init, ones_init, zeros_init

# ---------------------------------------------------------------------------
# Dense / norms
# ---------------------------------------------------------------------------

def dense(ctx: Ctx, name: str, x: jnp.ndarray, d_out: int, cfg: ArchConfig,
          *, axes=("embed", "mlp"), use_bias: bool = False,
          init=None, kind: OpKind = OpKind.DENSE) -> jnp.ndarray:
    d_in = x.shape[-1]
    w = ctx.param(name, (d_in, d_out), cfg.param_dtype,
                  init or lecun_init(), axes=axes)
    # init-mode traces run outside the model's site scopes (their outputs
    # are discarded), so only apply-mode resolutions are recorded
    out = policy_dot(cfg.approx_policy, x, w, name=name, kind=kind,
                     record=ctx.mode == "apply")
    if use_bias:
        b = ctx.param(name + "_b", (d_out,), cfg.param_dtype, zeros_init(),
                      axes=(axes[-1],))
        out = out + b.astype(out.dtype)
    return out


def norm(ctx: Ctx, name: str, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    d = x.shape[-1]
    scale = ctx.param(name + "_scale", (d,), "float32", ones_init(),
                      axes=("act_embed",))
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        bias = ctx.param(name + "_bias", (d,), "float32", zeros_init(),
                         axes=("act_embed",))
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + 1e-5) * scale + bias
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * lax.rsqrt(ms + 1e-6) * scale
    return y.astype(x.dtype)


def activate(h: jnp.ndarray, g: Optional[jnp.ndarray], act: str) -> jnp.ndarray:
    if act == "swiglu":
        return jax.nn.silu(g) * h
    if act == "geglu":
        return jax.nn.gelu(g) * h
    if act == "relu2":
        r = jax.nn.relu(h)
        return r * r
    if act == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(act)


def mlp(ctx: Ctx, x: jnp.ndarray, cfg: ArchConfig, d_ff: Optional[int] = None,
        *, use_bias: bool = False) -> jnp.ndarray:
    d_ff = d_ff or cfg.d_ff
    gated = cfg.act in ("swiglu", "geglu")
    h = dense(ctx, "wi", x, d_ff, cfg, axes=("embed", "mlp"), use_bias=use_bias)
    g = dense(ctx, "wg", x, d_ff, cfg, axes=("embed", "mlp")) if gated else None
    h = activate(h, g, cfg.act)
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"))
    return dense(ctx, "wo", h, x.shape[-1], cfg, axes=("mlp", "embed"),
                 use_bias=use_bias)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (online-softmax over KV chunks; causal / window / cross)
# ---------------------------------------------------------------------------

def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           q_pos: jnp.ndarray, kv_pos: jnp.ndarray, *,
           causal: bool, window: int = 0, chunk: int = 1024,
           softcap: float = 0.0, unroll_category: str = "attn",
           score_dtype=jnp.float32, policy=None,
           record: bool = True) -> jnp.ndarray:
    """Online-softmax attention (never materializes the full S x S matrix).

    q: (B, Sq, H, D); k, v: (B, Skv, KH, D); *_pos: (Sq,) / (Skv,) absolute
    positions used for causal/window masking (decode passes a 1-length q_pos).
    Either may instead be (B, Sq) / (B, Skv) for per-row positions — the
    slot-cache serving path, where every batch row is an independent request
    at its own sequence offset (masks then cost an extra batch dim, so the
    shared-position fast path is kept for train/prefill).

    With ``policy`` set, the call resolves the ambient ``kernel`` site
    (OpKind.ATTN_QK) and, when the effective config requests the flash
    kernel and the shape is eligible (shared 1-D positions, no window, no
    softcap, and sq == skv when causal — the kernel masks by index, which
    matches position masking for the monotone position vectors every
    non-cached path uses), dispatches to the fused Pallas flash attention.
    Ineligible shapes (windowed, softcapped, per-row serving, cached decode)
    resolve — and are recorded — as EXACT and take the jnp path below.
    """
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    if policy is not None:
        flash_ok = (jnp.ndim(q_pos) == 1 and jnp.ndim(kv_pos) == 1
                    and window == 0 and softcap == 0.0
                    and (not causal or sq == skv))
        macs = 2 * b * h * sq * skv * d  # qk^T + att@v
        # dims of one head's qk^T contraction (the flash kernel's grid unit)
        site_cfg = resolve_site(policy, "kernel", OpKind.ATTN_QK, q.dtype,
                                record=record, macs=macs,
                                dims=(sq, d, skv),
                                attn_eligible=flash_ok)
        if flash_ok and site_cfg.attn_kernel == "flash":
            return attention_kernel(site_cfg)(q, k, v, causal)
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scale = 1.0 / np.sqrt(d)
    sd = jnp.dtype(score_dtype)
    qf = (q.astype(jnp.float32) * scale).astype(sd)

    per_row = jnp.ndim(q_pos) == 2 or jnp.ndim(kv_pos) == 2
    if per_row:
        q_pos = jnp.broadcast_to(
            q_pos if jnp.ndim(q_pos) == 2 else q_pos[None], (b, sq))
        kv_pos = jnp.broadcast_to(
            kv_pos if jnp.ndim(kv_pos) == 2 else kv_pos[None], (b, skv))

    chunk = min(chunk, skv)
    n_chunks = int(np.ceil(skv / chunk))
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos,
                         ((0, 0), (0, pad)) if per_row else (0, pad),
                         constant_values=2**30)
    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    pc = (kv_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
          if per_row else kv_pos.reshape(n_chunks, chunk))

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs  # (B, C, H, D), (B, C, H, D), (C,) | (B, C)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(sd),
                       preferred_element_type=sd)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        if per_row:  # (B, Sq, C) masks from (B, C) x (B, Sq) positions
            mask = jnp.ones((b, sq, kb.shape[1]), bool)
            if causal:
                mask &= pb[:, None, :] <= q_pos[:, :, None]
            if window > 0:
                mask &= pb[:, None, :] > (q_pos[:, :, None] - window)
            mask &= pb[:, None, :] < 2**30  # padding
            mask = mask[:, None]            # broadcast over heads
        else:
            mask = jnp.ones((sq, kb.shape[1]), bool)
            if causal:
                mask &= pb[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= pb[None, :] > (q_pos[:, None] - window)
            mask &= pb[None, :] < 2**30  # padding
            mask = mask[None, None]
        s = jnp.where(mask, s, jnp.asarray(-1e30, sd))
        m_new = jnp.maximum(m, s.max(-1).astype(jnp.float32))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s.astype(jnp.float32) - m_new[..., None]).astype(sd)
        l_new = l * corr + p.astype(jnp.float32).sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(sd),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (kc, vc, pc),
                              unroll=min(unroll_for(unroll_category), n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sq, H, D)


def _paged_kv_attend(q, k, v, ck, cv, widx, phys_read, positions, *,
                     causal, window, chunk, softcap, unroll_category):
    """Scatter new K/V into the physical page pool, gather each row's pages,
    attend. Head-local by construction (no cross-head reduction), so it runs
    unchanged as a shard_map body with q/k/v/pool split over the head dims —
    the block-table gather/scatter stays on-shard."""
    p_cells = ck.shape[0]
    ck = ck.at[widx].set(k.astype(ck.dtype), mode="drop")
    cv = cv.at[widx].set(v.astype(cv.dtype), mode="drop")
    idx = jnp.minimum(phys_read, p_cells - 1)
    gk = jnp.take(ck, idx, axis=0)  # (B, K, KH, HD)
    gv = jnp.take(cv, idx, axis=0)
    out = attend(q, gk, gv, positions, jnp.arange(gk.shape[1]),
                 causal=causal, window=window, chunk=chunk, softcap=softcap,
                 unroll_category=unroll_category)
    return out, ck, cv


def _paged_shard_axis(sharder, q_shape, pool_shape) -> Optional[str]:
    """Mesh axis the paged-attention shard_map splits heads over, or None.

    Eligible only when the sharder lands the *same single* mesh axis on
    both the activation heads dim and the pool's kv_heads dim (its
    divisibility fallback already dropped axes that do not divide, so an
    indivisible head count degrades to the replicated GSPMD path rather
    than an error)."""
    if sharder is None:
        return None
    qspec = sharder.spec((None, None, "act_heads", None), q_shape)
    pspec = sharder.spec((None, "act_kv_heads", None), pool_shape)
    axq = qspec[2] if len(qspec) > 2 else None
    axp = pspec[1] if len(pspec) > 1 else None
    return axq if isinstance(axq, str) and axq == axp else None


def self_attention(ctx: Ctx, x: jnp.ndarray, cfg: ArchConfig, *,
                   positions: jnp.ndarray, cache: Optional[dict] = None,
                   causal: bool = True, n_heads: int = 0, kv_heads: int = 0,
                   head_dim: int = 0, use_bias: bool = False,
                   unroll_category: str = "attn"
                   ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """GQA self-attention. With ``cache`` (decode) appends K/V at
    ``cache['pos']`` and attends over the whole cache."""
    nh = n_heads or cfg.n_heads
    kh = kv_heads or cfg.kv_heads
    hd = head_dim or cfg.head_dim
    b, s, _ = x.shape
    q = dense(ctx, "wq", x, nh * hd, cfg, axes=("embed", "heads"),
              use_bias=use_bias).reshape(b, s, nh, hd)
    k = dense(ctx, "wk", x, kh * hd, cfg, axes=("embed", "kv_heads"),
              use_bias=use_bias).reshape(b, s, kh, hd)
    v = dense(ctx, "wv", x, kh * hd, cfg, axes=("embed", "kv_heads"),
              use_bias=use_bias).reshape(b, s, kh, hd)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))

    new_cache = None
    if cache is not None and "write_idx" in cache:
        # paged KV cache (repro.serve): per-layer physical page pool
        # k/v (P, KH, HD) where P = num_blocks * block_size; the request's
        # block table is pre-resolved by DecoderLM.paged_step into
        #   write_idx (B, S): physical cell of each new token (>= P: drop —
        #     padding rows / chunk padding beyond the reservation), and
        #   phys_read (B, K): physical cell of every *logical* kv position
        #     0..K-1 (clipped gather; unmapped entries land beyond the
        #     row's write position, so the causal mask excludes them).
        ck, cv = cache["k"], cache["v"]
        widx, phys_read = cache["write_idx"], cache["phys_read"]
        body = functools.partial(
            _paged_kv_attend, causal=causal, window=cfg.window,
            chunk=cfg.attn_chunk, softcap=cfg.logit_softcap,
            unroll_category=unroll_category)
        sharder = current_sharder()
        ax = _paged_shard_axis(sharder, q.shape, ck.shape)
        if ax is not None:
            # tensor-parallel serving: shard_map over the head dims keeps
            # every pool scatter/gather local to its shard; attend() is
            # per-head so the body needs no collectives (GQA grouping is
            # contiguous: q heads [j*nh/n, ...) read kv heads [j*kh/n, ...))
            from jax.sharding import PartitionSpec as P

            hspec = P(None, None, ax, None)
            pspec = P(None, ax, None)
            out, ck, cv = jax.shard_map(
                body, mesh=sharder.mesh,
                in_specs=(hspec, hspec, hspec, pspec, pspec, P(), P(), P()),
                out_specs=(hspec, pspec, pspec),
                check_vma=False)(q, k, v, ck, cv, widx, phys_read, positions)
        else:
            out, ck, cv = body(q, k, v, ck, cv, widx, phys_read, positions)
        ck = constrain(ck, ("cache_seq", "act_kv_heads", None))
        cv = constrain(cv, ("cache_seq", "act_kv_heads", None))
        out = out.reshape(b, s, nh * hd)
        out = dense(ctx, "wo", out, x.shape[-1], cfg, axes=("heads", "embed"),
                    use_bias=use_bias)
        return out, dict(k=ck, v=cv)
    if cache is not None:
        ck, cv, pos = cache["k"], cache["v"], cache["pos"]
        size = ck.shape[1]
        ring = "abs_pos" in cache
        slot = lax.rem(pos, size) if ring else pos
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        ck = constrain(ck, ("cache_batch", "cache_seq", "act_kv_heads", None))
        cv = constrain(cv, ("cache_batch", "cache_seq", "act_kv_heads", None))
        new_cache = dict(k=ck, v=cv, pos=pos + s)
        if ring:
            ap = lax.dynamic_update_slice(
                cache["abs_pos"], positions.astype(jnp.int32), (slot,))
            new_cache["abs_pos"] = ap
            kv_pos = jnp.where(ap < 0, 2**30, ap)  # empty slots masked out
        else:
            kv_pos = jnp.arange(size)
        out = attend(q, ck, cv, positions, kv_pos, causal=causal,
                     window=cfg.window, chunk=cfg.attn_chunk,
                     softcap=cfg.logit_softcap,
                     unroll_category=unroll_category)
    else:
        out = attend(q, k, v, positions, positions, causal=causal,
                     window=cfg.window, chunk=cfg.attn_chunk,
                     softcap=cfg.logit_softcap,
                     unroll_category=unroll_category,
                     score_dtype=cfg.attn_score_dtype,
                     policy=cfg.approx_policy,
                     record=ctx.mode == "apply")
    out = out.reshape(b, s, nh * hd)
    out = dense(ctx, "wo", out, x.shape[-1], cfg, axes=("heads", "embed"),
                use_bias=use_bias)
    return out, new_cache


def cross_attention(ctx: Ctx, x: jnp.ndarray, kv_src: jnp.ndarray,
                    cfg: ArchConfig, *, use_bias: bool = False) -> jnp.ndarray:
    """Full (non-causal) cross attention against encoder/image states."""
    nh, kh, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    b, s, _ = x.shape
    skv = kv_src.shape[1]
    q = dense(ctx, "wq", x, nh * hd, cfg, axes=("embed", "heads"),
              use_bias=use_bias).reshape(b, s, nh, hd)
    k = dense(ctx, "wk", kv_src, kh * hd, cfg, axes=("embed", "kv_heads"),
              use_bias=use_bias).reshape(b, skv, kh, hd)
    v = dense(ctx, "wv", kv_src, kh * hd, cfg, axes=("embed", "kv_heads"),
              use_bias=use_bias).reshape(b, skv, kh, hd)
    out = attend(q, k, v, jnp.arange(s), jnp.arange(skv), causal=False,
                 chunk=skv,  # single chunk: small KV, uniform attn trips
                 score_dtype=cfg.attn_score_dtype,
                 policy=cfg.approx_policy,
                 record=ctx.mode == "apply")
    out = out.reshape(b, s, nh * hd)
    return dense(ctx, "wo", out, x.shape[-1], cfg, axes=("heads", "embed"),
                 use_bias=use_bias)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed(ctx: Ctx, tokens: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    e = ctx.param("embedding", (cfg.vocab, cfg.d_model), cfg.param_dtype,
                  normal_init(1.0), axes=("vocab", "embed"))
    x = jnp.take(e, tokens, axis=0)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def unembed(ctx: Ctx, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        e = ctx.param("embedding", (cfg.vocab, cfg.d_model), cfg.param_dtype,
                      normal_init(1.0), axes=("vocab", "embed"))
        logits = policy_dot(cfg.approx_policy, x, e.T, name="lm_head",
                            kind=OpKind.LM_HEAD,
                            record=ctx.mode == "apply")
    else:
        logits = dense(ctx, "lm_head", x, cfg.vocab, cfg,
                       axes=("embed", "vocab"), kind=OpKind.LM_HEAD)
    return constrain(logits, ("act_batch", "act_seq", "act_vocab"))

