"""Decoder-only transformer family: dense, MoE, VLM (cross-attn), enc-dec.

Layer stacks are *stacked-parameter scans* (MaxText-style): one layer's
params are initialized under ``jax.vmap`` over a leading ``layers`` axis and
consumed with ``lax.scan``, keeping HLO size O(1) in depth — essential for
96-layer/340B dry-runs on a 512-device mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import constrain
from repro.parallel.unroll import unroll_for
from repro.policy import OpKind, plan_segments, site_scope

from .common import ArchConfig
from .layers import (cross_attention, dense, embed, mlp, norm,
                     self_attention, unembed)
from .module import Ctx, apply_model, init_model
from .moe import moe_ffn

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Op-site probes (policy segmentation)
# ---------------------------------------------------------------------------

def mlp_sites(cfg: ArchConfig, base: str):
    """(path, kind) probe sites of one dense MLP under ``base``."""
    names = ("wi", "wg", "wo") if cfg.act in ("swiglu", "geglu") else \
        ("wi", "wo")
    return [(f"{base}/{n}", OpKind.DENSE) for n in names]


def attn_sites(base: str):
    sites = [(f"{base}/{n}", OpKind.DENSE) for n in ("wq", "wk", "wv", "wo")]
    # the dynamic qk^T/att@v contraction pair resolves as one ATTN_QK site
    # (models/layers.attend) — it must be probed too, or a depth rule that
    # only changes attention dispatch would be invisible to segmentation
    sites.append((f"{base}/kernel", OpKind.ATTN_QK))
    return sites


def decoder_block_sites(cfg: ArchConfig, i: int, prefix: str = "decoder"):
    """Every contraction site of decoder layer ``i`` — must mirror the paths
    the traced block produces (Ctx scopes + dense leaf names)."""
    base = f"{prefix}/layer_{i}"
    sites = attn_sites(f"{base}/attn")
    if cfg.n_experts:
        names = ("w_in", "w_gate", "w_out") if cfg.act in ("swiglu", "geglu") \
            else ("w_in", "w_out")
        sites += [(f"{base}/ffn/{n}", OpKind.MOE_EXPERT) for n in names]
    else:
        sites += mlp_sites(cfg, f"{base}/ffn")
    return sites


def clip_segments(segments, lo: int, hi: int):
    """Intersect policy segments with the layer range [lo, hi)."""
    return tuple((max(a, lo), min(b, hi))
                 for a, b in segments if a < hi and b > lo)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def decoder_block(ctx: Ctx, cfg: ArchConfig, x, *, positions, cache=None,
                  causal=True):
    """Pre-norm self-attention + FFN (dense or MoE). Returns (x, cache, aux)."""
    use_bias = cfg.norm == "layernorm"  # starcoder2/whisper-style
    with ctx.scope("attn"):
        h, new_cache = self_attention(
            ctx, norm(ctx, "ln1", x, cfg), cfg, positions=positions,
            cache=cache, causal=causal, use_bias=use_bias)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    with ctx.scope("ffn"):
        y = norm(ctx, "ln2", x, cfg)
        if cfg.n_experts:
            h, aux = moe_ffn(ctx, y, cfg)
        else:
            h = mlp(ctx, y, cfg, use_bias=use_bias)
    x = x + h
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, new_cache, aux


def cross_block(ctx: Ctx, cfg: ArchConfig, x, kv_src):
    """Cross-attention block (VLM / whisper decoder insert)."""
    with ctx.scope("xattn"):
        h = cross_attention(ctx, norm(ctx, "ln1", x, cfg), kv_src, cfg)
    x = x + h
    with ctx.scope("ffn"):
        x = x + mlp(ctx, norm(ctx, "ln2", x, cfg), cfg)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


# ---------------------------------------------------------------------------
# Stacked-layer machinery
# ---------------------------------------------------------------------------

def stacked_init(layer_fn, rng, n_layers: int, *args, **kw):
    """Init a layer stack: returns (stacked_params, axes with 'layers' prepended)."""
    keys = jax.random.split(rng, n_layers)
    holder = {}

    def one(k):
        ctx = Ctx("init", rng=k)
        layer_fn(ctx, *args, **kw)
        holder["axes"] = ctx.axes
        return ctx.params

    params = jax.vmap(one)(keys)
    axes = {path: ("layers",) + a for path, a in holder["axes"].items()}
    return params, axes


def apply_remat(fn, remat: str):
    """Wrap a params-level function (pytree args only) with a remat policy.

    'dots'    saves every matmul output (incl. batched attention scores);
    'dots_nb' saves only non-batched matmuls (weight GEMMs) — attention
              scores are recomputed, the sweet spot found in the §Perf log;
    'full'    recomputes everything.
    """
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if remat == "dots_nb":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if remat == "full":
        return jax.checkpoint(fn)
    return fn


def scan_layers(layer_fn, stacked_params, x, *, cache=None,
                unroll: int = 0, remat: str = "none", **kw):
    """Run x through a stacked-param layer scan.

    cache (optional): pytree stacked on layer dim; scanned alongside params
    and the per-layer updated cache is emitted as a stacked output.
    remat: activation checkpoint policy applied per layer (params-level, so
    jax.checkpoint sees only pytree arguments).
    """
    inner = apply_remat(
        lambda p, h, c: apply_model(layer_fn, p, h, cache=c, **kw), remat)

    def body(carry, layer_in):
        h, aux_acc = carry
        p, c = layer_in
        h, new_c, aux = inner(p, h, c)
        return (h, aux_acc + aux), new_c

    (x, aux), new_cache = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stacked_params, cache),
        unroll=unroll or unroll_for("layers"))
    return x, new_cache, aux


def scan_policy_segments(layer_fn, stacked_params, x, *, segments,
                         base: int = 0, cache=None, remat: str = "none",
                         prefix: str = "layer", **kw):
    """Run consecutive ``scan_layers`` segments, one per policy segment.

    ``segments`` are (lo, hi) *global* layer ranges (plan_segments); the
    stacked params/cache are indexed relative to ``base`` (the global index
    of their row 0). Each segment is traced under the site scope
    ``{prefix}_{lo}``, so per-depth policy rules resolve against the
    segment's first layer — valid because every layer in a segment resolves
    identically by construction. A uniform policy yields one segment and
    the exact HLO the un-segmented scan produced.
    """
    aux_total = jnp.zeros((), jnp.float32)
    parts = []
    for lo, hi in segments:
        sub = jax.tree.map(lambda p: p[lo - base:hi - base], stacked_params)
        subc = (None if cache is None else
                jax.tree.map(lambda c: c[lo - base:hi - base], cache))
        with site_scope(f"{prefix}_{lo}", repeat=hi - lo):
            x, nc, aux = scan_layers(layer_fn, sub, x, cache=subc,
                                     remat=remat, **kw)
        aux_total = aux_total + aux
        parts.append(nc)
    new_cache = None
    if cache is not None:
        new_cache = (parts[0] if len(parts) == 1 else
                     jax.tree.map(lambda *t: jnp.concatenate(t, 0), *parts))
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# Decoder-only LM (dense + MoE + VLM)
# ---------------------------------------------------------------------------

class DecoderLM:
    """Dense / MoE / VLM decoder LM with a uniform train/prefill/decode API."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.is_vlm = cfg.cross_every > 0
        # maximal layer runs with identical resolved numerics (one scan each)
        self.segments = plan_segments(
            cfg.approx_policy,
            functools.partial(decoder_block_sites, cfg), 0, cfg.n_layers)

    # -- init ------------------------------------------------------------
    def init(self, rng, *, abstract: bool = False):
        def build(rng_):
            k_embed, k_layers, k_cross, k_head = jax.random.split(rng_, 4)
            params: Params = {}
            axes = {}
            ctx = Ctx("init", rng=k_embed)
            embed(ctx, jnp.zeros((1, 1), jnp.int32), self.cfg)
            if not self.cfg.tie_embeddings:
                x0 = jnp.zeros((1, 1, self.cfg.d_model), self.cfg.compute_dtype)
                norm(ctx, "final_ln", x0, self.cfg)
                unembed(ctx, x0, self.cfg)
            else:
                norm(ctx, "final_ln",
                     jnp.zeros((1, 1, self.cfg.d_model), self.cfg.compute_dtype),
                     self.cfg)
            params.update(ctx.params)
            axes.update(ctx.axes)

            pos0 = jnp.zeros((1,), jnp.int32)
            x0 = jnp.zeros((1, 1, self.cfg.d_model), self.cfg.compute_dtype)
            lp, la = stacked_init(
                lambda c, xx: decoder_block(c, self.cfg, xx, positions=pos0),
                k_layers, self.cfg.n_layers, x0)
            params["blocks"] = lp
            axes.update({("blocks",) + p: a for p, a in la.items()})

            if self.is_vlm:
                kv0 = jnp.zeros((1, 1, self.cfg.d_model), self.cfg.compute_dtype)
                cp, ca = stacked_init(
                    lambda c, xx: cross_block(c, self.cfg, xx, kv0),
                    k_cross, self.n_cross, x0)
                params["cross_blocks"] = cp
                axes.update({("cross_blocks",) + p: a for p, a in ca.items()})
            return params, axes

        if abstract:
            axes_holder = {}

            def build_shapes(r):
                p, a = build(r)
                axes_holder.update(a)
                return p

            shapes = jax.eval_shape(build_shapes, rng)
            return shapes, axes_holder
        return build(rng)

    @property
    def n_cross(self) -> int:
        return self.cfg.n_layers // self.cfg.cross_every if self.is_vlm else 0

    # -- forward (train / prefill) ----------------------------------------
    def forward(self, params: Params, batch: Dict[str, jnp.ndarray]):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.arange(s)
        ctx = Ctx("apply", params=params)

        layer = functools.partial(decoder_block, positions=positions,
                                  causal=True)
        layer_fn = lambda c, xx, cache=None: layer(c, cfg, xx, cache=cache)

        with site_scope("decoder"):
            x = embed(ctx, tokens, cfg)
            if not self.is_vlm:
                x, _, aux = scan_policy_segments(
                    layer_fn, params["blocks"], x, segments=self.segments,
                    remat=cfg.remat)
            else:
                img = batch["image_embeds"].astype(x.dtype)
                aux = jnp.zeros((), jnp.float32)
                per = cfg.cross_every
                for g in range(self.n_cross):
                    x, _, a = scan_policy_segments(
                        layer_fn, params["blocks"], x,
                        segments=clip_segments(self.segments, g * per,
                                               (g + 1) * per),
                        remat=cfg.remat)
                    aux = aux + a
                    cparams = jax.tree.map(lambda p: p[g],
                                           params["cross_blocks"])
                    cross_fn = apply_remat(
                        lambda cp, xx: apply_model(
                            lambda c, h: cross_block(c, cfg, h, img), cp, xx),
                        cfg.remat)
                    with site_scope(f"cross_{g}"):
                        x = cross_fn(cparams, x)
            x = norm(ctx, "final_ln", x, cfg)
            logits = unembed(ctx, x, cfg)
        return logits, aux

    # -- KV cache ----------------------------------------------------------
    def init_cache(self, batch_size: int, max_seq: int, *,
                   abstract: bool = False):
        cfg = self.cfg
        ring = bool(cfg.window) and cfg.window < max_seq
        size = min(cfg.window, max_seq) if ring else max_seq
        kshape = (cfg.n_layers, batch_size, size, cfg.kv_heads, cfg.head_dim)

        def mk(shape, dtype, fill=0):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jnp.full(shape, fill, dtype)

        cache = {
            "k": mk(kshape, jnp.dtype(cfg.compute_dtype)),
            "v": mk(kshape, jnp.dtype(cfg.compute_dtype)),
            "pos": mk((), jnp.int32),
        }
        if ring:  # ring buffer: absolute position of each slot, -1 = empty
            cache["abs_pos"] = mk((cfg.n_layers, size), jnp.int32, fill=-1)
        return cache

    # -- paged KV cache (block tables; repro.serve) -------------------------
    def init_paged_cache(self, num_blocks: int, block_size: int, *,
                         abstract: bool = False):
        """Physical page pool: ``k/v (layers, num_blocks*block_size, KH, HD)``.

        Logical sequences live in ``repro.serve.kv_pool`` block tables; the
        pool itself has no batch dimension — concurrency is bounded by pages,
        not rows. Windowed (ring-buffer) models are not supported: a paged
        pool never rolls, it frees whole pages at retirement.
        """
        cfg = self.cfg
        if cfg.window:
            # backstop for direct callers; the serving engine rejects this
            # combination earlier via EngineConfig.validate_for_model
            raise ValueError(
                f"paged KV cache needs window=0 (got window={cfg.window}: "
                "ring buffers roll in place, pages are freed whole)")
        if self.is_vlm:
            raise NotImplementedError(
                "paged serving does not cover VLM cross-attention blocks")
        cells = num_blocks * block_size
        kshape = (cfg.n_layers, cells, cfg.kv_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.compute_dtype)
        if abstract:
            return {"k": jax.ShapeDtypeStruct(kshape, dt),
                    "v": jax.ShapeDtypeStruct(kshape, dt)}
        return {"k": jnp.zeros(kshape, dt), "v": jnp.zeros(kshape, dt)}

    @staticmethod
    def paged_cache_axes():
        """Logical axes of each paged-pool leaf (``init_paged_cache`` k/v)
        for Sharder placement: layers and pool cells stay whole on every
        device, kv heads split over the model axis — the same split the
        paged attention shard_map uses, so block-table gather/scatter is
        always shard-local (repro.serve tensor-parallel serving)."""
        return ("layers", None, "act_kv_heads", None)

    def paged_step(self, params: Params, tokens: jnp.ndarray, cache, *,
                   block_size: int):
        """One fixed-shape step over block tables — decode (S=1), chunked
        prefill (S=chunk), and speculative verify (S=spec_k+1, see
        :meth:`paged_verify_step`) are the same trace family.

        tokens (B, S); cache holds the physical pools ``k/v`` from
        :meth:`init_paged_cache` plus per-call row metadata: ``block_tables``
        (B, MB) int32 page ids (-1 = unmapped) and ``pos`` (B,) — the row's
        write offset (its current logical length). Row ``i`` writes K/V for
        positions ``pos[i] .. pos[i]+S-1`` through its table and attends over
        its own gathered pages; writes that fall outside the mapped pages
        (padding rows, chunk padding past the reservation) are dropped, and
        unmapped reads are causally masked. Returns ``(logits (B, S, V),
        new {k, v})`` — the caller owns ``block_tables``/``pos``.
        """
        cfg = self.cfg
        bt, pos = cache["block_tables"], cache["pos"]
        b, s = tokens.shape
        mb = bt.shape[1]
        cells = cache["k"].shape[1]
        positions = pos[:, None] + jnp.arange(s)  # (B, S) absolute
        # physical cell of every logical kv position (B, MB*block_size)
        base = jnp.where(bt < 0, cells, bt * block_size)
        phys_read = (base[:, :, None] + jnp.arange(block_size)
                     ).reshape(b, mb * block_size)
        # physical cell of each written token; >= cells means "drop"
        lblk = positions // block_size
        wblk = jnp.take_along_axis(bt, jnp.minimum(lblk, mb - 1), axis=1)
        write_idx = jnp.where(
            (wblk < 0) | (lblk >= mb), cells,
            wblk * block_size + positions % block_size)

        ctx = Ctx("apply", params=params)
        layer_cache = {"k": cache["k"], "v": cache["v"]}

        def layer_fn(c, xx, cache=None):
            lc = dict(cache, write_idx=write_idx, phys_read=phys_read)
            return decoder_block(c, cfg, xx, positions=positions,
                                 cache=lc, causal=True)

        with site_scope("decoder"):
            x = embed(ctx, tokens, cfg)
            x, new_lc, _ = scan_policy_segments(
                layer_fn, params["blocks"], x, segments=self.segments,
                cache=layer_cache)
            x = norm(ctx, "final_ln", x, cfg)
            logits = unembed(ctx, x, cfg)
        return logits, new_lc

    def paged_verify_step(self, params: Params, tokens: jnp.ndarray, cache,
                          *, block_size: int):
        """Speculative-decoding verify: one batched :meth:`paged_step` over
        ``S = k+1`` candidate positions (joining S=1 decode and S=chunk
        prefill as the third fixed shape of the same trace family).

        ``tokens[:, 0]`` is each row's last *committed* token and
        ``tokens[:, 1:]`` the ``k`` draft tokens; ``cache``/``block_size``
        are as in :meth:`paged_step` (``pos`` = the row's committed length,
        so K/V for the whole candidate window scatters at its true
        position offsets). Because causal attention at candidate ``j``
        sees only the in-step K/V of candidates ``<= j`` plus the
        committed pool, the per-position greedy tokens are exactly what
        ``k+1`` sequential S=1 decode steps would have produced — the
        standard accept/reject + bonus-token argument.

        Returns ``(greedy (B, S) per-position argmax, n_acc (B,) accepted
        draft count = longest prefix with greedy[:, j] == tokens[:, j+1],
        new {k, v})``. The emitted tokens are ``greedy[:, :n_acc+1]`` (the
        ``+1`` is the bonus token from the verify logits at the last
        accepted position); K/V scattered past ``pos + n_acc`` belongs to
        rejected candidates and must be logically rolled back by the
        caller (the serving engine truncates the block table and lets the
        next window overwrite in place).
        """
        logits, new_kv = self.paged_step(params, tokens, cache,
                                         block_size=block_size)
        greedy = jnp.argmax(logits, -1)                     # (B, S)
        match = (greedy[:, :-1] == tokens[:, 1:]).astype(jnp.int32)
        n_acc = jnp.cumprod(match, axis=1).sum(axis=1)      # (B,)
        return greedy, n_acc, new_kv

    # -- cached forward (shared by decode_step / prefill) -------------------
    def _cached_forward(self, params: Params, tokens: jnp.ndarray, cache,
                        positions, pos,
                        image_embeds: Optional[jnp.ndarray] = None):
        """Embed -> cached layer stack -> logits. ``positions`` feeds rope and
        attention masking; ``pos`` is the cache's scalar write offset,
        shared by every row (the engine serves from the paged cache)."""
        cfg = self.cfg
        ctx = Ctx("apply", params=params)

        ring = "abs_pos" in cache
        layer_cache = {"k": cache["k"], "v": cache["v"]}
        if ring:
            layer_cache["abs_pos"] = cache["abs_pos"]

        def layer_fn(c, xx, cache=None):
            lc = dict(cache, pos=pos)
            xx, nc, aux = decoder_block(c, cfg, xx, positions=positions,
                                        cache=lc, causal=True)
            nc.pop("pos")
            return xx, nc, aux

        with site_scope("decoder"):
            x = embed(ctx, tokens, cfg)
            if not self.is_vlm:
                x, new_lc, _ = scan_policy_segments(
                    layer_fn, params["blocks"], x, segments=self.segments,
                    cache=layer_cache)
            else:
                img = image_embeds.astype(x.dtype)
                per = cfg.cross_every
                new_parts = []
                for g in range(self.n_cross):
                    x, nc, _ = scan_policy_segments(
                        layer_fn, params["blocks"], x,
                        segments=clip_segments(self.segments, g * per,
                                               (g + 1) * per),
                        cache=layer_cache)
                    new_parts.append(nc)
                    cparams = jax.tree.map(lambda p: p[g],
                                           params["cross_blocks"])
                    with site_scope(f"cross_{g}"):
                        x = apply_model(
                            lambda c, xx: cross_block(c, cfg, xx, img),
                            cparams, x)
                new_lc = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                      *new_parts)
            x = norm(ctx, "final_ln", x, cfg)
            logits = unembed(ctx, x, cfg)
        return logits, new_lc

    # -- decode (one token, KV cache) --------------------------------------
    def decode_step(self, params: Params, tokens: jnp.ndarray, cache,
                    image_embeds: Optional[jnp.ndarray] = None):
        """tokens: (B, 1). Returns (logits (B, 1, V), new cache).

        ``cache['pos']`` is a scalar: all rows sit at the same offset."""
        pos = cache["pos"]
        positions = jnp.reshape(pos, (1,))
        logits, new_lc = self._cached_forward(params, tokens, cache,
                                              positions, pos, image_embeds)
        return logits, dict(new_lc, pos=pos + 1)

    # -- prefill (whole prompt in one forward, KV cache) --------------------
    def prefill(self, params: Params, tokens: jnp.ndarray, cache,
                image_embeds: Optional[jnp.ndarray] = None):
        """Batched prompt ingestion: one forward writes the prompt K/V into
        the cache and returns full logits. tokens: (B, S) — right-padded
        prompts are fine: a pad entry at position p >= true_len is either
        overwritten by decode before position p is reached or excluded by
        the causal mask, so it is never attended.

        Returns (logits (B, S, V), new cache with pos advanced by S).
        """
        if "abs_pos" in cache:
            raise NotImplementedError(
                "prefill does not support ring/window caches")
        pos = cache["pos"]
        if jnp.ndim(pos) != 0:
            raise ValueError("prefill expects a scalar-pos cache")
        s = tokens.shape[1]
        positions = pos + jnp.arange(s)
        logits, new_lc = self._cached_forward(params, tokens, cache,
                                              positions, pos, image_embeds)
        return logits, dict(new_lc, pos=pos + s)


# ---------------------------------------------------------------------------
# Encoder-decoder (whisper backbone; conv/audio frontend is a stub per the
# assignment — input_specs provides precomputed frame embeddings)
# ---------------------------------------------------------------------------

def encoder_block(ctx: Ctx, cfg: ArchConfig, x, *, positions, cache=None):
    with ctx.scope("attn"):
        h, _ = self_attention(ctx, norm(ctx, "ln1", x, cfg), cfg,
                              positions=positions, causal=False,
                              use_bias=True, unroll_category="attn_enc")
    x = x + h
    with ctx.scope("ffn"):
        x = x + mlp(ctx, norm(ctx, "ln2", x, cfg), cfg, use_bias=True)
    x = constrain(x, ("act_batch", "frames", "act_embed"))
    return x, None, jnp.zeros((), jnp.float32)


def encdec_decoder_block(ctx: Ctx, cfg: ArchConfig, x, *, positions,
                         enc_kv, cache=None):
    use_bias = True
    with ctx.scope("attn"):
        h, new_cache = self_attention(ctx, norm(ctx, "ln1", x, cfg), cfg,
                                      positions=positions, cache=cache,
                                      causal=True, use_bias=use_bias)
    x = x + h
    with ctx.scope("xattn"):
        h = cross_attention(ctx, norm(ctx, "lnx", x, cfg), enc_kv, cfg,
                            use_bias=use_bias)
    x = x + h
    with ctx.scope("ffn"):
        x = x + mlp(ctx, norm(ctx, "ln2", x, cfg), cfg, use_bias=use_bias)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, new_cache, jnp.zeros((), jnp.float32)


def encoder_block_sites(cfg: ArchConfig, i: int):
    base = f"encoder/layer_{i}"
    return attn_sites(f"{base}/attn") + mlp_sites(cfg, f"{base}/ffn")


def encdec_decoder_sites(cfg: ArchConfig, i: int):
    base = f"decoder/layer_{i}"
    return (attn_sites(f"{base}/attn") + attn_sites(f"{base}/xattn")
            + mlp_sites(cfg, f"{base}/ffn"))


class EncDecLM:
    """Whisper-style: transformer encoder over precomputed frame embeddings,
    causal decoder with per-layer cross attention."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        pol = cfg.approx_policy
        self.enc_segments = plan_segments(
            pol, functools.partial(encoder_block_sites, cfg),
            0, cfg.enc_layers)
        self.dec_segments = plan_segments(
            pol, functools.partial(encdec_decoder_sites, cfg),
            0, cfg.n_layers)

    def init(self, rng, *, abstract: bool = False):
        def build(rng_):
            ke, kd, kx = jax.random.split(rng_, 3)
            cfg = self.cfg
            params: Params = {}
            axes = {}
            ctx = Ctx("init", rng=kx)
            embed(ctx, jnp.zeros((1, 1), jnp.int32), cfg)
            x0 = jnp.zeros((1, 1, cfg.d_model), cfg.compute_dtype)
            norm(ctx, "final_ln", x0, cfg)
            unembed(ctx, x0, cfg)
            # learned positional embeddings for frames (frontend stub)
            ctx.param("enc_pos", (cfg.enc_frames, cfg.d_model),
                      cfg.param_dtype, axes=("frames", "embed"))
            params.update(ctx.params)
            axes.update(ctx.axes)
            pos0 = jnp.zeros((1,), jnp.int32)
            ep, ea = stacked_init(
                lambda c, xx: encoder_block(c, cfg, xx, positions=pos0),
                ke, cfg.enc_layers, x0)
            params["enc_blocks"] = ep
            axes.update({("enc_blocks",) + p: a for p, a in ea.items()})
            dp, da = stacked_init(
                lambda c, xx: encdec_decoder_block(c, cfg, xx, positions=pos0,
                                                   enc_kv=x0),
                kd, cfg.n_layers, x0)
            params["dec_blocks"] = dp
            axes.update({("dec_blocks",) + p: a for p, a in da.items()})
            return params, axes

        if abstract:
            axes_holder = {}

            def build_shapes(r):
                p, a = build(r)
                axes_holder.update(a)
                return p

            shapes = jax.eval_shape(build_shapes, rng)
            return shapes, axes_holder
        return build(rng)

    def encode(self, params, frames):
        cfg = self.cfg
        ctx = Ctx("apply", params=params)
        pe = ctx.param("enc_pos", (cfg.enc_frames, cfg.d_model),
                       cfg.param_dtype, axes=("frames", "embed"))
        x = frames.astype(cfg.compute_dtype) + pe.astype(cfg.compute_dtype)
        positions = jnp.arange(frames.shape[1])
        enc_fn = lambda c, xx, cache=None: encoder_block(
            c, cfg, xx, positions=positions)
        with site_scope("encoder"):
            x, _, _ = scan_policy_segments(
                enc_fn, params["enc_blocks"], x, segments=self.enc_segments,
                remat=cfg.remat)
        return x

    def forward(self, params, batch):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        positions = jnp.arange(tokens.shape[1])
        ctx = Ctx("apply", params=params)
        dec_fn = lambda c, xx, cache=None: encdec_decoder_block(
            c, cfg, xx, positions=positions, enc_kv=enc)
        with site_scope("decoder"):
            x = embed(ctx, tokens, cfg)
            x, _, _ = scan_policy_segments(
                dec_fn, params["dec_blocks"], x, segments=self.dec_segments,
                remat=cfg.remat)
            x = norm(ctx, "final_ln", x, cfg)
            logits = unembed(ctx, x, cfg)
        return logits, jnp.zeros((), jnp.float32)

    def init_cache(self, batch_size: int, max_seq: int, *,
                   abstract: bool = False):
        cfg = self.cfg

        def mk(shape, dtype):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jnp.zeros(shape, dtype)

        kshape = (cfg.n_layers, batch_size, max_seq, cfg.kv_heads,
                  cfg.head_dim)
        dt = jnp.dtype(cfg.compute_dtype)
        return {
            "k": mk(kshape, dt), "v": mk(kshape, dt),
            "enc": mk((batch_size, cfg.enc_frames, cfg.d_model), dt),
            "pos": mk((), jnp.int32),
        }

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        pos = cache["pos"]
        positions = pos[None].reshape(1,)
        enc = cache["enc"]
        ctx = Ctx("apply", params=params)

        def layer_fn(c, xx, cache=None):
            lc = dict(k=cache["k"], v=cache["v"], pos=pos)
            xx, nc, aux = encdec_decoder_block(
                c, cfg, xx, positions=positions, enc_kv=enc, cache=lc)
            return xx, {"k": nc["k"], "v": nc["v"]}, aux

        with site_scope("decoder"):
            x = embed(ctx, tokens, cfg)
            x, new_lc, _ = scan_policy_segments(
                layer_fn, params["dec_blocks"], x,
                segments=self.dec_segments,
                cache={"k": cache["k"], "v": cache["v"]})
            x = norm(ctx, "final_ln", x, cfg)
            logits = unembed(ctx, x, cfg)
        return logits, {"k": new_lc["k"], "v": new_lc["v"], "enc": enc,
                        "pos": pos + 1}
