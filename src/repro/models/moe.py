"""Mixture-of-Experts FFN with expert parallelism (EP).

Two implementations:

* ``ep`` (production): ``jax.shard_map`` over the mesh. Expert weights are
  2-D sharded — experts over the ``model`` axis, the contraction dim over the
  data(+pod) axes (FSDP) and all-gathered just-in-time. Each model rank
  dispatches its local tokens to *its own* expert slice with a static
  capacity buffer, runs the expert GEMMs, and the partial outputs are
  psum-combined over the model axis (same collective volume as a TP FFN
  all-reduce — the baseline we later hillclimb with all-to-all dispatch).
  Token-choice top-k routing with capacity dropping (Switch-style), combine
  weights applied on the output side.

* ``dense`` (reference): every expert on every token, gate-weighted. Used as
  the numerics oracle for the EP path in tests (with a capacity factor large
  enough that nothing drops, the two agree) and for smoke runs without a mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_sharder
from repro.policy import policy_expert_matmul

from .common import ArchConfig
from .layers import activate
from .module import Ctx, lecun_init


def _expert_mm(x: jnp.ndarray, w: jnp.ndarray, cfg: ArchConfig,
               name: str, record: bool = True) -> jnp.ndarray:
    """(E, C, d) x (E, d, f) -> (E, C, f), per-site DAISM via the policy."""
    return policy_expert_matmul(cfg.approx_policy, x, w, name=name,
                                record=record)


def _route(x2d: jnp.ndarray, router_w: jnp.ndarray, cfg: ArchConfig):
    """Token-choice top-k. Returns (ids (T,k), probs (T,k), aux_loss)."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w.astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)          # (T, E)
    probs, ids = lax.top_k(probs_full, cfg.topk)          # (T, k)
    probs = probs / probs.sum(-1, keepdims=True)
    # Switch-style load-balance auxiliary loss.
    me = probs_full.mean(0)                                # (E,)
    ce = jnp.zeros((cfg.n_experts,)).at[ids.reshape(-1)].add(
        1.0 / ids.size)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return ids, probs, aux


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    c = int(np.ceil(tokens * cfg.topk / cfg.n_experts * cfg.capacity_factor))
    return max(c, cfg.topk)


def _local_dispatch_compute(x2d, ids, probs, w_in, w_gate, w_out, e0: int,
                            cfg: ArchConfig, record: bool = True):
    """Dispatch local tokens to the E_local experts [e0, e0+E_local), run
    them, and return the (partial) combined output (T, d)."""
    t, d = x2d.shape
    e_local = w_in.shape[0]
    cap = _capacity(t, cfg)
    flat_ids = ids.reshape(-1)                       # (T*k,)
    tok = jnp.arange(flat_ids.size) // cfg.topk      # owning token per slot
    le = flat_ids - e0
    mine = (le >= 0) & (le < e_local)
    le_safe = jnp.where(mine, le, 0)
    # position of each slot within its expert's capacity buffer
    oh = jax.nn.one_hot(jnp.where(mine, le, e_local), e_local + 1,
                        dtype=jnp.int32)             # (T*k, E_local+1)
    pos = jnp.cumsum(oh, axis=0) - 1
    pos = jnp.take_along_axis(pos, le_safe[:, None], axis=1)[:, 0]
    keep = mine & (pos < cap)
    pos_safe = jnp.where(keep, pos, cap)             # slot `cap` = trash row

    buf = jnp.zeros((e_local, cap + 1, d), x2d.dtype)
    buf = buf.at[le_safe, pos_safe].add(jnp.where(keep[:, None],
                                                  x2d[tok], 0))
    buf = buf[:, :cap]                               # (E_local, cap, d)

    gated = cfg.act in ("swiglu", "geglu")
    h = _expert_mm(buf, w_in, cfg, "w_in", record)
    g = _expert_mm(buf, w_gate, cfg, "w_gate", record) if gated else None
    h = activate(h, g, cfg.act)
    y = _expert_mm(h, w_out, cfg, "w_out", record)   # (E_local, cap, d)

    y = jnp.pad(y, ((0, 0), (0, 1), (0, 0)))         # restore trash row
    out_slots = y[le_safe, pos_safe]                 # (T*k, d)
    out_slots = jnp.where(keep[:, None], out_slots, 0)
    return (out_slots.reshape(t, cfg.topk, d)
            * probs.astype(out_slots.dtype)[..., None]).sum(axis=1)


def moe_ffn(ctx: Ctx, x: jnp.ndarray, cfg: ArchConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE feed-forward. x: (B, S, d). Returns (out, aux_loss)."""
    d = x.shape[-1]
    ff = cfg.expert_ff
    gated = cfg.act in ("swiglu", "geglu")
    router_w = ctx.param("router", (d, cfg.n_experts), "float32",
                         lecun_init(), axes=("embed", None))
    wexp_axes = ("expert", "embed", "expert_mlp")
    w_in = ctx.param("w_in", (cfg.n_experts, d, ff), cfg.param_dtype,
                     lecun_init(), axes=wexp_axes)
    w_gate = (ctx.param("w_gate", (cfg.n_experts, d, ff), cfg.param_dtype,
                        lecun_init(), axes=wexp_axes) if gated else None)
    w_out = ctx.param("w_out", (cfg.n_experts, ff, d), cfg.param_dtype,
                      lecun_init(), axes=("expert", "expert_mlp", "embed"))

    record = ctx.mode == "apply"  # init traces run outside the site scopes
    sharder = current_sharder()
    use_ep = (cfg.moe_impl == "ep" and sharder is not None
              and "model" in sharder.mesh.axis_names
              and cfg.n_experts % sharder.mesh.shape["model"] == 0)
    if use_ep:
        mesh = sharder.mesh
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
        # batch and weight contraction dims must divide across the mesh
        use_ep = (x.shape[0] % dp_size == 0 and d % dp_size == 0
                  and ff % dp_size == 0)

    if not use_ep:
        return _dense_moe(x, router_w, w_in, w_gate, w_out, cfg, record)
    n_model = mesh.shape["model"]
    b, s, _ = x.shape

    wg = w_gate if gated else w_in  # placeholder operand when ungated

    def ep_body(x_loc, router_loc, w_in_loc, w_gate_loc, w_out_loc):
        # FSDP: gather the contraction dim of the expert weights just-in-time.
        def gather_d(w, axis):
            for a in dp_axes[::-1]:
                w = lax.all_gather(w, a, axis=axis, tiled=True)
            return w
        w_in_f = gather_d(w_in_loc, 1)
        w_gate_f = gather_d(w_gate_loc, 1) if gated else None
        w_out_f = gather_d(w_out_loc, 2)
        t_loc = x_loc.shape[0] * x_loc.shape[1]
        x2d = x_loc.reshape(t_loc, d)
        ids, probs, aux = _route(x2d, router_loc, cfg)
        rank = lax.axis_index("model")
        e0 = rank * (cfg.n_experts // n_model)
        out = _local_dispatch_compute(x2d, ids, probs, w_in_f, w_gate_f,
                                      w_out_f, e0, cfg, record)
        out = lax.psum(out, "model")
        aux = lax.pmean(aux, "model")
        for a in dp_axes:
            aux = lax.pmean(aux, a)
        return out.reshape(x_loc.shape), aux

    in_specs = (
        P(dp_axes if dp_axes else None, None, None),            # x
        P(None, None),                                          # router
        P("model", dp_axes if dp_axes else None, None),         # w_in
        P("model", dp_axes if dp_axes else None, None),         # w_gate
        P("model", None, dp_axes if dp_axes else None),         # w_out
    )
    out_specs = (P(dp_axes if dp_axes else None, None, None), P())
    out, aux = jax.shard_map(
        ep_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(x, router_w, w_in, wg, w_out)
    return out, aux


def _dense_moe(x, router_w, w_in, w_gate, w_out, cfg: ArchConfig,
               record: bool = True):
    """Reference: all experts on all tokens, top-k gate-weighted. Expert
    GEMMs go through the same per-site policy as the EP path (every expert
    sees every token, so the broadcast (E, T, d) operand is the einsum's
    own working set)."""
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    ids, probs, aux = _route(x2d, router_w, cfg)
    gate_full = jnp.zeros((x2d.shape[0], cfg.n_experts), jnp.float32
                          ).at[jnp.arange(x2d.shape[0])[:, None], ids].set(probs)
    gated = cfg.act in ("swiglu", "geglu")
    xb = jnp.broadcast_to(x2d[None], (cfg.n_experts,) + x2d.shape)
    h = _expert_mm(xb, w_in, cfg, "w_in", record)                 # (E, T, f)
    g = _expert_mm(xb, w_gate, cfg, "w_gate", record) if gated else None
    h = activate(h, g, cfg.act)
    y = _expert_mm(h, w_out, cfg, "w_out", record)                # (E, T, d)
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), gate_full)
    return out.astype(x.dtype).reshape(b, s, d), aux
