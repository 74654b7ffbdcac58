"""Backend dispatcher: resolve a site's numerics, validate, execute.

This is the single injection point between models and the DAISM GEMM:

* :func:`make_dot` builds a ``dot``-style callable bound to one policy
  (AQT-style): models call ``dot(x, w, name=..., kind=...)`` instead of
  branching on a threaded config.
* Resolution happens at trace time: the site path comes from the ambient
  :mod:`~repro.policy.sites` scope stack, backend/dtype combinations are
  validated here (actionable errors naming the site), and the decision is
  recorded in a per-policy resolution log for reporting.
* Jitted kernels are cached per distinct resolved :class:`DaismConfig`
  (:func:`matmul_kernel`), so a mixed policy re-uses one compiled kernel per
  unique config instead of recompiling per call site.
* :func:`auto_interpret` is the one home for Pallas interpret auto-selection
  (kernels/ops.py consumes it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import Backend, DaismConfig, Variant

from .policy import EXACT, ApproxPolicy, describe_config
from .sites import OpKind, current_path, current_repeat

# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

_GEMM_DTYPES = ("bfloat16", "float32")


def auto_interpret(cfg: "DaismConfig | bool | None" = None) -> bool:
    """Pallas interpret mode: explicit setting wins, else True off-TPU.

    The one home for interpret auto-selection: accepts a full
    :class:`DaismConfig` (its ``interpret`` field is the explicit setting)
    or the bare explicit flag, so direct kernel entry points
    (``kernels.daism_matmul`` / ``kernels.flash_attention``) resolve their
    ``interpret=None`` defaults through the same explicit-wins/TPU-compiles
    semantics as the policy dispatcher.
    """
    explicit = cfg.interpret if isinstance(cfg, DaismConfig) else cfg
    if explicit is not None:
        return explicit
    return jax.default_backend() == "cpu"


def effective_attn_config(cfg: DaismConfig, *,
                          eligible: bool = True) -> DaismConfig:
    """The config an attention-score site (OpKind.ATTN_QK) actually runs.

    Attention numerics follow the resolved config only when it opts into the
    fused flash kernel (``attn_kernel='flash'``) *and* the call shape is
    flash-eligible; otherwise the site executes the exact jnp online-softmax
    path, so its effective config is EXACT. This keeps catch-all rules like
    ``*=pc3_tr`` from silently changing attention numerics (or the energy
    report) the moment the ATTN_QK site exists — approximating the dynamic
    attention GEMMs is strictly opt-in via the ``:flash`` spec token.
    """
    if cfg.attn_kernel == "flash" and eligible:
        return cfg
    return EXACT


def validate_for_dtype(cfg: DaismConfig, dtype, *, site: str = "") -> None:
    """Raise an actionable error if ``cfg`` cannot run on ``dtype`` operands.

    Called at resolve time (and by ``ArchConfig`` at construction via its
    compute dtype) so misconfigurations fail before any kernel traces.
    """
    if cfg.exact:
        return
    where = f"site {site!r}: " if site else ""
    name = jnp.dtype(dtype).name
    if name not in _GEMM_DTYPES:
        raise ValueError(
            f"{where}DAISM approximate GEMMs support bfloat16/float32 "
            f"operands, got {name}; run this site exact or change the "
            "compute dtype")
    if cfg.backend is Backend.PALLAS and name != "bfloat16":
        raise ValueError(
            f"{where}backend {cfg.backend.value!r} is bfloat16-only, got "
            f"{name}; use backend='jnp' for float32 or switch the compute "
            "dtype to bfloat16")


# ---------------------------------------------------------------------------
# Resolution log (per-policy, per-site) — feeds the reports
# ---------------------------------------------------------------------------

# policy -> {(path, kind): (config, dtype_name, macs_per_trace)}
_LOG: Dict[ApproxPolicy, Dict[Tuple[str, OpKind],
                              Tuple[DaismConfig, str, int]]] = {}
_STATS = {"kernel_builds": 0, "kernel_traces": 0}


def clear_log(policy: Optional[ApproxPolicy] = None) -> None:
    if policy is None:
        _LOG.clear()
    else:
        _LOG.pop(policy, None)


def resolution_log(policy: ApproxPolicy) -> Dict[Tuple[str, OpKind],
                                                 Tuple[DaismConfig, str, int]]:
    """Sites resolved so far for ``policy`` (only traced sites appear)."""
    return dict(_LOG.get(policy, {}))


def _record(policy: ApproxPolicy, path: str, kind: OpKind, cfg: DaismConfig,
            dtype, macs: int) -> None:
    _LOG.setdefault(policy, {})[(path, kind)] = (
        cfg, jnp.dtype(dtype).name, int(macs))


# ---------------------------------------------------------------------------
# Site observers — the static analyzer's trace hook
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SiteEvent:
    """One resolved contraction site, as seen at trace time.

    ``dims = (m, k, n)`` are the dims of a single kernel invocation (leading
    batch axes folded into ``m``; for batched expert GEMMs the per-expert
    dims). ``macs`` is the total multiply count of the site including any
    expert batching and the ambient scan ``repeat``.
    """

    path: str
    kind: OpKind
    config: DaismConfig
    dtype: str
    dims: Tuple[int, int, int]
    macs: int
    repeat: int


_OBSERVERS: List[Callable[[SiteEvent], None]] = []


@contextlib.contextmanager
def observe_sites(callback: Callable[[SiteEvent], None]):
    """Deliver a :class:`SiteEvent` to ``callback`` for every site resolved
    (with ``record=True``) inside the with-block.

    This is how ``repro.analyze`` materializes the op-site graph from a
    ``jax.eval_shape`` trace without touching the per-policy resolution log.
    """
    _OBSERVERS.append(callback)
    try:
        yield
    finally:
        _OBSERVERS.remove(callback)


def _energy_per_mult_pj(cfg: DaismConfig, dtype_name: str) -> float:
    """Estimated pJ per multiplication (core/energy model, Eq 4-6)."""
    from repro.core import energy as E

    if dtype_name not in ("bfloat16", "float32"):
        dtype_name = "float32"
    exp = E.exponent_handling_energy(dtype_name)
    if cfg.exact:
        return E.total(E.eyeriss_energy_per_mult(
            dtype_name, truncated=False)) + exp
    return E.total(E.daism_energy_per_mult(cfg.variant, dtype_name)) + exp


def energy_per_mult_pj(cfg: DaismConfig, dtype_name: str) -> float:
    """Public per-mult energy estimate (the analyzer's site table uses it)."""
    return _energy_per_mult_pj(cfg, dtype_name)


def site_report(policy: ApproxPolicy) -> str:
    """Human-readable per-site resolution table with energy estimates.

    Covers the sites traced so far under ``policy``; ``macs`` is the
    multiply count of the most recent trace of each site (batch-shaped),
    and the energy column is macs x the analytical per-mult model.
    """
    log = _LOG.get(policy, {})
    if not log:
        return (f"policy {policy.name or '<anonymous>'}: "
                "no sites resolved yet (trace a model first)")
    rows, total_pj, exact_pj = [], 0.0, 0.0
    for (path, kind), (cfg, dtype_name, macs) in sorted(log.items()):
        pj = macs * _energy_per_mult_pj(cfg, dtype_name)
        base = macs * _energy_per_mult_pj(
            DaismConfig(variant=Variant.EXACT, backend=Backend.EXACT),
            dtype_name)
        total_pj += pj
        exact_pj += base
        rows.append((path, kind.value, describe_config(cfg), macs, pj))
    width = max(len(r[0]) for r in rows)
    lines = [f"== per-site resolution ({policy.name or '<anonymous>'}) =="]
    for path, kind, conf, macs, pj in rows:
        lines.append(f"  {path:<{width}}  {kind:<10s} {conf:<18s} "
                     f"{macs:>12,d} mults  {pj / 1e6:>10.2f} uJ")
    if exact_pj > 0:
        lines.append(
            f"  estimated multiply energy {total_pj / 1e6:.2f} uJ "
            f"(saves {100 * (1 - total_pj / exact_pj):.1f}% vs all-exact "
            f"{exact_pj / 1e6:.2f} uJ)")
    return "\n".join(lines)


def estimated_energy_uj(policy: ApproxPolicy) -> Tuple[float, float]:
    """(policy_energy, all_exact_energy) in uJ over the traced sites."""
    log = _LOG.get(policy, {})
    total = base = 0.0
    exact_cfg = DaismConfig(variant=Variant.EXACT, backend=Backend.EXACT)
    for (_, _), (cfg, dtype_name, macs) in log.items():
        total += macs * _energy_per_mult_pj(cfg, dtype_name)
        base += macs * _energy_per_mult_pj(exact_cfg, dtype_name)
    return total / 1e6, base / 1e6


# ---------------------------------------------------------------------------
# Kernel cache
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def matmul_kernel(cfg: DaismConfig) -> Callable:
    """One jitted 2-D approximate matmul per distinct resolved config.

    The lru_cache plus jit's own (shape-keyed) cache mean a mixed policy
    compiles each unique (config, shape) combination once, however many
    sites share it. ``kernel_stats()`` exposes build/trace counters for the
    cache-hit tests.
    """
    from repro.core.gemm import daism_matmul

    _STATS["kernel_builds"] += 1

    def kernel(a, w):
        _STATS["kernel_traces"] += 1  # runs at trace time only
        return daism_matmul(a, w, cfg)

    return jax.jit(kernel)


@functools.lru_cache(maxsize=None)
def attention_kernel(cfg: DaismConfig) -> Callable:
    """One jitted flash-attention callable per distinct resolved config.

    ``kernel(q, k, v, causal)`` takes (B, S, H, D) tensors (GQA head repeat
    and ragged-length padding happen inside the wrapper); ``causal`` is a
    static argument. Exact configs run the kernel with MXU contractions
    (``variant=None``); approximate configs fuse the config's shift-plane
    product into the QK/PV contractions.
    """
    from repro.kernels.flash_attention import flash_attention_bhsd

    _STATS["kernel_builds"] += 1
    variant = None if cfg.exact else cfg.variant
    interpret = auto_interpret(cfg)

    def kernel(q, k, v, causal):
        _STATS["kernel_traces"] += 1  # runs at trace time only
        return flash_attention_bhsd(q, k, v, causal=causal, variant=variant,
                                    interpret=interpret)

    return jax.jit(kernel, static_argnames=("causal",))


def kernel_stats() -> Dict[str, int]:
    info = matmul_kernel.cache_info()
    return dict(_STATS, cache_hits=info.hits, cache_misses=info.misses,
                cached_kernels=info.currsize)


# ---------------------------------------------------------------------------
# Injection points
# ---------------------------------------------------------------------------


def resolve_site(policy: ApproxPolicy, name: str, kind: OpKind, dtype,
                 *, record: bool = True, macs: int = 0,
                 dims: Tuple[int, int, int] = (0, 0, 0),
                 attn_eligible: bool = True) -> DaismConfig:
    """Resolve + validate the config for the site named ``name`` under the
    ambient site scope. Returns the (frozen) resolved DaismConfig.

    ATTN_QK sites resolve to their *effective* config (see
    :func:`effective_attn_config`): the rule's numerics apply only when it
    opts into the flash kernel and the caller's shape is eligible
    (``attn_eligible``); otherwise the site runs — and is recorded as —
    EXACT.
    """
    path = current_path(name)
    kind = OpKind(kind)
    cfg = policy.resolve(path, kind)
    if kind is OpKind.ATTN_QK:
        cfg = effective_attn_config(cfg, eligible=attn_eligible)
        if not cfg.exact and jnp.dtype(dtype).name != "bfloat16":
            raise ValueError(
                f"site {path!r}: flash attention with a DAISM variant is "
                f"bfloat16-only (got {jnp.dtype(dtype).name}); run the site "
                "exact (drop the variant, keep ':flash') or switch the "
                "compute dtype to bfloat16")
    validate_for_dtype(cfg, dtype, site=path)
    if record:
        repeat = current_repeat()
        _record(policy, path, kind, cfg, dtype, macs * repeat)
        for cb in _OBSERVERS:
            cb(SiteEvent(path=path, kind=kind, config=cfg,
                         dtype=jnp.dtype(dtype).name, dims=dims,
                         macs=macs * repeat, repeat=repeat))
    return cfg


def policy_dot(policy: ApproxPolicy, x, w, *, name: str,
               kind: OpKind = OpKind.DENSE, record: bool = True):
    """``x @ w`` over the last axis of ``x`` with site-resolved numerics.

    Exact sites preserve the plain ``jnp.dot`` deployment path (weights cast
    to the activation dtype); approximate sites run the DAISM GEMM through
    the per-config kernel cache. Output dtype always matches ``x``.
    """
    k = x.shape[-1]
    n = w.shape[-1]
    m = int(np.prod(x.shape[:-1], dtype=np.int64))
    macs = m * int(k) * int(n)
    cfg = resolve_site(policy, name, kind, x.dtype, record=record, macs=macs,
                       dims=(m, int(k), int(n)))
    if cfg.exact:
        return jnp.dot(x, w.astype(x.dtype))
    out = matmul_kernel(cfg)(x.reshape(-1, k), w)
    return out.reshape(*x.shape[:-1], n).astype(x.dtype)


def make_dot(policy: ApproxPolicy) -> Callable:
    """Bind ``policy`` into a ``dot(x, w, *, name, kind, record)`` callable —
    the AQT-style injectable matmul models consume."""
    return functools.partial(policy_dot, policy)


def policy_conv2d(policy: ApproxPolicy, x, kernel, *, name: str,
                  stride: int = 1, padding: str = "SAME",
                  record: bool = True):
    """NHWC conv with site-resolved numerics (im2col + DAISM GEMM when the
    site resolves approximate, ``lax.conv_general_dilated`` when exact)."""
    from repro.core.gemm import conv2d_im2col

    kh, kw, cin, cout = kernel.shape
    nb, h, wdim = x.shape[0], x.shape[1], x.shape[2]
    if padding == "SAME":
        ho, wo = -(-h // stride), -(-wdim // stride)
    else:  # VALID
        ho, wo = -(-(h - kh + 1) // stride), -(-(wdim - kw + 1) // stride)
    macs = nb * ho * wo * kh * kw * cin * cout
    cfg = resolve_site(policy, name, OpKind.CONV, x.dtype, record=record,
                       macs=macs, dims=(nb * ho * wo, kh * kw * cin, cout))
    return conv2d_im2col(x, kernel.astype(x.dtype), cfg, stride=stride,
                         padding=padding).astype(x.dtype)


def policy_expert_matmul(policy: ApproxPolicy, x, w, *, name: str,
                         record: bool = True):
    """(E, C, d) x (E, d, f) -> (E, C, f) batched expert GEMM."""
    e, c, d = x.shape
    f = w.shape[-1]
    macs = e * c * d * f
    cfg = resolve_site(policy, name, OpKind.MOE_EXPERT, x.dtype,
                       record=record, macs=macs, dims=(c, d, f))
    if cfg.exact:
        return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))
    kern = matmul_kernel(cfg)
    return jax.vmap(lambda xe, we: kern(xe, we))(x, w).astype(x.dtype)
