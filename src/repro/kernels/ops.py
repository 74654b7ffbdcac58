"""jit'd public wrappers around the Pallas kernels (padding + dispatch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bitops import round_up as _round_up
from repro.core.config import DaismConfig
from repro.policy.dispatch import auto_interpret as _auto_interpret

from .daism_matmul import daism_matmul_kernel

# Row granularity of the M tile: the f32 output tile's sublane count.
_ROW_ALIGN = 8
# Lane granularity of the N tile: N pads to a multiple of this, and no more.
_LANES = 128


def row_tile(m: int, block_m: int) -> int:
    """The kernel's M tile for an M-row input: ``block_m`` caps it, and a
    smaller M takes the least multiple of 8 rows that holds it.

    The emulated multiplier costs the same per padded row as per live one,
    so a 4-row decode runs 8 rows, not ``block_m``. The M and N tiles do
    not change the K order of the accumulation, so no output bit depends
    on them.
    """
    return min(block_m, _round_up(m, _ROW_ALIGN))


def col_tile(n: int, bm: int, block_m: int, block_n: int) -> int:
    """The kernel's N tile under an M tile of ``bm`` rows: the widest
    multiple of 128 lanes that divides N's 128-padded extent, capped at
    ``block_n`` widened ``block_m // bm`` times.

    Each K sub-chunk of the sweep pays a fixed cost (its operand
    transposes and broadcasts) besides its (K_FUSE, bm, bn) slab, so the
    wider the tile, the more lanes share that cost; a shorter row tile
    takes a wider column tile under the same slab size. N pads only to
    128 lanes, so a narrow N keeps a narrow tile.
    """
    lanes = _round_up(n, _LANES) // _LANES
    widen = max(1, min(lanes, block_n * (block_m // bm) // _LANES))
    while lanes % widen:
        widen -= 1
    return _LANES * widen


@functools.partial(jax.jit, static_argnums=(2,))
def daism_matmul_pallas(a: jnp.ndarray, w: jnp.ndarray, cfg: DaismConfig) -> jnp.ndarray:
    """(M, K) @ (K, N) -> (M, N) f32 with automatic pad-to-tile.

    The M tile is :func:`row_tile` of M (``cfg.block_m`` is its upper
    bound) and the N tile :func:`col_tile` (``cfg.block_n`` caps it at a
    full row tile); K uses ``cfg.block_k``. Zero padding is
    semantics-preserving: approx(0 * w) == 0 contributes nothing to the
    exact accumulation.
    """
    if a.dtype != jnp.bfloat16 or w.dtype != jnp.bfloat16:
        raise ValueError("Pallas DAISM kernel is bfloat16-only; f32 uses the "
                         "dual-plane jnp backend")
    m, k = a.shape
    _, n = w.shape
    bm = row_tile(m, cfg.block_m)
    bk, bn = cfg.block_k, col_tile(n, bm, cfg.block_m, cfg.block_n)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a
    w_p = jnp.pad(w, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else w
    out = daism_matmul_kernel(
        a_p, w_p,
        variant=cfg.variant,
        block_m=bm, block_n=bn, block_k=bk,
        interpret=_auto_interpret(cfg),
    )
    return out[:m, :n]
