"""Three-term roofline from a compiled dry-run artifact (no real hardware).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = wire_bytes_per_device / link_bw

``compiled.cost_analysis()`` reports the per-device SPMD program's flops and
bytes. Collective bytes are NOT in cost_analysis: we parse the optimized HLO
text (``compiled.as_text()``), sum the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
and apply ring-algorithm wire factors with the group size N parsed from
``replica_groups``:

    all-reduce      2 (N-1)/N x bytes        (ring reduce+broadcast phases)
    all-gather      (N-1)/N x result bytes
    reduce-scatter  (N-1)/N x operand bytes (~= result x (N-1))
    all-to-all      (N-1)/N x bytes
    collective-permute  1 x bytes

The chip's peaks come from :data:`CHIP_PEAKS`, keyed by
``jax.Device.device_kind``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    bf16_flops: float    # FLOP/s, dense bf16 matmul
    hbm_bw: float        # bytes/s, HBM
    link_bw: float       # bytes/s, one ICI link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over 4 links.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             link_bw=1600e9 / 8 / 4),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind without a published entry raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "CHIP_PEAKS with its source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%name = TYPE[shape]{layout} op-name(` — possibly a tuple of types.
_LINE_RE = re.compile(
    r"=\s*(?P<types>\(?[a-z0-9_]+\[[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(types: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(types):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota format: replica_groups=[num_groups,group_size]<=[...]
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].replace("{", " ").strip()
        if first:
            return len(first.split(","))
    return default


def _wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    r = (n - 1) / n
    return {"all-reduce": 2 * r, "all-gather": r, "reduce-scatter": r,
            "all-to-all": r, "collective-permute": 1.0}[op]


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    count: int = 0

    def add(self, op: str, b: float):
        self.wire_bytes += b
        self.by_op[op] = self.by_op.get(op, 0.0) + b
        self.count += 1


def collective_bytes_from_hlo(hlo_text: str, default_group: int
                              ) -> CollectiveStats:
    stats = CollectiveStats()
    seen_start = set()
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        op = m.group("op")
        b = _shape_bytes(m.group("types"))
        n = _group_size(line, default_group)
        stats.add(op, b * _wire_factor(op, n))
    return stats


def model_flops_estimate(cfg, shape_kind: str, seq: int, batch: int) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (forward) with N = active params.

    MoE: N counts topk/n_experts of expert params (active). Decode: D = one
    token per step x batch.
    """
    import numpy as np
    from repro.models.registry import build_model
    import jax

    model = build_model(cfg)
    shapes, _ = model.init(jax.random.PRNGKey(0), abstract=True)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n_total = 0
    n_expert = 0
    for path, leaf in flat:
        keys = "/".join(str(getattr(p, "key", "")) for p in path)
        n = int(np.prod(leaf.shape))
        if any(k in keys for k in ("w_in", "w_gate", "w_out")) and cfg.n_experts:
            n_expert += n
        else:
            n_total += n
    active = n_total + (n_expert * cfg.topk // max(cfg.n_experts, 1))
    tokens = batch * (1 if shape_kind == "decode" else seq)
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * active * tokens
