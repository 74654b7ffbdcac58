"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The trace is started between two ticks and stopped after the window's
last tick, and each tick waits for its steps, so every device event in it
belongs to the traced window. The window spans the benchmark's own host
annotations (``bench.tick`` around each ``ServeEngine.tick()``,
``bench.wait_arrival`` while the open loop waits for the next request) and
those events: the device's clock stands about a millisecond off the host's,
so the first step can show as starting just before the first span. On
each device plane of a chip the run used:

* busy time is the union of the intervals of the events on the
  ``XLA Ops`` line, clipped to the window (idle = window - busy);
* kernel time is the summed duration of the Mosaic custom calls (the
  Pallas kernels) on that line; ops are counted where they run, so a
  ``while`` op that holds the ops of its body (the layer loop) is not
  counted beside them;
* step programs are the events on the ``XLA Modules`` line whose name
  holds ``jit_step`` (``ServeEngine``'s jitted paged step). They run in
  launch order on one stream, so the k-th of them is the k-th step the
  benchmark saw the engine launch.

The program gives its kernels and steps no names of their own yet, so the
reduction finds them by what the trace shows today.
"""
from __future__ import annotations

import collections
import glob
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

HOST_SPANS = ("bench.tick", "bench.wait_arrival")
STEP_MODULE = "jit_step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged intervals clipped to [lo, hi)."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def is_kernel(name: str, stats: Dict) -> bool:
    """A Mosaic custom call (a Pallas kernel) on the ops line: the trace
    names each op by its HLO text, which holds the call's target."""
    text = " ".join([name] + [str(v) for v in stats.values()])
    return "tpu_custom_call" in text


def leaves(events):
    """The events that hold no other event of their line (a ``while`` op
    holds the ops of its body): (name, start, end, event), by start."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for i, ev in enumerate(events)
            if i + 1 == len(events) or events[i + 1][1] >= ev[2]]


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), e


def _stats(e) -> Dict:
    return {k: v for k, v in e.stats}


def device_planes(data, device_ids: Sequence[int]):
    want = {f"/device:TPU:{i}" for i in device_ids}
    return [p for p in data.planes if p.name in want]


def host_spans(data) -> List[Tuple[str, int, int]]:
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e, _ in _events(line):
                if name in HOST_SPANS:
                    out.append((name, s, e))
    return out


def summarize(trace_dir: str, device_ids: Sequence[int],
              top: int = 10) -> Dict:
    """The numbers of one trace, averaged over the chips in
    ``device_ids``."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path(trace_dir))
    spans = host_spans(data)
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    planes = device_planes(data, device_ids)
    lines = [{ln.name: ln for ln in plane.lines} for plane in planes]
    ops_events = [list(_events(ln[OPS_LINE])) if OPS_LINE in ln else []
                  for ln in lines]
    # the trace holds only the traced ticks' device work; the device's
    # clock may put its first op just before the host's first span
    device = [ev for events in ops_events for ev in events]
    lo = min([s for _, s, _ in spans] + [ev[1] for ev in device])
    hi = max([e for _, _, e in spans] + [ev[2] for ev in device])
    busy_ns = kernel_ns = 0
    ops = collections.Counter()
    steps: List[Tuple[int, int]] = []
    idle: List[Interval] = []
    for ln, events in zip(lines, ops_events):
        for name, s, e, ev in leaves(events):
            ops[name] += e - s
            if is_kernel(name, _stats(ev)):
                kernel_ns += e - s
        merged = union([(s, e) for _, s, e, _ in events], lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        idle += gaps(merged, lo, hi)
        if MODULES_LINE in ln and not steps:
            steps = [(s, e) for name, s, e, _ in _events(ln[MODULES_LINE])
                     if STEP_MODULE in name]
    n = max(len(planes), 1)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "step_s": [(e - s) / 1e9 for s, e in steps],
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(top)],
            "idle_gaps": [[host_activity(spans, g), (g[1] - g[0]) / 1e9]
                          for g in idle[:top]],
        },
    }


def host_activity(spans, gap: Interval) -> str:
    """The benchmark span that covers most of a device idle gap, or
    ``host`` (harness or engine work outside any span)."""
    cover = collections.Counter()
    for name, s, e in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > 0:
            cover[name] += overlap
    if not cover:
        return "host"
    name, ns = cover.most_common(1)[0]
    return name if ns * 2 >= gap[1] - gap[0] else "host"


# ---------------------------------------------------------------------------
# Matching the trace's step programs to the launches the benchmark saw
# ---------------------------------------------------------------------------

def launches(run) -> List[Tuple[str, list]]:
    """(kind, rows) of every step launched in the traced ticks, in order."""
    return [launch for t in run["ticks"] for launch in t.rows]


def matched_steps(run) -> Optional[List[Tuple[str, list, float]]]:
    """(kind, rows, device seconds) per traced step program, or None when
    the trace's step programs do not pair one to one with the launches."""
    seen = launches(run)
    steps = run["trace"]["step_s"]
    if not seen or len(seen) != len(steps):
        print(f"bench: the trace holds {len(steps)} {STEP_MODULE} programs "
              f"but the engine launched {len(seen)} steps: the step "
              "metrics are left out", file=sys.stderr, flush=True)
        return None
    return [(k, rows, s) for (k, rows), s in zip(seen, steps)]


def mean_step_ms(run, kind: str) -> Optional[float]:
    steps = matched_steps(run) or []
    times = [s for k, _, s in steps if k == kind]
    return 1e3 * sum(times) / len(times) if times else None


# ---------------------------------------------------------------------------
# Readers that metrics of several cells share (bench/metrics/<name>.py)
# ---------------------------------------------------------------------------

def idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device:
    1 minus the union of the device-op intervals over the window."""
    t = run["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def step_mfu_pct(run) -> Optional[float]:
    """Model operations of the traced step programs (weight GEMMs and
    attention over the live context, ``bench/flops.py:step_flops``) over
    their device time, over the chip's bf16 peak."""
    from bench import flops, peaks

    steps = matched_steps(run)
    if not steps:
        return None
    pk = peaks.peaks(run["device_kind"])
    work = sum(flops.step_flops(run["conf"]["model"], rows)
               for _, rows, _ in steps)
    device_s = sum(s for _, _, s in steps)
    return 100.0 * work / (device_s * pk["bf16_flops"])
