"""The program's own spans in a traced run: the serving engine's host spans
(``engine.*``, written by ``repro.serve.ServeEngine`` with
``jax.profiler`` annotations) read beside the benchmark's spans
(``bench.*``) and the device ops, all on the profiler's clock.

This adds to what ``bench/trace.py`` reads of a trace and changes none of
it. From the same window and the same device planes it gives:

* ``engine_spans``: (name, start ns, end ns, stats) of every ``engine.*``
  span, by start;
* ``idle_in_span_s``: per span name, the device idle time while the host
  is inside a span of that name (the phases of a tick do not overlap, so
  the ``engine.*`` phases split ``engine.tick``'s share);
* ``idle_in_tick_s``: that time for ``engine.tick``, None where the trace
  holds no engine spans or no device plane;
* ``idle_gaps``: the longest device idle gaps, each named by the innermost
  span that covers at least half of it, or ``host``. With the benchmark's
  spans alone this is ``bench/trace.py``'s own naming.

A trace of a program that writes no engine spans gives none, and every
reader of them returns None.

    python3 -m bench.spans TRACE_DIR [DEVICE_ID ...]    # prints a summary
"""
from __future__ import annotations

import collections
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PREFIX = "engine."
TICK = "engine.tick"
# where bench/run.py:run_cell writes the trace of a --trace 1 run
TRACE_DIR = os.path.join(ROOT, "bench", "out", "trace")

Span = Tuple[str, int, int, Dict]


def program_spans(data) -> List[Span]:
    """(name, start, end, stats) of every engine span on a host plane."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e, ev in trace._events(line):
                if name.startswith(PREFIX):
                    out.append((name, s, e, trace._stats(ev)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def overlap_ns(a: Sequence[trace.Interval],
               b: Sequence[trace.Interval]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def name_gap(spans: Sequence[Tuple[str, int, int]],
             gap: trace.Interval) -> str:
    """The innermost span name that covers at least half of ``gap`` (the
    time of all its spans summed), or ``host``. Among names nested equally
    deep, the one that covers most; where none nests, this is
    ``trace.host_activity``."""
    hits = [(name, s, e) for name, s, e in spans
            if min(e, gap[1]) - max(s, gap[0]) > 0]
    cover = collections.Counter()
    depth: Dict[str, int] = {}
    for name, s, e in hits:
        cover[name] += min(e, gap[1]) - max(s, gap[0])
        inside = sum(1 for other in hits if other != (name, s, e)
                     and other[1] <= s and e <= other[2])
        depth[name] = max(depth.get(name, 0), inside)
    half = [name for name, ns in cover.items()
            if ns * 2 >= gap[1] - gap[0]]
    if not half:
        return "host"
    return sorted(half, key=lambda n: (-depth[n], -cover[n]))[0]


def summarize(trace_dir: str, device_ids: Sequence[int],
              top: int = 10) -> Dict:
    """The engine spans of one trace and the device idle time under them,
    averaged over the chips in ``device_ids``, over the window
    ``trace.summarize`` takes."""
    import jax

    data = jax.profiler.ProfileData.from_file(trace.xplane_path(trace_dir))
    ops = []
    for plane in trace.device_planes(data, device_ids):
        lines = {ln.name: ln for ln in plane.lines}
        ops.append([(s, e) for _, s, e, _ in trace._events(
            lines[trace.OPS_LINE])] if trace.OPS_LINE in lines else [])
    return reduce(trace.host_spans(data), program_spans(data), ops, top)


def reduce(bench_spans: Sequence[Tuple[str, int, int]],
           engine: Sequence[Span], ops: Sequence[Sequence[trace.Interval]],
           top: int = 10) -> Dict:
    """``summarize`` on the spans and the device ops of each plane."""
    device = [iv for events in ops for iv in events]
    lo = min([s for _, s, _ in bench_spans] + [s for s, _ in device])
    hi = max([e for _, _, e in bench_spans] + [e for _, e in device])
    by_name: Dict[str, List[trace.Interval]] = collections.defaultdict(list)
    for name, s, e in bench_spans:
        by_name[name].append((s, e))
    for name, s, e, _ in engine:
        by_name[name].append((s, e))
    covered = {name: trace.union(iv, lo, hi) for name, iv in by_name.items()}
    idle_ns = collections.Counter()
    idle: List[trace.Interval] = []
    for events in ops:
        plane_gaps = trace.gaps(trace.union(events, lo, hi), lo, hi)
        idle += plane_gaps
        for name, iv in covered.items():
            idle_ns[name] += overlap_ns(plane_gaps, iv)
    idle.sort(key=lambda g: g[0] - g[1])
    named = list(bench_spans) + [(name, s, e) for name, s, e, _ in engine]
    # no device plane: nothing to say of device idle time
    idle_in_span = ({name: idle_ns[name] / len(ops) / 1e9
                     for name in covered} if ops else {})
    return {
        "engine_spans": list(engine),
        "idle_in_span_s": idle_in_span,
        "idle_in_tick_s": idle_in_span.get(TICK),
        "idle_gaps": [[name_gap(named, g), (g[1] - g[0]) / 1e9]
                      for g in idle[:top]],
    }


# ---------------------------------------------------------------------------
# Readers that metrics share (bench/metrics/<name>.py)
# ---------------------------------------------------------------------------

def of_run(run) -> Dict:
    """The span summary of a traced run, made once per run. The trace is
    where the harness wrote it and the chips are the cell's, unless
    ``run`` names them (``trace_dir``, ``device_ids``)."""
    if "spans" not in run:
        ids = run.get("device_ids")
        if ids is None:
            import jax

            with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
                cells = {w["name"]: w for w in json.load(f)["workloads"]}
            ids = [d.id for d in jax.devices()[:cells[run["name"]]["chips"]]]
        run["spans"] = summarize(run.get("trace_dir", TRACE_DIR), ids)
    return run["spans"]


def idle_in_tick_pct(run) -> Optional[float]:
    """Share of the traced window in which no device op runs while the
    host is inside ``engine.tick``."""
    idle = of_run(run)["idle_in_tick_s"]
    window = run["trace"]["window_s"]
    return None if idle is None or not window else 100.0 * idle / window


def prefill_fill_pct(run) -> Optional[float]:
    """Live prompt tokens over the rows the prefill launches padded to
    (``num_slots x prefill_chunk``), over the traced ``engine.launch``
    spans of kind ``prefill``."""
    launches = [stats for name, _, _, stats in of_run(run)["engine_spans"]
                if name == "engine.launch" and stats.get("kind") == "prefill"]
    padded = sum(stats["padded"] for stats in launches)
    if not padded:
        return None
    return 100.0 * sum(stats["tokens"] for stats in launches) / padded


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        sys.exit(__doc__.rstrip().splitlines()[-1].strip())
    ids = [int(a) for a in argv[1:]] or [0]
    summary = summarize(argv[0], ids)
    host = collections.defaultdict(lambda: [0, 0.0])
    for name, s, e, _ in summary.pop("engine_spans"):
        host[name][0] += 1
        host[name][1] += (e - s) / 1e9
    summary["host_s"] = {name: {"count": c, "s": t}
                         for name, (c, t) in sorted(host.items())}
    print(json.dumps(summary, indent=1))


if __name__ == "__main__":
    main()
