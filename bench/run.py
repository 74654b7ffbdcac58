"""Benchmark harness: one cell, one seed, one process, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; everything that belongs to it is
data found by name: its configuration (``bench/configs/<config>.json``), its
engine settings and check (``bench/workloads/<cell>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and, with
``--trace 1``, one reader per per-layer metric (``bench/metrics/<name>.py``).

A run takes the chip (it exits non-zero, printing no result, without a TPU
or with fewer chips than the cell asks for), makes the weights on the device
from the seed, builds the program's ``ServeEngine``, warms up the cell's two
step shapes by driving one short request through it, and then measures for
``--seconds``, driving ``ServeEngine.submit`` and ``ServeEngine.tick``
itself: an open loop submits each request when it falls due by the wall
clock, a closed loop submits a client's next request when its last one is
done. The window ends at the first tick boundary after ``--seconds``. The
engine then drains what was sent in the window, for at most
``DRAIN_SECONDS``. Then the peak device memory is read, the program's
state is freed, and a sample of the served requests is compared with the
plain reference (``bench/check.py``). ``--trace 1`` traces the last
``TRACE_SHARE`` of the window and prints the per-layer metrics instead of
the end-to-end ones. (``bench/calibrate.py`` runs the same cell over many
seeds and rates in one process, and reads the float8 control.)

The last stdout line is one JSON object; the numbers compared with their
limits are its last key and the last lines on stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]   # the yardstick, the program

from bench import check, traffic, weights  # noqa: E402

ACTIVATIONS = {"gelu_tanh": "gelu", "relu2": "relu2"}
DRAIN_SECONDS = 60.0    # the longest wait after the window for what it owes
TRACE_SHARE = 0.25      # the traced part of a --trace 1 window (its end)
SHAPE_KEYS = ("n_layers", "d_model", "n_heads", "kv_heads", "head_dim",
              "d_ff", "vocab", "rope_theta")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> Dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_files(name: str):
    """(BENCHMARK.json, its cell entry, the configuration file, the cell's
    settings: its workload file merged with its traffic file)."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"bench: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    wl = dict(load_json("bench", "workloads", name + ".json"),
              **load_json("bench", "traffic", cell["traffic"] + ".json"))
    return bench, cell, load_json(conf_entry["file"]), wl


class CompileCounter:
    """Counts JAX compilation events (tracing, lowering, compiling)."""

    def __init__(self):
        import jax

        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += secs


def program_config(conf: Dict):
    """The program's ArchConfig for a configuration file: the program's
    registered architecture with the file's sizes, all of which are
    checked, and the file's dtype."""
    from repro.configs import get_config

    m = conf["model"]
    cfg = get_config(conf["arch"])
    cfg = dataclasses.replace(
        cfg, **{k: m[k] for k in SHAPE_KEYS}, act=ACTIVATIONS[m["act"]],
        param_dtype=conf["dtype"], compute_dtype=conf["dtype"])
    if (cfg.norm != "layernorm" or cfg.tie_embeddings or cfg.window
            or cfg.n_experts or m["rotary_fraction"] != 1.0):
        raise ValueError(f"{conf['arch']}: the program's layout differs from "
                         "what the configuration file states")
    return cfg


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One request of the window: when it fell due, when it was handed to
    the engine, and the engine's own state (stamps, output)."""

    due: float
    submitted: float
    state: object

    @property
    def prompt(self) -> List[int]:
        return self.state.request.prompt


@dataclasses.dataclass
class Tick:
    step: int           # engine.step during the tick
    start: float
    end: float
    rows: List[tuple]   # (launch kind, [(new tokens, position)]) per step
    kv_util: float


def planned_rows(engine) -> List[tuple]:
    """(kind, rows) of every step the next tick launches, in launch order
    (each group's prefill chunk, then each group's decode step)."""
    chunk = engine.cfg.prefill_chunk
    out = []
    for g in engine.groups.values():
        rows = [(min(chunk, len(st.request.prompt) - st.next_pos),
                 st.next_pos) for st in g.prefill_rows.values()]
        if rows:
            out.append(("prefill", rows))
    for g in engine.groups.values():
        rows = [(1, st.seq_len) for st in g.decode_rows.values()]
        if rows:
            out.append(("decode", rows))
    return out


def drive(engine, wl: Dict, plan: List[traffic.Planned], seconds: float,
          tier: str, trace_dir: Optional[str]):
    """Run the window (and an open loop's drain). Returns the served
    requests, the ticks, the window's bounds and the traced span."""
    import jax

    from repro.serve import Request

    served: List[Served] = []
    ticks: List[Tick] = []
    open_loop = wl["loop"] == "open"
    queues = {}
    if not open_loop:
        for p in plan:
            queues.setdefault(p.client, []).append(p)
    current = {}
    t0 = time.perf_counter()
    window_end = None
    traced = None
    nxt = 0

    def submit(p, due):
        st = engine.submit(Request(prompt=p.prompt,
                                   max_new_tokens=p.max_new_tokens,
                                   policy=tier))
        served.append(Served(due=due, submitted=time.perf_counter(),
                             state=st))
        return st

    while True:
        now = time.perf_counter()
        if window_end is None:
            # an open loop hands over what fell due before the window
            # closes; a closed loop's clients send nothing once it has
            if open_loop:
                while nxt < len(plan) and t0 + plan[nxt].due_s <= now:
                    submit(plan[nxt], t0 + plan[nxt].due_s)
                    nxt += 1
            elif now - t0 < seconds:
                for c, q in queues.items():
                    st = current.get(c)
                    if st is not None and not st.finish_reason:
                        continue
                    if not q:
                        raise RuntimeError(f"closed-loop client {c} has "
                                           "sent its whole plan")
                    due = st.finish_time if st is not None else t0
                    current[c] = submit(q.pop(0), due)
            if now - t0 >= seconds:
                window_end = now
                if traced is not None:
                    traced = (traced[0], now)
                    jax.profiler.stop_trace()
        if window_end is not None:
            pending = [s for s in served if not s.state.finish_reason]
            if not pending or now - window_end > DRAIN_SECONDS:
                break
        if (trace_dir and traced is None and window_end is None
                and now - t0 >= seconds * (1 - TRACE_SHARE)):
            jax.profiler.start_trace(trace_dir)
            traced = (time.perf_counter(), None)
        tracing = traced is not None and traced[1] is None
        if any(g.sched.has_work for g in engine.groups.values()):
            rows = planned_rows(engine) if tracing else []
            step = engine.step
            start = time.perf_counter()
            if tracing:
                with jax.profiler.TraceAnnotation("bench.tick"):
                    engine.tick()
            else:
                engine.tick()
            end = time.perf_counter()
            ticks.append(Tick(step, start, end, rows,
                              engine.pool.utilization()["pool_util"]))
        else:
            until = t0 + (plan[nxt].due_s if open_loop and nxt < len(plan)
                          else seconds)
            wait = max(0.0, min(until - now, 0.05))
            if tracing:
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(wait)
            else:
                time.sleep(wait)
    return served, ticks, (t0, window_end), traced


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def end_to_end(names: List[str], served: List[Served], window, end_time,
               setup_s: float) -> Dict:
    t0, t1 = window
    in_window = [s for s in served if s.due <= t1]
    out = {}
    for name in names:
        if name == "setup_s":
            out[name] = {"value": setup_s, "unit": "s"}
        elif name == "tokens_per_s":
            # every token emitted by the tick boundary that closed the window
            tokens = sum(sum(1 for t in token_times(s) if t <= t1)
                         for s in served)
            out[name] = {"value": tokens / (t1 - t0), "unit": "tokens/s"}
        elif name == "ttft_p95_ms":
            ttft = [((s.state.first_token_time or end_time) - s.due) * 1e3
                    for s in in_window]
            out[name] = {"value": pct(ttft, 95), "unit": "ms"}
        elif name == "itl_p95_ms":
            gaps = [g * 1e3 for s in in_window for g in s.state.token_gaps_s]
            out[name] = {"value": pct(gaps, 95), "unit": "ms"}
        else:
            raise KeyError(f"no end-to-end metric {name!r} in the harness")
    return out


def token_times(s: Served) -> List[float]:
    st = s.state
    if not st.first_token_time:
        return []
    return list(st.first_token_time + np.concatenate(
        [[0.0], np.cumsum(st.token_gaps_s)]))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_shardings(model, mesh):
    """The program's own layout of its parameters over a ``model`` mesh
    axis, as ``ServeEngine`` lays them out, so that weights too large for
    one chip are made where they are served."""
    import jax

    from repro.models.module import axes_tree
    from repro.parallel.sharding import (Sharder, base_rules,
                                         tree_shardings, use_sharder)

    sharder = Sharder(mesh, base_rules(False, serve=True))
    with use_sharder(sharder):
        shapes, axes = model.init(jax.random.PRNGKey(0), abstract=True)
    return tree_shardings(sharder, shapes, axes_tree(shapes, axes))


def run_cell(name: str, bench: Dict, cell: Dict, conf: Dict, wl: Dict,
             seed: int, seconds: float, trace: bool, control: bool,
             devices) -> Dict:
    """One run on ``devices`` (the cell's chips); a cell of several chips
    serves one tensor-parallel engine over a ``model`` mesh axis."""
    import jax

    from repro.models.registry import build_model
    from repro.serve import EngineConfig, Request, ServeEngine

    cfg = program_config(conf)
    model = build_model(cfg)
    shapes, _ = model.init(jax.random.PRNGKey(0), abstract=True)
    spec = weights.tree_spec(shapes)
    mesh = shardings = None
    if len(devices) > 1:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((len(devices),), ("model",))
        shardings = program_shardings(model, mesh)
    params = weights.make_params(shapes, seed, shardings)
    tier = wl["tier"]["name"]
    engine = ServeEngine(model, params, EngineConfig(
        **wl["engine"], shards=len(devices),
        tiers=((tier, wl["tier"]["spec"]),)), mesh=mesh)
    vocab = conf["model"]["vocab"]
    engine.submit(Request(prompt=traffic.warmup_prompt(wl, vocab),
                          max_new_tokens=2, policy=tier))
    while engine.tick():
        pass
    plan = traffic.schedule(wl, seed, seconds, vocab)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, "bench", "out", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = CompileCounter()
    setup_s = time.perf_counter() - T_START
    log(f"bench: {name} seed {seed}: set-up {setup_s:.3f}s, {len(plan)} "
        f"requests planned")
    served, ticks, window, traced = drive(engine, wl, plan, seconds, tier,
                                          trace_dir)
    end_time = time.perf_counter()
    compile_events = compiles.events
    log(f"bench: window {window[1] - window[0]:.3f}s, {len(ticks)} ticks; "
        f"{compile_events} compile events ({compiles.seconds:.3f}s) in "
        "the window and the drain")
    late = [(s.submitted - s.due) * 1e3 for s in served]
    log(f"bench: generator late p50 {pct(late, 50)} / max "
        f"{max(late) if late else None} ms over {len(late)} requests")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices) or None
    in_window = [s for s in served if s.due <= window[1]]
    failed = sum(1 for s in in_window if not s.state.first_token_time)
    records = [(list(s.prompt), list(s.state.output),
                bool(s.state.finish_reason)) for s in served]
    del engine, params, served[:]
    gc.collect()

    compared = check.compare(conf, wl, spec, seed, records, control=control)
    log(f"bench: compared {compared.get('sample')}; control "
        f"{compared.get('control')}")

    result = {"correct": bool(compared["correct"]) and failed == 0
              and len(in_window) > 0,
              "attempted": len(in_window), "failed": failed}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        from bench import trace as trace_mod

        summary = trace_mod.summarize(trace_dir,
                                      device_ids=[d.id for d in devices])
        run = dict(name=name, conf=conf, wl=wl, ticks=ticks, window=window,
                   traced=traced, trace=summary, in_window_states=in_window,
                   device_kind=devices[0].device_kind)
        metrics = {}
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result["metrics"] = metrics
        result["breakdown"] = summary["breakdown"]
    else:
        names = [m["name"] for m in bench["end_to_end"]
                 if name in m.get("workloads", [name])]
        result["metrics"] = end_to_end(names, in_window, window, end_time,
                                       setup_s)
    result["device"] = dev
    ttft = [((s.state.first_token_time or end_time) - s.due) * 1e3
            for s in in_window]
    ends = {t.step: t.end for t in ticks}
    waits = [(ends[s.state.admit_step] - s.due) * 1e3 for s in in_window
             if s.state.admit_step in ends]
    third = max(1, len(waits) // 3)
    result["info"] = {
        "requests": len(in_window),
        "wait_first_third_ms": float(np.mean(waits[:third])) if waits
        else None,
        "wait_last_third_ms": float(np.mean(waits[-third:])) if waits
        else None,
        "tokens": sum(len(r[1]) for r in records),
        "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
        "itl_p50_ms": pct([g * 1e3 for s in in_window
                           for g in s.state.token_gaps_s], 50),
        "drain_s": end_time - window[1], "ticks": len(ticks),
        "sample": compared.get("sample")}
    result["generator_late_ms_max"] = max(late) if late else None
    result["compile_events_in_window"] = compile_events
    if control:
        result["control"] = compared["control"]
    result["compared"] = compared["numbers"]
    for key, v in compared["numbers"].items():
        log(f"compared {key} {v['value']} limit {v['limit']}")
    return result


def take_chip(name: str, cell: Dict):
    """The cell's TPU chips, with the compile cache placed; exits (no
    result) where JAX finds no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(f"bench: cell {name} needs {cell['chips']} TPU chip(s); "
                 f"JAX sees {len(devices)} {devices[0].platform} device(s). "
                 "No result.")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices[:cell["chips"]]


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench, cell, conf, wl = cell_files(args.workload)
    devices = take_chip(args.workload, cell)
    result = run_cell(args.workload, bench, cell, conf, wl, args.seed,
                      args.seconds, bool(args.trace), False, devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
