"""The reduction of the program's own spans (``bench/spans.py``) and the
readers of the metrics built on it: interval arithmetic, the naming of
idle gaps by the innermost span, the device idle time inside engine ticks,
each reader on a small synthetic run, and both on traces recorded on a
TPU v5e (``bench/tests/data/``): the benchmark's spans alone
(``record_trace.py``) and a tiny ``ServeEngine`` under them
(``record_engine_trace.py``).

    python -m pytest bench/tests
"""
from __future__ import annotations

import glob
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import run, spans, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "v5e_small")
ENGINE = sorted(os.path.dirname(p) for p in glob.glob(
    os.path.join(DATA, "v5e_engine", "**", "*.xplane.pb"), recursive=True))


def test_overlap_of_interval_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45), (60, 70)]
    assert spans.overlap_ns(a, b) == 5 + 5 + 2 + 5
    assert spans.overlap_ns(a, []) == 0
    assert spans.overlap_ns(a, a) == 30


def test_gap_is_named_by_the_innermost_span():
    nested = [("bench.tick", 0, 100), ("engine.tick", 5, 95),
              ("engine.apply", 40, 60), ("bench.wait_arrival", 100, 300)]
    assert spans.name_gap(nested, (45, 55)) == "engine.apply"
    assert spans.name_gap(nested, (50, 70)) == "engine.apply"  # half
    assert spans.name_gap(nested, (55, 75)) == "engine.tick"
    assert spans.name_gap(nested, (10, 30)) == "engine.tick"
    assert spans.name_gap(nested, (96, 99)) == "bench.tick"
    assert spans.name_gap(nested, (150, 200)) == "bench.wait_arrival"
    assert spans.name_gap(nested, (400, 410)) == "host"
    assert spans.name_gap(nested, (280, 360)) == "host"


def test_gap_naming_without_nesting_is_the_harness_naming():
    rng = np.random.default_rng(0)
    for _ in range(200):
        edges = np.sort(rng.choice(1000, size=12, replace=False))
        flat = [(str(rng.choice(["bench.tick", "bench.wait_arrival"])),
                 int(s), int(e)) for s, e in edges.reshape(-1, 2)]
        s, e = sorted(rng.choice(1100, size=2, replace=False))
        gap = (int(s), int(e))
        assert spans.name_gap(flat, gap) == trace.host_activity(flat, gap)


def test_idle_time_inside_ticks():
    bench_spans = [("bench.tick", 0, 100)]
    ticks = [("engine.tick", 5, 40, {}), ("engine.tick", 45, 70, {}),
             ("engine.fetch", 30, 40, {})]
    ops = [[(0, 10), (20, 30), (50, 60)]]
    out = spans.reduce(bench_spans, ticks, ops)
    # idle (10,20) (30,50) (60,100); ticks cover 10 + 10 + 5 + 10 of it
    assert out["idle_in_span_s"]["engine.tick"] == pytest.approx(35e-9)
    assert out["idle_in_tick_s"] == out["idle_in_span_s"]["engine.tick"]
    assert out["idle_in_span_s"]["engine.fetch"] == pytest.approx(10e-9)
    assert out["idle_in_span_s"]["bench.tick"] == pytest.approx(70e-9)
    assert [name for name, _ in out["idle_gaps"]] == [
        "bench.tick", "engine.fetch", "engine.tick"]
    # two chips: the mean of their idle times
    two = spans.reduce(bench_spans, ticks, ops + [[(0, 100)]])
    assert two["idle_in_tick_s"] == pytest.approx(17.5e-9)
    assert spans.reduce(bench_spans, ticks, [])["idle_in_tick_s"] is None


def test_harness_summary_of_the_small_trace_is_unchanged():
    with open(os.path.join(DATA, "v5e_small.summary.json")) as f:
        recorded = json.load(f)
    assert json.loads(json.dumps(trace.summarize(SMALL, [0]))) == recorded


def test_small_trace_without_engine_spans():
    out = spans.summarize(SMALL, [0])
    assert out["engine_spans"] == [] and out["idle_in_tick_s"] is None
    assert out["idle_gaps"] == trace.summarize(
        SMALL, [0])["breakdown"]["idle_gaps"]
    run_ = dict(trace_dir=SMALL, device_ids=[0],
                trace={"window_s": 0.13}, in_window_states=[])
    for name in ("device.idle_in_tick_pct.serve",
                 "step.prefill_fill_pct.serve",
                 "step.prefill_fill_pct.batch"):
        assert run.load_reader(name)(dict(run_)) is None, name


def _served(due, submit, admit, first):
    state = types.SimpleNamespace(submit_time=submit, first_token_time=first)
    if admit is not None:
        state.admit_time = admit
    return types.SimpleNamespace(due=due, state=state)


def test_stamp_readers_on_a_synthetic_run():
    states = [_served(1.0, 1.001, 1.0 + w, 1.5 + w)
              for w in np.linspace(0.0, 0.2, 21)]
    states.append(_served(3.0, 3.0, 0.0, 0.0))          # not admitted
    waits = run.load_reader("sched.admit_wait_p95_ms.serve")
    prefill = run.load_reader("engine.admit_to_first_token_p95_ms.serve")
    assert waits({"in_window_states": states}) == pytest.approx(190.0)
    assert prefill({"in_window_states": states}) == pytest.approx(500.0)
    # a program that stamps no admission: nothing to read
    unstamped = [_served(1.0, 1.0, None, 1.5)]
    assert waits({"in_window_states": unstamped}) is None
    assert prefill({"in_window_states": unstamped}) is None


def test_span_readers_on_a_synthetic_run():
    launch = [("engine.launch", 10, 20, dict(kind="prefill", rows=1,
                                             tokens=100, padded=1024)),
              ("engine.launch", 30, 40, dict(kind="prefill", rows=2,
                                             tokens=156, padded=1024)),
              ("engine.launch", 50, 60, dict(kind="decode", rows=3,
                                             tokens=3, padded=8))]
    synthetic = {"trace": {"window_s": 2.0},
                 "spans": {"engine_spans": launch, "idle_in_tick_s": 0.1}}
    assert run.load_reader("step.prefill_fill_pct.serve")(
        synthetic) == pytest.approx(12.5)
    assert run.load_reader("device.idle_in_tick_pct.serve")(
        synthetic) == pytest.approx(5.0)


@pytest.fixture(scope="module", params=ENGINE)
def engine_trace(request):
    return (request.param, trace.summarize(request.param, [0]),
            spans.summarize(request.param, [0]))


def test_engine_trace_spans_nest_in_the_benchmark_ticks(engine_trace):
    _, harness, out = engine_trace
    engine = out["engine_spans"]
    ticks = [sp for sp in engine if sp[0] == "engine.tick"]
    assert len(ticks) == 5            # admission, a prefill, three decodes
    launches = [sp[3] for sp in engine if sp[0] == "engine.launch"]
    assert [st["kind"] for st in launches] == ["prefill"] + ["decode"] * 3
    assert len(launches) == len(harness["step_s"])
    for name, s, e, _ in engine:
        assert any(ts <= s and e <= te for _, ts, te, _ in ticks), name


def test_engine_trace_idle_is_named_and_split(engine_trace):
    path, harness, out = engine_trace
    idle = harness["window_s"] - harness["busy_s"]
    assert 0 < out["idle_in_tick_s"] < idle
    phases = sum(v for k, v in out["idle_in_span_s"].items()
                 if k.startswith("engine.") and k != "engine.tick")
    assert phases <= out["idle_in_tick_s"] * (1 + 1e-9)
    names = [name for name, _ in out["idle_gaps"]]
    assert names[:4] == ["bench.wait_arrival"] * 4
    assert any(name.startswith("engine.") for name in names[4:])
    fill = spans.prefill_fill_pct(dict(trace_dir=path, device_ids=[0]))
    assert fill == pytest.approx(100.0 * 12 / (4 * 16))
