"""The trace reduction, on interval arithmetic and on the small traces
recorded on a TPU v5e under ``bench/tests/data/`` by
``bench/tests/record_trace.py``: four ``jit_step`` programs and two DAISM
Pallas kernels, each in a ``bench.tick`` span and followed by a 20 ms
sleep in a ``bench.wait_arrival`` span.

    python -m pytest bench/tests
"""
from __future__ import annotations

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(os.path.dirname(p) for p in glob.glob(
    os.path.join(DATA, "*", "**", "*.xplane.pb"), recursive=True))


def test_union_and_gaps():
    merged = trace.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)], 1, 25)
    assert merged == [(1, 4), (5, 12), (20, 25)]
    assert trace.gaps(merged, 0, 26) == [(0, 1), (4, 5), (12, 20), (25, 26)]


def test_leaves_leave_out_enclosing_ops():
    # a while op (0-10) holding two kernels, then a lone op
    events = [("k2", 5, 9, None), ("while", 0, 10, None), ("k1", 1, 4, None),
              ("op", 12, 13, None)]
    assert [e[0] for e in trace.leaves(events)] == ["k1", "k2", "op"]
    assert trace.is_kernel('%k = f32[8] custom-call(), custom_call_target='
                           '"tpu_custom_call"', {})
    assert not trace.is_kernel("%while.13 = (s32[]) while()", {})


@pytest.fixture(scope="module", params=RECORDED)
def small(request):
    return trace.summarize(request.param, device_ids=[0])


def test_small_trace_window_and_idle(small):
    # six 20 ms sleeps lie inside the window: at least 120 ms idle
    assert 0.12 < small["window_s"] < 2.0
    idle = small["window_s"] - small["busy_s"]
    assert 0.12 <= idle < small["window_s"]
    assert small["busy_s"] > 0
    names = {name for name, _ in small["breakdown"]["idle_gaps"]}
    assert "bench.wait_arrival" in names
    longest = small["breakdown"]["idle_gaps"][0][1]
    assert 0.019 < longest < 0.2


def test_small_trace_steps_and_kernels(small):
    assert len(small["step_s"]) == 4
    assert all(0 < s < 0.05 for s in small["step_s"])
    assert 0 < small["kernel_s"] < small["busy_s"]
