"""The harness end to end at a tiny size on the CPU, without its look for
a chip: a sound run is correct, and each fault a serving cell can have,
planted in the timed path, makes ``correct`` come out false.

    python -m pytest bench/tests
"""
from __future__ import annotations

import copy
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

BENCH = {"end_to_end": [
    {"name": "setup_s"},
    {"name": "tokens_per_s", "workloads": ["closed"]},
    {"name": "ttft_p95_ms", "workloads": ["open"]},
    {"name": "itl_p95_ms", "workloads": ["open"]}], "per_layer": []}

CONF = {"arch": "starcoder2_15b", "dtype": "bfloat16", "model": {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab": 256, "act": "gelu_tanh",
    "rope_theta": 100000.0, "rotary_fraction": 1.0}}

OPEN = {"tier": {"name": "exact", "spec": "*=exact"},
        "engine": {"num_slots": 4, "prefill_chunk": 16, "max_seq": 64,
                   "block_size": 16},
        "loop": "open", "rate_per_s": 40.0,
        "prompt": {"dist": "uniform", "min": 8, "max": 24},
        "output": {"dist": "uniform", "min": 4, "max": 12},
        "check": {"gemm": "exact", "rows": 512, "served_tokens": 200,
                  "limits": {"widest_gap": 0.1}}}

CLOSED = dict(copy.deepcopy(OPEN), loop="closed", clients=4,
              tier={"name": "approx", "spec": "*=pc3_tr:pallas"},
              check={"gemm": "pc3_tr", "rows": 512, "served_tokens": 200,
                     "limits": {"mean_gap": 0.01}})


def run_tiny(wl, seed=3, seconds=1.5, control=False):
    name = wl["loop"]
    return run.run_cell(name, BENCH, {"chips": 1}, CONF, wl, seed, seconds,
                        trace=False, control=control,
                        devices=jax.devices()[:1])


@pytest.fixture
def fault(monkeypatch):
    from repro.models.transformer import DecoderLM

    original = DecoderLM.paged_step

    def plant(kind):
        def paged_step(self, params, tokens, cache, *, block_size):
            logits, kv = original(self, params, tokens, cache,
                                  block_size=block_size)
            if kind == "state_unchanged":
                kv = {"k": cache["k"], "v": cache["v"]}
            elif kind == "token_altered" and tokens.shape[1] == 1:
                logits = jnp.roll(logits, 1, axis=-1)
            elif kind == "half_batch":
                # the second half of the rows left out: they get the
                # first half's logits
                half = logits.shape[0] // 2
                logits = jnp.concatenate([logits[:half], logits[:half]])
            return logits, kv

        monkeypatch.setattr(DecoderLM, "paged_step", paged_step)

    return plant


@pytest.mark.parametrize("wl", [OPEN, CLOSED], ids=["open", "closed"])
def test_sound_run_is_correct(wl):
    result = run_tiny(wl)
    assert result["correct"], result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compile_events_in_window"] == 0
    assert list(result)[-1] == "compared"
    names = {"open": ("setup_s", "ttft_p95_ms", "itl_p95_ms"),
             "closed": ("setup_s", "tokens_per_s")}[wl["loop"]]
    assert set(result["metrics"]) == set(names)


@pytest.mark.parametrize("kind", ["token_altered", "state_unchanged",
                                  "half_batch"])
@pytest.mark.parametrize("wl", [OPEN, CLOSED], ids=["open", "closed"])
def test_fault_is_not_correct(wl, kind, fault):
    fault(kind)
    result = run_tiny(wl)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("wl", [OPEN, CLOSED], ids=["open", "closed"])
def test_control_is_not_correct(wl):
    result = run_tiny(wl, control=True)
    (name, number), = result["compared"].items()
    assert result["control"][name] > number["value"], (
        number, result["control"])
    assert result["correct"] and not result["control"]["correct"], (
        number, result["control"])
