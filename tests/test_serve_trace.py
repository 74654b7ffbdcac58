"""The serving engine's profiler spans and request stamps.

Each case serves a tiny workload twice: once with the profiler off, once
under ``jax.profiler.start_trace``. The trace's host plane holds the
engine's spans (``engine.tick`` and one span per tick phase) with their
stats; the tests read them back from the ``.xplane.pb`` and check them
against what the engine did. The cases cover plain serving with a prefix
cache hit, two policy tiers, preemption with swap, and speculative
decoding.
"""
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.serve import EngineConfig, Request, ServeEngine

MIXED_SPEC = "*/layer_0/*=exact,@lm_head=exact,*=pc3_tr"
SPANS = ("engine.tick", "engine.grow", "engine.launch", "engine.admit",
         "engine.fetch", "engine.apply", "engine.swap_out", "engine.swap_in")


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=128)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(vocab, seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in sizes]


def _plain(vocab):
    # a 21-token prompt served twice: the second adopts its full blocks
    shared, a, b = _prompts(vocab, 29, (21, 6, 9))
    ecfg = EngineConfig(num_slots=2, max_seq=48, block_size=8,
                        prefill_chunk=8)
    return ecfg, [Request(prompt=shared, max_new_tokens=4),
                  Request(prompt=a, max_new_tokens=6),
                  Request(prompt=b, max_new_tokens=5),
                  Request(prompt=shared, max_new_tokens=4, arrival_step=14)]


def _tiers(vocab):
    a, b, c = _prompts(vocab, 13, (6, 9, 7))
    ecfg = EngineConfig(num_slots=2, max_seq=48,
                        tiers=(("free", MIXED_SPEC),))
    return ecfg, [Request(prompt=a, max_new_tokens=5),
                  Request(prompt=b, max_new_tokens=4, policy="free"),
                  Request(prompt=c, max_new_tokens=4, policy=MIXED_SPEC)]


def _preempt(vocab):
    # 1-block prompts growing to 3 blocks each against a 4-page pool
    ecfg = EngineConfig(num_slots=2, max_seq=32, block_size=8, num_blocks=4,
                        prefill_chunk=8, preempt=True)
    return ecfg, [Request(prompt=p, max_new_tokens=12)
                  for p in _prompts(vocab, 13, (6, 6, 6))]


def _spec(vocab):
    ecfg = EngineConfig(num_slots=2, max_seq=48, block_size=8,
                        prefill_chunk=8, spec_draft="*=pc3_tr", spec_k=3)
    return ecfg, [Request(prompt=p, max_new_tokens=9)
                  for p in _prompts(vocab, 5, (7, 12, 5))]


CASES = {"plain": _plain, "mixed_tiers": _tiers, "preempt": _preempt,
         "spec": _spec}


def _engine_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every engine span, by start."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module", params=sorted(CASES))
def traced(request, served, tmp_path_factory):
    cfg, model, params = served
    ecfg, requests = CASES[request.param](cfg.vocab)
    off = ServeEngine(model, params, ecfg).run(requests)
    engine = ServeEngine(model, params, ecfg)
    trace_dir = tmp_path_factory.mktemp(f"trace_{request.param}")
    jax.profiler.start_trace(str(trace_dir))
    try:
        report = engine.run(requests)
    finally:
        jax.profiler.stop_trace()
    return dict(case=request.param, cfg=ecfg, off=off, report=report,
                spans=_engine_spans(trace_dir))


def _of(spans, name, **stats):
    return [s for s in spans if s[0] == name
            and all(s[3].get(k) == v for k, v in stats.items())]


def test_every_span_lies_inside_a_tick(traced):
    spans = traced["spans"]
    ticks = _of(spans, "engine.tick")
    names = {s[0] for s in spans}
    assert names <= set(SPANS)
    assert {"engine.tick", "engine.grow", "engine.launch", "engine.admit",
            "engine.fetch", "engine.apply"} <= names
    if traced["case"] == "preempt":
        assert {"engine.swap_out", "engine.swap_in"} <= names
        assert all(s[3]["blocks"] >= 1 for s in _of(spans, "engine.swap_in"))
    for name, start, end, _ in spans:
        if name != "engine.tick":
            assert any(ts <= start and end <= te for _, ts, te, _ in ticks), \
                name
    assert ({s[3]["phase"] for s in _of(spans, "engine.admit")}
            == {"overlap", "post"})


def test_tick_spans_count_engine_steps(traced):
    ticks = _of(traced["spans"], "engine.tick")
    assert len(ticks) == traced["report"].ticks
    assert [s[3]["step_num"] for s in ticks] == list(range(len(ticks)))


def test_prefill_tokens_are_the_prompt_tokens_not_cached(traced):
    ecfg, done = traced["cfg"], traced["report"].completed
    prefill = _of(traced["spans"], "engine.launch", kind="prefill")
    assert sum(s[3]["tokens"] for s in prefill) == sum(
        len(st.request.prompt) - st.cached_len for st in done)
    for _, _, _, stats in prefill:
        assert stats["padded"] == ecfg.num_slots * ecfg.prefill_chunk
        assert 1 <= stats["rows"] <= stats["tokens"] <= stats["padded"]
    if traced["case"] == "plain":
        assert max(st.cached_len for st in done) >= 8


def test_decode_launch_rows_equal_tokens(traced):
    ecfg, spans = traced["cfg"], traced["spans"]
    decode = _of(spans, "engine.launch", kind="decode")
    spec = _of(spans, "engine.launch", kind="spec")
    assert decode or spec
    for _, _, _, stats in decode:
        assert stats["rows"] == stats["tokens"]
        assert stats["padded"] == ecfg.num_slots
    for _, _, _, stats in spec:
        assert stats["tokens"] == stats["rows"] * (ecfg.spec_k + 1)
    assert bool(spec) == (traced["case"] == "spec")
    launches = _of(spans, "engine.launch")
    assert len(_of(spans, "engine.fetch")) == len(launches)
    # every emitted token is counted by exactly one apply span
    assert (sum(s[3]["emitted"] for s in _of(spans, "engine.apply"))
            == traced["report"].generated_tokens)


def test_request_stamps_run_submit_admit_first_token_finish(traced):
    done = traced["report"].completed
    for st in done:
        assert (0 < st.submit_time <= st.admit_time <= st.first_token_time
                <= st.finish_time)
    if traced["case"] == "preempt":
        # resumed after its first token: the first admission is kept
        assert any(st.preemptions for st in done)


def test_outputs_identical_with_profiler_on_and_off(traced):
    assert ([s.output for s in traced["report"].completed]
            == [s.output for s in traced["off"].completed])
