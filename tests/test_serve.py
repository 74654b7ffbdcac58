"""Serving-engine tests: scheduler admit/retire, continuous batching,
row reuse isolation, and token-identity of the paged (block-table,
chunked-prefill) engine vs. the single-request decode_step path —
including under mixed per-request approximation policies and prefix-cache
block reuse."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.runtime.watchdog import StepWatchdog
from repro.serve import (EngineConfig, Request, Scheduler, ServeEngine,
                         poisson_requests, synthetic_requests)

MAX_SEQ = 48


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=128)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


MIXED_SPEC = "*/layer_0/*=exact,@lm_head=exact,*=pc3_tr"


def _reference_generate(model, params, prompt, max_new):
    """The existing single-request path: scalar-pos cache, one decode_step
    per prompt/generated token. The oracle batched serving must match."""
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(1, MAX_SEQ)
    toks = jnp.asarray([prompt], jnp.int32)
    logits = None
    for t in range(len(prompt)):
        logits, cache = decode(params, toks[:, t:t + 1], cache)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    while len(out) < max_new:
        logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32), cache)
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


# ---------------------------------------------------------------------------
# Scheduler (pure logic, no jax)
# ---------------------------------------------------------------------------

def test_scheduler_admits_and_retires():
    sched = Scheduler(num_slots=2)
    for _ in range(3):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=4))
    admitted = sched.admit(step=0)
    assert [s.slot for s in admitted] == [0, 1]
    assert sched.free_slots == 0 and len(sched.waiting) == 1
    assert sched.admit(step=1) == []  # no free slot -> nobody admitted

    done = sched.retire(0, "length", step=5)
    assert done.finish_reason == "length" and done.slot == -1
    assert sched.free_slots == 1

    late = sched.admit(step=6)
    assert len(late) == 1 and late[0].slot == 0  # freed slot is reused
    assert late[0].joined_running_batch  # slot 1 was still decoding
    assert late[0].request_id == 2
    sched.retire(0, "eos", step=8)
    sched.retire(1, "length", step=8)
    assert not sched.has_work and sched.free_slots == 2


def test_scheduler_arrival_step_gating():
    sched = Scheduler(num_slots=4)
    sched.submit(Request(prompt=[1], max_new_tokens=2, arrival_step=0))
    sched.submit(Request(prompt=[2], max_new_tokens=2, arrival_step=5))
    assert len(sched.admit(step=0)) == 1  # the future arrival must wait
    assert sched.admit(step=4) == []
    assert len(sched.admit(step=5)) == 1


def test_scheduler_unarrived_head_does_not_block():
    """Non-monotonic arrival trace: an unarrived head-of-queue request must
    not starve arrived requests queued behind it."""
    sched = Scheduler(num_slots=2)
    sched.submit(Request(prompt=[1], max_new_tokens=2, arrival_step=10))
    sched.submit(Request(prompt=[2], max_new_tokens=2, arrival_step=0))
    admitted = sched.admit(step=0)
    assert [s.request_id for s in admitted] == [1]
    assert [s.request_id for s in sched.waiting] == [0]  # order preserved
    assert [s.request_id for s in sched.admit(step=10)] == [0]


# ---------------------------------------------------------------------------
# Engine vs. the single-request oracle
# ---------------------------------------------------------------------------

def test_batched_decode_token_identical_to_single_request(served):
    """5 mixed-length requests over 2 slots (forcing slot reuse and
    mid-stream joins) generate exactly the tokens the legacy path does."""
    cfg, model, params = served
    requests = synthetic_requests(5, cfg.vocab, base_prompt=6, base_gen=6,
                                  seed=3)
    expected = {i: _reference_generate(model, params, r.prompt,
                                       r.max_new_tokens)
                for i, r in enumerate(requests)}

    engine = ServeEngine(model, params, EngineConfig(num_slots=2,
                                                     max_seq=MAX_SEQ))
    report = engine.run(requests)
    assert len(report.completed) == 5
    assert report.joined_mid_stream >= 1  # continuous batching exercised
    for state in report.completed:
        assert state.output == expected[state.request_id], state.request_id


def test_slot_reuse_does_not_leak_kv(served):
    """The same prompt served fresh and after slot reuse (with different
    neighbors in the batch) must generate identical tokens — any stale K/V
    from the previous occupant would corrupt the reused slot."""
    cfg, model, params = served
    rng = np.random.default_rng(7)
    twin = rng.integers(0, cfg.vocab, size=7).tolist()
    other = [rng.integers(0, cfg.vocab, size=int(n)).tolist()
             for n in (5, 9, 6)]
    requests = [
        Request(prompt=twin, max_new_tokens=6),      # first wave, slot 0
        Request(prompt=other[0], max_new_tokens=12),  # long-running neighbor
        Request(prompt=other[1], max_new_tokens=4),
        Request(prompt=twin, max_new_tokens=6),      # lands in a reused slot
        Request(prompt=other[2], max_new_tokens=3),
    ]
    engine = ServeEngine(model, params, EngineConfig(num_slots=2,
                                                     max_seq=MAX_SEQ))
    report = engine.run(requests)
    by_id = {s.request_id: s for s in report.completed}
    assert by_id[3].admit_step > 0  # actually reused a slot mid-stream
    assert by_id[0].output == by_id[3].output


def test_eos_retires_early(served):
    cfg, model, params = served
    prompt = [3, 14, 15, 92, 65]
    ref = _reference_generate(model, params, prompt, 8)
    eos = ref[2]
    engine = ServeEngine(model, params, EngineConfig(num_slots=1,
                                                     max_seq=MAX_SEQ))
    report = engine.run([Request(prompt=prompt, max_new_tokens=8,
                                 eos_id=eos)])
    state = report.completed[0]
    assert state.finish_reason == "eos"
    assert state.output == ref[:3]


def test_invalid_requests_rejected(served):
    cfg, model, params = served
    engine = ServeEngine(model, params, EngineConfig(num_slots=1,
                                                     max_seq=16))
    with pytest.raises(ValueError, match="max_seq"):
        engine.submit(Request(prompt=[1] * 10, max_new_tokens=10))
    with pytest.raises(ValueError, match="non-empty"):
        engine.submit(Request(prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=0))


def test_prefill_matches_step_decode_logits(served):
    """Model-level: one batched prefill == stepping the prompt through the
    cache (the old serve path), including right-padded rows."""
    cfg, model, params = served
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0, cfg.vocab)

    cache = model.init_cache(1, 16)
    step_logits = []
    for t in range(6):
        lg, cache = model.decode_step(params, toks[:, t:t + 1], cache)
        step_logits.append(np.asarray(lg[:, 0], np.float32))
    ref = np.stack(step_logits, 1)

    # same prompt right-padded to 8 in a 2-row batch: rows are independent
    padded = jnp.zeros((2, 8), jnp.int32).at[0, :6].set(toks[0])
    c2 = model.init_cache(2, 16)
    plg, c2 = model.prefill(params, padded, c2)
    np.testing.assert_allclose(np.asarray(plg[:1, :6], np.float32), ref,
                               rtol=1e-5, atol=1e-5)
    assert int(c2["pos"]) == 8


# ---------------------------------------------------------------------------
# Paged engine: chunked prefill, per-request policies, prefix caching
# ---------------------------------------------------------------------------

def test_chunked_prefill_token_identical_with_small_blocks(served):
    """Prompts longer than prefill_chunk (multi-chunk ingestion) over small
    KV pages (multi-block tables) still generate exactly the tokens of the
    single-request path."""
    cfg, model, params = served
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).tolist()
               for n in (19, 5, 26, 11)]
    requests = [Request(prompt=p, max_new_tokens=4 + i)
                for i, p in enumerate(prompts)]
    expected = [_reference_generate(model, params, r.prompt,
                                    r.max_new_tokens) for r in requests]
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=MAX_SEQ, block_size=8, prefill_chunk=8))
    report = engine.run(requests)
    assert len(report.completed) == 4
    for state in report.completed:
        assert state.output == expected[state.request_id], state.request_id
    # multi-chunk prefill actually happened: the longest prompt needs 4 ticks
    assert max(s.admit_step for s in report.completed) >= 0
    assert report.kv_util_peak > 0


def test_mixed_policy_tiers_token_identical(served):
    """Per-request policies: base-tier and approximate-tier requests served
    concurrently each match their own single-request oracle, and the engine
    runs one policy group per resolved tier."""
    cfg, model, params = served
    from repro.models.registry import build_model
    approx_model = build_model(cfg.with_policy(MIXED_SPEC))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).tolist()
               for n in (6, 9, 7)]
    requests = [
        Request(prompt=prompts[0], max_new_tokens=5),               # base
        Request(prompt=prompts[1], max_new_tokens=4, policy="free"),
        Request(prompt=prompts[2], max_new_tokens=4, policy=MIXED_SPEC),
    ]
    expected = {
        0: _reference_generate(model, params, prompts[0], 5),
        1: _reference_generate(approx_model, params, prompts[1], 4),
        2: _reference_generate(approx_model, params, prompts[2], 4),
    }
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=MAX_SEQ, tiers=(("free", MIXED_SPEC),)))
    report = engine.run(requests)
    assert len(report.completed) == 3
    for state in report.completed:
        assert state.output == expected[state.request_id], state.request_id
    # tier name and equivalent raw spec share one group (one jit'd step)
    assert report.policy_groups == 2


def test_prefix_cache_reuses_blocks_and_stays_identical(served):
    """A later identical prompt adopts the committed prompt blocks
    (cached_len > 0, pool prefix hits) and still generates the exact same
    tokens as the from-scratch path."""
    cfg, model, params = served
    rng = np.random.default_rng(29)
    prompt = rng.integers(0, cfg.vocab, size=21).tolist()
    requests = [
        Request(prompt=prompt, max_new_tokens=4),
        Request(prompt=prompt, max_new_tokens=4, arrival_step=14),
    ]
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=MAX_SEQ, block_size=8, prefill_chunk=8))
    report = engine.run(requests)
    by_id = {s.request_id: s for s in report.completed}
    assert by_id[1].cached_len >= 8       # at least one full block adopted
    assert report.prefix_hits >= 1
    assert by_id[0].output == by_id[1].output
    assert by_id[0].output == _reference_generate(model, params, prompt, 4)


def test_paged_pool_exceeds_equal_memory_slot_concurrency(served):
    """With pool memory worth 2 max_seq slots, the paged engine runs >2
    short requests concurrently — the concurrency the slot pool capped."""
    cfg, model, params = served
    # pool = 6 blocks of 8 cells = 48 cells = one old max_seq=48 slot * 2...
    # 96 cells == 2 slots of max_seq=48; short requests need 2 blocks each
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=MAX_SEQ, block_size=8, num_blocks=12,
        prefill_chunk=8))
    requests = synthetic_requests(4, cfg.vocab, base_prompt=6, base_gen=6,
                                  seed=5)
    report = engine.run(requests)
    assert len(report.completed) == 4
    assert report.peak_active_requests > 2  # beats the 2-slot equal-memory cap
    for state in report.completed:
        expected = _reference_generate(model, params, state.request.prompt,
                                       state.request.max_new_tokens)
        assert state.output == expected, state.request_id


def test_admission_blocks_on_pool_exhaustion_then_drains(served):
    """A pool too small for two concurrent requests serializes them via
    admission control instead of deadlocking or corrupting K/V."""
    cfg, model, params = served
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=32, block_size=8, num_blocks=3,
        prefill_chunk=8))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=9).tolist() for _ in range(2)]
    requests = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    report = engine.run(requests)  # each needs 2 blocks; only 3 exist
    assert len(report.completed) == 2
    assert report.peak_active_requests == 1  # second waited for pages
    for state in report.completed:
        expected = _reference_generate(model, params, state.request.prompt, 6)
        assert state.output == expected


def test_engine_config_validation():
    with pytest.raises(ValueError, match="num_slots"):
        EngineConfig(num_slots=0)
    with pytest.raises(ValueError, match="block_size"):
        EngineConfig(block_size=-1)
    with pytest.raises(ValueError, match="multiple"):
        EngineConfig(max_seq=40, block_size=16)
    with pytest.raises(ValueError, match="prefill_chunk.*must be\n?.*<="):
        EngineConfig(max_seq=16, prefill_chunk=32)
    with pytest.raises(ValueError, match="power of two"):
        EngineConfig(max_seq=96, prefill_chunk=12)
    with pytest.raises(ValueError, match="tiers"):
        EngineConfig(tiers=(("free", 3),))
    # dict ergonomics + parse_tiers round trip
    from repro.serve import parse_tiers
    tiers = parse_tiers("free=*=pc3_tr;paid=*/attn/*=exact,*=pc3_tr")
    assert tiers == (("free", "*=pc3_tr"),
                     ("paid", "*/attn/*=exact,*=pc3_tr"))
    assert EngineConfig(tiers=dict(tiers)).tiers == tiers
    with pytest.raises(ValueError, match="tier entry"):
        parse_tiers("freepc3_tr")


def test_unknown_tier_rejected(served):
    cfg, model, params = served
    engine = ServeEngine(model, params, EngineConfig(num_slots=1,
                                                     max_seq=16))
    with pytest.raises(ValueError, match="unknown policy tier"):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=2,
                              policy="gold"))


# ---------------------------------------------------------------------------
# Async tick loop, report schema, preemption/swap, sharding config
# ---------------------------------------------------------------------------

def test_scheduler_priority_admission_and_requeue():
    sched = Scheduler(num_slots=1)
    sched.submit(Request(prompt=[1], max_new_tokens=2, priority=0))
    sched.submit(Request(prompt=[2], max_new_tokens=2, priority=5))
    admitted = sched.admit(step=0)
    assert [s.request_id for s in admitted] == [1]  # higher priority wins
    # preemption re-enters at the *front*, ahead of the equal-priority waiter
    sched.submit(Request(prompt=[3], max_new_tokens=2, priority=0))
    state = sched.requeue(admitted[0].slot)
    assert state.preemptions == 1 and state.slot == -1
    assert [s.request_id for s in sched.waiting][0] == 1
    assert [s.request_id for s in sched.admit(step=1)] == [1]


def test_report_schema_latency_percentiles_and_idle(served):
    """Satellite: ServeReport's percentile/async/preemption fields are
    schema-stable — downstream (launch/serve.py) reads them
    by name."""
    cfg, model, params = served
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=32, block_size=8, prefill_chunk=8))
    rng = np.random.default_rng(7)
    requests = [Request(prompt=rng.integers(0, cfg.vocab, size=6).tolist(),
                        max_new_tokens=5) for _ in range(3)]
    report = engine.run(requests)
    for prefix in ("ttft", "latency", "tok_lat"):
        p50, p95, p99 = (getattr(report, f"{prefix}_p{q}_ms")
                         for q in (50, 95, 99))
        assert 0.0 <= p50 <= p95 <= p99
    assert report.ticks > 0
    assert report.host_idle_s >= 0.0
    assert 0.0 <= report.host_idle_frac <= 1.0
    assert report.preemptions == 0 and report.resumes == 0
    assert report.shards == 1
    gaps = sum(len(s.token_gaps_s) for s in report.completed)
    assert gaps == report.generated_tokens - len(report.completed)


def test_sync_tick_loop_token_identical_to_async(served):
    """overlap=False (the synchronous baseline) runs the same schedule —
    admission and batch composition — so tokens must match exactly."""
    cfg, model, params = served
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(5, 12, size=4)]

    def run(overlap):
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=2, max_seq=32, block_size=8, prefill_chunk=8,
            overlap=overlap))
        report = engine.run([
            Request(prompt=p, max_new_tokens=6, arrival_step=i)
            for i, p in enumerate(prompts)])
        return report

    fast, base = run(True), run(False)
    assert ([s.output for s in fast.completed]
            == [s.output for s in base.completed])
    assert fast.host_idle_s >= 0.0 and base.host_idle_s >= 0.0


def test_preempt_then_resume_token_identical(served):
    """Under page exhaustion the preempting engine swaps a victim's pages
    to host and resumes it later; greedy decode must be unaffected."""
    cfg, model, params = served
    # 1-block prompts that grow to 3 blocks each against a 4-page pool:
    # concurrent decode exhausts the pool and forces swaps
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=32, block_size=8, num_blocks=4,
        prefill_chunk=8, preempt=True))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=6).tolist() for _ in range(3)]
    requests = [Request(prompt=p, max_new_tokens=12) for p in prompts]
    report = engine.run(requests)
    assert report.preemptions >= 1 and report.resumes >= 1
    assert report.resumes == report.preemptions  # everyone came back
    kinds = {ev["event"] for ev in report.events}
    assert {"preempt", "resume"} <= kinds
    assert len(report.completed) == 3
    assert max(s.preemptions for s in report.completed) >= 1
    for state in report.completed:
        expected = _reference_generate(model, params, state.request.prompt,
                                       12)
        assert state.output == expected, f"req {state.request_id} diverged"


def test_preempt_sustains_higher_concurrency_than_reservation(served):
    """Acceptance: optimistic admission + swap serves >= 2x the concurrent
    requests of whole-lifetime reservation from the same pool."""
    cfg, model, params = served
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab, size=6).tolist() for _ in range(2)]

    def run(preempt):
        # each request: 1-block prompt, 3-block lifetime; the 3-page pool
        # fits only one whole lifetime but two prompts
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=2, max_seq=32, block_size=8, num_blocks=3,
            prefill_chunk=8, preempt=preempt))
        return engine.run([Request(prompt=p, max_new_tokens=12)
                           for p in prompts])

    reserved, preempting = run(False), run(True)
    assert reserved.peak_active_requests == 1
    assert preempting.peak_active_requests >= 2 * \
        reserved.peak_active_requests
    ref = {tuple(s.request.prompt): s.output for s in reserved.completed}
    for state in preempting.completed:
        assert state.output == ref[tuple(state.request.prompt)]


def test_sharded_engine_requires_matching_mesh(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="no mesh"):
        ServeEngine(model, params, EngineConfig(num_slots=4, shards=4))


# ---------------------------------------------------------------------------
# Runtime watchdog (shared by train loop + engine)
# ---------------------------------------------------------------------------

def test_watchdog_skips_warmup_and_counts_stragglers():
    dog = StepWatchdog(factor=3.0, alpha=0.5, warmup=1)
    assert not dog.observe(100.0)  # compile step: excluded from the EWMA
    assert not dog.observe(1.0)    # seeds the EWMA
    assert not dog.observe(1.2)
    assert dog.observe(50.0)       # straggler vs ~1.1 EWMA
    assert dog.stragglers == 1
    assert dog.ewma < 30.0


def test_engine_config_rejects_windowed_model(served):
    """Windowed (ring-buffer) caches cannot be paged; the engine rejects
    the combination at construction with the offending field named."""
    cfg, model, params = served
    windowed = dataclasses.replace(cfg, window=16)
    with pytest.raises(ValueError, match=r"ArchConfig\.window=16 .* paged"):
        EngineConfig().validate_for_model(windowed)
    with pytest.raises(ValueError, match=r"ArchConfig\.window"):
        ServeEngine(build_model(windowed), params, EngineConfig(num_slots=1))


# ---------------------------------------------------------------------------
# Self-speculative decoding (draft with the approximate policy, verify exact)
# ---------------------------------------------------------------------------

SPEC_DRAFT = "*=pc3_tr"


def test_spec_config_validation():
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(spec_k=2)                    # draft policy missing
    with pytest.raises(ValueError, match="spec_draft"):
        EngineConfig(spec_draft=SPEC_DRAFT)       # k missing
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(spec_k=-1, spec_draft=SPEC_DRAFT)
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(max_seq=16, block_size=16, spec_k=16,
                     spec_draft=SPEC_DRAFT)       # k >= max_seq
    with pytest.raises(ValueError, match="spec_min_accept"):
        EngineConfig(spec_k=2, spec_draft=SPEC_DRAFT, spec_min_accept=1.5)
    ok = EngineConfig(spec_k=3, spec_draft=SPEC_DRAFT)
    assert ok.spec_k == 3


def test_paged_verify_step_accept_and_bonus_semantics(served):
    """paged_verify_step against the sequential S=1 oracle: correct drafts
    are accepted up to the first mismatch, and the verify logits at the
    last accepted position supply the bonus token."""
    cfg, model, params = served
    block_size, num_blocks = 8, 4
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab, size=6).tolist()
    table = jnp.arange(num_blocks, dtype=jnp.int32)[None, :]

    def fresh_prefill():
        kv = model.init_paged_cache(num_blocks, block_size)
        cache = dict(kv, block_tables=table, pos=jnp.zeros(1, jnp.int32))
        logits, kv = model.paged_step(
            params, jnp.asarray([prompt], jnp.int32), cache,
            block_size=block_size)
        return int(jnp.argmax(logits[0, -1])), kv

    # sequential oracle: t1 from prefill, then three S=1 decode steps
    t1, kv = fresh_prefill()
    toks = [t1]
    for j in range(3):
        cache = dict(kv, block_tables=table,
                     pos=jnp.asarray([len(prompt) + j], jnp.int32))
        logits, kv = model.paged_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            block_size=block_size)
        toks.append(int(jnp.argmax(logits[0, 0])))
    t1, t2, t3, t4 = toks

    def verify(drafts):
        _, kv = fresh_prefill()
        cache = dict(kv, block_tables=table,
                     pos=jnp.asarray([len(prompt)], jnp.int32))
        greedy, n_acc, _ = model.paged_verify_step(
            params, jnp.asarray([[t1] + drafts, ], jnp.int32), cache,
            block_size=block_size)
        return [int(t) for t in greedy[0]], int(n_acc[0])

    wrong = (t4 + 1) % cfg.vocab
    greedy, n_acc = verify([t2, t3, wrong])
    assert greedy[:3] == [t2, t3, t4]  # per-position argmax == sequential
    assert n_acc == 2                  # third draft rejected
    # emitted = accepted drafts + the bonus token from the verify logits
    assert greedy[:n_acc + 1] == [t2, t3, t4]

    _, n_acc = verify([t2, t3, t4])
    assert n_acc == 3                  # perfect drafts: all accepted
    _, n_acc = verify([(t2 + 1) % cfg.vocab, t3, t4])
    assert n_acc == 0                  # first mismatch gates the rest


def test_spec_decode_token_identical_mixed_tiers(served):
    """Acceptance: speculative decode under mixed-tier Poisson traffic is
    token-identical to plain decode, and the draft tier's own group is
    ineligible (it would verify with the numerics it drafted with)."""
    cfg, model, params = served
    tiers = (("free", SPEC_DRAFT), ("paid", MIXED_SPEC))

    def run(spec):
        ecfg = EngineConfig(
            num_slots=4, max_seq=MAX_SEQ, block_size=8, prefill_chunk=8,
            tiers=tiers,
            spec_draft=SPEC_DRAFT if spec else "", spec_k=3 if spec else 0)
        engine = ServeEngine(model, params, ecfg)
        report = engine.run(poisson_requests(
            8, cfg.vocab, rate=0.5, base_prompt=7, base_gen=10, seed=0,
            tiers=["free", "paid"]))
        return engine, report

    _, plain = run(False)
    engine, spec = run(True)
    assert ([s.output for s in spec.completed]
            == [s.output for s in plain.completed])
    assert spec.spec_steps >= 1
    assert 0.0 <= spec.spec_accept_rate <= 1.0
    assert spec.spec_tokens_per_step >= 1.0  # bonus token floor
    # the free tier resolves to the draft policy: that group never drafts
    by_key = {g.label: g.spec_on for g in engine.groups.values()}
    assert by_key["free"] is False
    assert any(s.spec_drafted > 0 for s in spec.completed)
    for s in spec.completed:
        assert 0 <= s.spec_accepted <= s.spec_drafted


def test_spec_decode_with_preemption_rolls_back_and_drains(served):
    """Speculation + preemption: rejected-draft pages are truncated back to
    the pool, preempted rows resume, tokens stay identical to the plain
    reserve engine, and the pool drains to zero pages in use."""
    cfg, model, params = served
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, size=6).tolist() for _ in range(4)]

    def burst():
        return [Request(prompt=p, max_new_tokens=18) for p in prompts]

    ref = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=32, block_size=8, num_blocks=4,
        prefill_chunk=8)).run(burst())
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=32, block_size=8, num_blocks=4,
        prefill_chunk=8, preempt=True, spec_draft=SPEC_DRAFT, spec_k=3))
    report = engine.run(burst())
    assert report.preemptions >= 1 and report.resumes == report.preemptions
    assert report.spec_steps >= 1
    assert ([s.output for s in report.completed]
            == [s.output for s in ref.completed])
    stats = engine.pool.stats()
    assert stats["blocks_in_use"] == 0  # no leaked speculative pages


def test_spec_controller_disables_low_acceptance_group(served):
    """The EWMA controller shuts a group's speculation off after the warmup
    once acceptance sinks below spec_min_accept, emitting a spec_off
    event; identity never depended on it (the group just runs S=1)."""
    cfg, model, params = served
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_seq=MAX_SEQ, spec_draft=SPEC_DRAFT, spec_k=3,
        spec_min_accept=0.9))
    group = engine._group_for(None)
    assert group.spec_on
    for _ in range(engine._SPEC_WARMUP):
        engine._update_spec_controller(group, [0.0, 0.1])
    assert group.spec_on is False
    offs = [ev for ev in engine.events if ev["event"] == "spec_off"]
    assert len(offs) == 1 and offs[0]["group"] == group.label
    # permanent for the run: further observations don't resurrect it
    engine._update_spec_controller(group, [1.0])
    assert group.spec_on is False


@pytest.mark.parametrize("ev,text", [
    (dict(event="admit", joined_running=True, blocks=2, cached_blocks=1),
     "admit  req 3 -> base/row 1 [2 pages, 1 cached] (joined running"),
    (dict(event="preempt", blocks=2), "preempt req 3 (base/row 1: 2 pages"),
    (dict(event="resume", blocks=3), "resume req 3 -> base/row 1 [3 pages"),
    (dict(event="retire", reason="eos"), "retire req 3 (base/row 1 freed, eos)"),
    (dict(event="spec_off", request_id=-1, slot=-1, ewma=0.125),
     "spec off for group base (acceptance EWMA 0.125)"),
], ids=lambda v: v["event"] if isinstance(v, dict) else "")
def test_serve_cli_prints_every_event_kind(ev, text):
    """The CLI's timeline has a line for each event the engine emits; a
    ``spec_off`` event has no request, row or reason."""
    from repro.launch.serve import event_line

    line = event_line(dict(dict(step=7, request_id=3, slot=1, group="base"),
                           **ev))
    assert line.startswith("step    7  ") and text in line
