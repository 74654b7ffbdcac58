"""Tensor-parallel paged serving: 4-way sharded engine token identity.

Runs in subprocesses (the sharded engine needs 4 fake devices; the main
pytest process keeps the default single-device environment). Two claims:

* mixed-tier Poisson traffic served by the 4-shard engine is
  token-identical to the single-device engine (same EngineConfig), and
* a preempt/swap/resume cycle on the sharded engine is token-identical too
  — the page gather/scatter swap path crosses shards without corruption, and
* the full composition — 4-way sharded + preempting + self-speculative
  decode (cheap draft, exact batched verify) — still matches the plain
  single-device reserve engine token-for-token.

The smoke model runs f32 compute: the row-parallel output projections
psum partial sums in a different order per mesh size, which at bf16
(eps ~ 8e-3) is enough to flip near-tied argmaxes on a random toy model;
at f32 the reorder noise (~1e-6) is far below toy logit gaps.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             poisson_requests)

    assert jax.device_count() == 4, jax.devices()
    cfg = get_config("tinyllama_1_1b").smoke(
        n_layers=2, vocab=128, window=0, kv_heads=4,
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    TIERS = (("free", "*=pc3_tr"), ("paid", "*=exact"))

    def outputs(report):
        return {s.request_id: s.output for s in report.completed}

    def requests(seed):
        return poisson_requests(6, cfg.vocab, rate=0.5, base_prompt=7,
                                base_gen=10, seed=seed,
                                tiers=["free", "paid"])
""")

_SHARDED = _COMMON + textwrap.dedent("""
    base = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, prefill_chunk=8,
        tiers=TIERS))
    ref = outputs(base.run(requests(0)))

    mesh = make_mesh((4,), ("model",))
    eng = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, prefill_chunk=8,
        tiers=TIERS, shards=4), mesh=mesh)
    rep = eng.run(requests(0))
    assert rep.shards == 4, rep.shards
    assert rep.policy_groups == 2, rep.policy_groups
    got = outputs(rep)
    assert got == ref, {k: (got[k], ref[k]) for k in got if got[k] != ref[k]}
    print("SHARDED-IDENTICAL-OK")
""")

_PREEMPT = _COMMON + textwrap.dedent("""
    reqs = poisson_requests(6, cfg.vocab, rate=1.0, base_prompt=7,
                            base_gen=14, seed=1, tiers=["free", "paid"])
    def fresh():
        return [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                        arrival_step=r.arrival_step, policy=r.policy)
                for r in reqs]
    base = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, prefill_chunk=8,
        tiers=TIERS))
    ref = outputs(base.run(fresh()))

    mesh = make_mesh((4,), ("model",))
    # 8-page pool against rows growing to 3 pages each: exhaustion is
    # guaranteed under concurrent decode, so the swap path really runs
    eng = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, num_blocks=8,
        prefill_chunk=8, tiers=TIERS, shards=4, preempt=True), mesh=mesh)
    rep = eng.run(fresh())
    assert rep.preemptions >= 1, "pool never exhausted; shrink it"
    assert rep.resumes == rep.preemptions
    got = outputs(rep)
    assert got == ref, {k: (got[k], ref[k]) for k in got if got[k] != ref[k]}
    print("SHARDED-PREEMPT-OK", rep.preemptions, rep.resumes)
""")

_SPEC = _COMMON + textwrap.dedent("""
    reqs = poisson_requests(6, cfg.vocab, rate=1.0, base_prompt=7,
                            base_gen=14, seed=1, tiers=["free", "paid"])
    def fresh():
        return [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                        arrival_step=r.arrival_step, policy=r.policy)
                for r in reqs]
    base = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, prefill_chunk=8,
        tiers=TIERS))
    ref = outputs(base.run(fresh()))

    mesh = make_mesh((4,), ("model",))
    # sharded + preempting + speculative: the draft chain, batched verify,
    # page rollback, and swap path all cross the 4-way mesh together
    eng = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=48, block_size=8, num_blocks=8,
        prefill_chunk=8, tiers=TIERS, shards=4, preempt=True,
        spec_draft="*=pc3_tr", spec_k=3), mesh=mesh)
    rep = eng.run(fresh())
    assert rep.shards == 4, rep.shards
    assert rep.spec_steps >= 1, "speculation never ran"
    assert rep.preemptions >= 1, "pool never exhausted; shrink it"
    got = outputs(rep)
    assert got == ref, {k: (got[k], ref[k]) for k in got if got[k] != ref[k]}
    stats = eng.pool.stats()
    assert stats["blocks_in_use"] == 0, stats
    print("SHARDED-SPEC-PREEMPT-OK", rep.spec_steps,
          round(rep.spec_tokens_per_step, 2))
""")

_MISMATCH = _COMMON + textwrap.dedent("""
    mesh = make_mesh((4,), ("model",))
    try:
        ServeEngine(model, params, EngineConfig(
            num_slots=3, max_seq=48, block_size=8, prefill_chunk=8,
            shards=4), mesh=mesh)
    except ValueError as e:
        assert "divisible" in str(e) and "SRV007" in str(e), e
        print("DIVISIBILITY-REJECTED-OK")
""")


def _run(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)


@pytest.mark.slow
def test_sharded_engine_token_identical_mixed_tier_poisson():
    out = _run(_SHARDED)
    assert "SHARDED-IDENTICAL-OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_sharded_engine_preempt_resume_token_identical():
    out = _run(_PREEMPT)
    assert "SHARDED-PREEMPT-OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_sharded_engine_spec_preempt_token_identical():
    out = _run(_SPEC)
    assert "SHARDED-SPEC-PREEMPT-OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_sharded_engine_rejects_indivisible_layout():
    out = _run(_MISMATCH)
    assert "DIVISIBILITY-REJECTED-OK" in out.stdout, out.stderr[-3000:]
