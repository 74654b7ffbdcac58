"""Serving bench: paged KV vs slot pool, policy tiers, preemption, sharding.

Drives repro.serve.ServeEngine over seeded workloads in several
configurations and backs the repo's serving claims:

* ``slot`` / ``paged`` / ``mixed`` — equal KV memory (128 cells): the paged
  pool completes identical tokens to the slot pool while sustaining
  strictly higher peak concurrency; mixed-tier traffic batches per
  resolved policy.
* ``reserve`` vs ``preempt`` — same undersized pool: optimistic admission
  with preemption/swap admits >= 2x the concurrent requests of
  whole-lifetime reservation, token-identically.
* ``async`` vs ``sync`` — same workload: the async tick loop (overlapping
  host scheduling with the in-flight device step) spends a smaller
  fraction of wall time blocked on device fetches than the synchronous
  baseline (``ServeReport.host_idle_frac``).
* ``spec_pc3_tr`` / ``spec_pc2_tr`` — the mixed-tier engine with
  self-speculative decoding (cheap-draft k=3 + one exact batched verify):
  token-identical to plain, > 1.5 tokens per verify step, accept rate per
  draft tier.
* ``multi_device`` (CPU only) — subprocess children at 1 vs 4 virtual
  CPU devices, equal total KV memory: the 4-way tensor-parallel engine
  (sharded params, KV pages, and decode step) emits identical tokens —
  also with preemption + speculative decoding stacked on top. The children
  run f32 compute so the row-parallel psum reorder (~1e-6) stays far below
  toy logit gaps. On a TPU host the suite refuses to run: a child cannot
  open the chip its parent holds (``chip_smoke.py --four-chips`` covers
  sharded serving on chips).

Wall times on this CPU container measure *relative* overhead (the jnp
bit-op backend is reference semantics, not a fast kernel); deployment
numbers live in gemm_bench.py.

Standalone:  PYTHONPATH=src python benchmarks/serve_bench.py [--arch A ...]
Harness:     PYTHONPATH=src:. python benchmarks/run.py serve_bench
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

TIERS = (("free", "*=pc3_tr"), ("paid", "*/attn/*=exact,*=pc3_tr"))

_MULTIDEV_TIERS = (("free", "*=pc3_tr"), ("paid", "*=exact"))

# claims guarded by ``run.py --check`` against the checked-in
# BENCH_serve.json (direction = which way is better; "bool" claims must
# keep holding). Numeric wall-clock rows are deliberately NOT gated — on
# shared CI machines they are too noisy; the named claims below are the
# correctness/efficiency properties the serving engine actually promises.
REGRESSION_CLAIMS = {
    "paged_tokens_identical_to_slot": "bool",
    "preempt_tokens_identical_to_reserve": "bool",
    "spec_tokens_identical_to_plain": "bool",
    "spec_tokens_per_verify_step_exceeds_1_5": "bool",
    "spec_pc3_tr_tokens_per_step": "higher",
    "multi_device_tokens_identical": "bool",
    "multi_device_spec_preempt_tokens_identical": "bool",
}


def _report_row(name, report, ecfg):
    return {
        "name": name,
        "us_per_call": round(report.step_p50_ms * 1e3, 1),  # decode step
        "tokens_per_s": round(report.tokens_per_s, 1),
        "ttft_p50_ms": round(report.ttft_p50_ms, 1),
        "ttft_p99_ms": round(report.ttft_p99_ms, 1),
        "latency_p99_ms": round(report.latency_p99_ms, 1),
        "kv_util_mean": round(report.kv_util_mean, 3),
        "kv_util_peak": round(report.kv_util_peak, 3),
        "peak_concurrency": report.peak_active_requests,
        "prefix_hits": report.prefix_hits,
        "policy_groups": report.policy_groups,
        "kv_cells": ecfg.blocks * ecfg.block_size,
        "host_idle_frac": round(report.host_idle_frac, 4),
        "preemptions": report.preemptions,
        "shards": report.shards,
    }


def _multidevice_child(devices: int, spec: bool = False) -> None:
    """Child mode: serve a fixed mixed-tier Poisson workload on
    ``devices`` virtual CPU devices (sharded when > 1) and print the
    outputs + report numbers as JSON on stdout. ``spec`` additionally
    turns on preemption and self-speculative decoding — the full
    composition (shards x preempt x spec) vs the plain reserve child."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve import EngineConfig, ServeEngine, poisson_requests

    cfg = get_config("tinyllama_1_1b").smoke(
        n_layers=2, vocab=128, window=0, kv_heads=4,
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((devices,), ("model",)) if devices > 1 else None
    # equal total KV memory across device counts: 16 x 8-token pages
    ecfg = EngineConfig(num_slots=4, max_seq=48, block_size=8,
                        num_blocks=16, prefill_chunk=8,
                        tiers=_MULTIDEV_TIERS, shards=devices,
                        preempt=spec,
                        spec_draft="*=pc3_tr" if spec else "",
                        spec_k=3 if spec else 0)
    engine = ServeEngine(model, params, ecfg, mesh=mesh)
    report = engine.run(poisson_requests(
        8, cfg.vocab, rate=0.5, base_prompt=7, base_gen=10, seed=0,
        tiers=[name for name, _ in _MULTIDEV_TIERS]))
    suffix = "_spec" if spec else ""
    print(json.dumps({
        "devices": devices,
        "shards": report.shards,
        "spec_steps": report.spec_steps,
        "spec_tokens_per_step": round(report.spec_tokens_per_step, 3),
        "preemptions": report.preemptions,
        "outputs": {s.request_id: s.output for s in report.completed},
        "row": _report_row(f"serve_multidevice_{devices}dev{suffix}",
                           report, ecfg),
    }))


def _run_multidevice() -> "tuple[list, dict]":
    import jax

    if jax.default_backend() != "cpu":
        # the children fake their devices on the host CPU, and a child
        # cannot open the chip this process already holds
        raise SystemExit(
            "serve_bench multi_device suite: runs only with "
            "JAX_PLATFORMS=cpu (its children use virtual CPU devices); on "
            f"{jax.default_backend()} run `python chip_smoke.py --four-chips` "
            "for the sharded-vs-single-chip comparison")
    rows, outs = [], {}
    for devices, spec in ((1, False), (4, False), (4, True)):
        env = dict(os.environ)
        argv = [sys.executable, os.path.abspath(__file__),
                "--multidevice-child", str(devices)]
        if spec:
            argv.append("--multidevice-spec")
        proc = subprocess.run(argv, env=env, capture_output=True, text=True,
                              timeout=560)
        if proc.returncode:
            raise RuntimeError(
                f"multi-device child ({devices} devices, spec={spec}) "
                "failed:\n" + proc.stderr[-3000:])
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append(payload["row"])
        outs[(devices, spec)] = payload
    claims = {
        "multi_device_ran_4_shards": outs[(4, False)]["shards"] == 4,
        "multi_device_tokens_identical":
            outs[(1, False)]["outputs"] == outs[(4, False)]["outputs"],
        # the full composition: 4-way sharded + preempting + speculative
        # decode still matches the 1-device plain reserve engine
        "multi_device_spec_preempt_tokens_identical":
            outs[(1, False)]["outputs"] == outs[(4, True)]["outputs"],
        "multi_device_spec_verify_steps": outs[(4, True)]["spec_steps"],
        "multi_device_spec_ran": outs[(4, True)]["spec_steps"] >= 1,
    }
    return rows, claims


def run(arch: str = "tinyllama_1_1b", requests: int = 10, rate: float = 0.5,
        max_seq: int = 64, base_prompt: int = 20, base_gen: int = 8):
    import jax

    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serve import EngineConfig, ServeEngine, poisson_requests

    cfg = get_config(arch).smoke(window=0)  # paged pools need non-ring caches
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    def workload(tiers=()):
        return poisson_requests(
            requests, cfg.vocab, rate=rate, base_prompt=base_prompt,
            base_gen=base_gen, seed=0, tiers=tiers, repeat_prompt_every=3)

    # equal KV memory everywhere: 2*64 = 8*16 = 128 cells
    configs = (
        ("slot", EngineConfig(num_slots=2, max_seq=max_seq,
                              block_size=max_seq, prefill_chunk=16), ()),
        ("paged", EngineConfig(num_slots=4, max_seq=max_seq, block_size=16,
                               num_blocks=8 * max_seq // 64,
                               prefill_chunk=16), ()),
        ("mixed", EngineConfig(num_slots=4, max_seq=max_seq, block_size=16,
                               num_blocks=8 * max_seq // 64,
                               prefill_chunk=16, tiers=TIERS),
         [name for name, _ in TIERS]),
    )
    rows, reports = [], {}
    for label, ecfg, tier_names in configs:
        engine = ServeEngine(model, params, ecfg)
        report = engine.run(workload(tier_names))
        reports[label] = report
        rows.append(_report_row(f"serve_{arch}_{label}", report, ecfg))

    # -- preemption/swap vs whole-lifetime reservation, same tiny pool ----
    import numpy as np

    rng = np.random.default_rng(21)
    from repro.serve import Request

    burst_prompts = [rng.integers(0, cfg.vocab, size=6).tolist()
                     for _ in range(4)]

    def burst():  # 1-page prompts growing to 3 pages, all arriving at once
        return [Request(prompt=p, max_new_tokens=18) for p in burst_prompts]

    for label, preempt in (("reserve", False), ("preempt", True)):
        ecfg = EngineConfig(num_slots=4, max_seq=32, block_size=8,
                            num_blocks=4, prefill_chunk=8, preempt=preempt)
        report = ServeEngine(model, params, ecfg).run(burst())
        reports[label] = report
        rows.append(_report_row(f"serve_{arch}_{label}", report, ecfg))

    # -- async tick loop vs synchronous baseline, same workload -----------
    # a heavier smoke model so the per-step device compute outlasts jax's
    # dispatch overhead: with the tiny default config the step finishes
    # inside the launch call and there is nothing to overlap
    heavy_cfg = get_config(arch).smoke(window=0, d_model=256, n_layers=4,
                                       d_ff=1024, vocab=512)
    heavy_model = build_model(heavy_cfg)
    heavy_params, _ = heavy_model.init(jax.random.PRNGKey(0))
    for label, overlap in (("async", True), ("sync", False)):
        ecfg = EngineConfig(num_slots=4, max_seq=max_seq, block_size=16,
                            num_blocks=2 * max_seq // 8, prefill_chunk=16,
                            tiers=TIERS, overlap=overlap)
        report = ServeEngine(heavy_model, heavy_params, ecfg).run(
            poisson_requests(12, heavy_cfg.vocab, rate=rate,
                             base_prompt=base_prompt, base_gen=base_gen,
                             seed=0, tiers=[name for name, _ in TIERS]))
        reports[label] = report
        rows.append(_report_row(f"serve_{arch}_{label}", report, ecfg))

    # -- self-speculative decoding: cheap draft + exact verify ------------
    # same mixed-tier engine + workload as "mixed" (the plain baseline),
    # with two draft tiers: the policy-matched pc3_tr and the cruder
    # pc2_tr truncation. "free" (= pc3_tr) is its own draft under the
    # first, so only "paid" speculates there; both groups speculate under
    # pc2_tr. Greedy verify keeps every variant token-identical to plain.
    import dataclasses

    spec_labels = []
    for draft_label, draft in (("pc3_tr", "*=pc3_tr"), ("pc2_tr", "*=pc2_tr")):
        label = f"spec_{draft_label}"
        spec_labels.append(label)
        ecfg = dataclasses.replace(configs[2][1], spec_draft=draft, spec_k=3)
        report = ServeEngine(model, params, ecfg).run(
            workload([name for name, _ in TIERS]))
        reports[label] = report
        row = _report_row(f"serve_{arch}_{label}", report, ecfg)
        row.update({
            "spec_verify_steps": report.spec_steps,
            "spec_accept_rate": round(report.spec_accept_rate, 3),
            "spec_tokens_per_step": round(report.spec_tokens_per_step, 3),
            "spec_disabled_groups": report.spec_disabled_groups,
            "decode_steps": report.decode_steps,
        })
        rows.append(row)

    md_rows, md_claims = _run_multidevice()
    rows += md_rows

    slot, paged, mixed = reports["slot"], reports["paged"], reports["mixed"]
    outputs = {label: [r.output for r in reports[label].completed]
               for label in ("slot", "paged", "mixed", "reserve", "preempt",
                             "async", "sync")}
    claims = {
        "all_requests_complete": all(
            len(reports[label].completed) == expect
            for label, expect in (("slot", requests), ("paged", requests),
                                  ("mixed", requests),
                                  ("reserve", len(burst_prompts)),
                                  ("preempt", len(burst_prompts)),
                                  ("async", 12), ("sync", 12))),
        # block tables are a pure indexing change: same tokens out
        "paged_tokens_identical_to_slot": outputs["slot"] == outputs["paged"],
        # the headline: same 128 KV cells, strictly more requests in flight
        "paged_concurrency_exceeds_equal_memory_slot":
            paged.peak_active_requests > slot.peak_active_requests,
        "slot_peak_concurrency": slot.peak_active_requests,
        "paged_peak_concurrency": paged.peak_active_requests,
        "prefix_cache_hit_on_repeated_prompts": paged.prefix_hits >= 1,
        "mixed_tier_policy_groups": mixed.policy_groups,
        "mixed_tier_serves_two_groups": mixed.policy_groups == 2,
        # preemption: same 4-page pool, >= 2x admitted concurrency,
        # token-identical through the swap/resume cycle
        "preemption_occurred": reports["preempt"].preemptions >= 1,
        "preempt_tokens_identical_to_reserve":
            outputs["reserve"] == outputs["preempt"],
        "preempt_2x_admitted_concurrency":
            reports["preempt"].peak_active_requests
            >= 2 * reports["reserve"].peak_active_requests,
        "reserve_peak_concurrency": reports["reserve"].peak_active_requests,
        "preempt_peak_concurrency": reports["preempt"].peak_active_requests,
        # async loop: same tokens, less wall time blocked on the device
        "async_tokens_identical_to_sync":
            outputs["async"] == outputs["sync"],
        "async_idle_frac_below_sync":
            reports["async"].host_idle_frac < reports["sync"].host_idle_frac,
        "async_host_idle_frac": round(reports["async"].host_idle_frac, 4),
        "sync_host_idle_frac": round(reports["sync"].host_idle_frac, 4),
        # speculative decoding: greedy verify makes acceptance a pure
        # correctness check, so identity is claimed against plain mixed
        "spec_tokens_identical_to_plain": all(
            [r.output for r in reports[lbl].completed] == outputs["mixed"]
            for lbl in spec_labels),
        "spec_tokens_per_verify_step_exceeds_1_5":
            reports["spec_pc3_tr"].spec_tokens_per_step > 1.5,
        "spec_pc3_tr_accept_rate":
            round(reports["spec_pc3_tr"].spec_accept_rate, 3),
        "spec_pc2_tr_accept_rate":
            round(reports["spec_pc2_tr"].spec_accept_rate, 3),
        "spec_pc3_tr_tokens_per_step":
            round(reports["spec_pc3_tr"].spec_tokens_per_step, 3),
        "spec_fewer_decode_steps_than_plain":
            reports["spec_pc3_tr"].decode_steps
            < reports["mixed"].decode_steps,
        **md_claims,
    }
    return rows, claims


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama_1_1b")
    p.add_argument("--requests", type=int, default=10)
    p.add_argument("--rate", type=float, default=0.5)
    p.add_argument("--max-seq", type=int, default=64)
    p.add_argument("--prompt-len", type=int, default=20)
    p.add_argument("--gen", type=int, default=8)
    p.add_argument("--multidevice-child", type=int, default=0,
                   help=argparse.SUPPRESS)  # internal: subprocess mode
    p.add_argument("--multidevice-spec", action="store_true",
                   help=argparse.SUPPRESS)  # internal: spec+preempt child
    args = p.parse_args()
    if args.multidevice_child:
        _multidevice_child(args.multidevice_child, spec=args.multidevice_spec)
        raise SystemExit(0)
    rows, claims = run(arch=args.arch, requests=args.requests,
                       rate=args.rate, max_seq=args.max_seq,
                       base_prompt=args.prompt_len, base_gen=args.gen)
    for r in rows:
        print(r)
    print(claims)
