"""Serving launcher: thin CLI over the paged continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b --smoke

Builds the model, submits a synthetic workload (fixed stagger or Poisson
arrivals), and drives repro.serve.ServeEngine: a paged KV pool (block
tables + prefix caching), chunked prefill interleaved with decode, and one
jit'd step per approximation-policy group. Prints the per-request timeline
and the engine's latency/throughput/KV-utilization report.

``--policy "*/attn/*=exact,*=pc3_tr"`` serves the whole engine with one
per-site policy; ``--tiers "free=*=pc3_tr;paid=*/attn/*=exact"`` registers
named per-request tiers and spreads the workload across them (mixed-tier
traffic batches per resolved policy — no cross-tier recompiles). The legacy
``--variant pc3_tr`` flag still works through the uniform-policy
deprecation shim. After the run the per-group site resolution report is
printed. Speed is measured on the chip by bench/run.py.
"""
import argparse
import dataclasses
import os
import warnings


def build_daism(variant: str, backend: str):
    from repro.core import Backend, DaismConfig, Variant
    return DaismConfig(variant=Variant(variant), backend=Backend(backend))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + small workload (CPU-friendly)")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--slots", type=int, default=2,
                   help="decode batch width per policy group")
    p.add_argument("--max-seq", type=int, default=64,
                   help="per-request KV capacity (prompt + generation)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV page size in tokens (= --max-seq reproduces "
                        "the old slot pool)")
    p.add_argument("--blocks", type=int, default=0,
                   help="physical KV pages (0 = slots*max_seq/block_size, "
                        "the old slot pool's memory)")
    p.add_argument("--prefill-chunk", type=int, default=16,
                   help="prompt tokens ingested per engine tick "
                        "(chunked prefill; power of two)")
    p.add_argument("--prompt-len", type=int, default=8,
                   help="base prompt length (workload staggers around it)")
    p.add_argument("--gen", type=int, default=8,
                   help="base generation length")
    p.add_argument("--arrival-every", type=int, default=0,
                   help="space arrivals N engine steps apart (0 = all at once)")
    p.add_argument("--poisson", type=float, default=0.0,
                   help="Poisson arrival rate in requests/step (overrides "
                        "--arrival-every; 0 = disabled)")
    p.add_argument("--policy", default="",
                   help="engine-wide per-site approximation policy spec, "
                        "e.g. '*/attn/*=exact,*/layer_0/*=exact,*=pc3_tr' "
                        "(repro.policy mini-language)")
    p.add_argument("--tiers", default="",
                   help="named per-request policy tiers, e.g. "
                        "'free=*=pc3_tr;paid=*/attn/*=exact' — the workload "
                        "is spread across them (mixed-tier serving)")
    p.add_argument("--variant", default="exact",
                   help="DEPRECATED (use --policy): uniform multiplier "
                        "variant (exact | fla | ... | pc3_tr)")
    p.add_argument("--backend", default="jnp",
                   help="daism backend for approximate variants")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--devices", type=int, default=0)
    p.add_argument("--shards", type=int, default=1,
                   help="tensor-parallel serving: shard params, KV pages "
                        "and every policy group's step over an N-way "
                        "'model' mesh axis (N must divide --blocks and "
                        "--slots; pair with --devices N on CPU)")
    p.add_argument("--preempt", action="store_true",
                   help="optimistic admission + preemption: swap the "
                        "lowest-priority running request's KV pages to a "
                        "host buffer under pool exhaustion instead of "
                        "reserving whole lifetimes up front")
    p.add_argument("--swap-blocks", type=int, default=0,
                   help="host swap buffer size in KV pages "
                        "(0 = one full request's worth)")
    p.add_argument("--spec-draft", default="",
                   help="self-speculative decoding: draft policy (a --tiers "
                        "name or a raw policy spec) used for cheap draft "
                        "steps; the group's own exact step verifies them "
                        "(greedy outputs stay token-identical)")
    p.add_argument("--spec-k", type=int, default=0,
                   help="draft tokens proposed per speculative verify step "
                        "(0 = speculation off; pair with --spec-draft)")
    p.add_argument("--sync", action="store_true",
                   help="synchronous tick loop (disable the async "
                        "host/device overlap; baseline for "
                        "ServeReport.host_idle_frac)")
    p.add_argument("--no-preflight", action="store_true",
                   help="skip the daism-lint static preflight")
    args = p.parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.configs import get_config
    from repro.launch.cache import configure_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve import (EngineConfig, ServeEngine, parse_tiers,
                             poisson_requests, synthetic_requests)

    cfg = get_config(args.arch)
    if args.smoke:
        overrides = {"window": 0}  # paged pools need non-ring caches
        if args.shards > 1:
            # the head-local paged attention shard_map needs kv heads
            # divisible by the mesh axis; the default smoke config has 2
            overrides["kv_heads"] = args.shards
        cfg = cfg.smoke(**overrides)
    if args.policy:
        cfg = cfg.with_policy(args.policy)
    elif args.variant != "exact":
        warnings.warn("--variant/--backend are deprecated; use --policy "
                      f"'*={args.variant}:{args.backend}'", DeprecationWarning,
                      stacklevel=1)
        cfg = dataclasses.replace(cfg,
                                  daism=build_daism(args.variant, args.backend))
    tiers = parse_tiers(args.tiers) if args.tiers else ()
    engine_cfg = EngineConfig(
        num_slots=args.slots, max_seq=args.max_seq,
        block_size=args.block_size, num_blocks=args.blocks,
        prefill_chunk=args.prefill_chunk, tiers=tiers,
        shards=args.shards, preempt=args.preempt,
        swap_blocks=args.swap_blocks, overlap=not args.sync,
        spec_draft=args.spec_draft, spec_k=args.spec_k)
    if not args.no_preflight:
        # static lint of the full (model, policy, engine) triple before the
        # (expensive) params init: bad tiers, window/paged conflicts and
        # undersized pools abort here (launch/lint.py standalone)
        from repro.analyze import preflight

        preflight(cfg, engine_cfg=engine_cfg, label=f"serve {args.arch}")
    configure_compile_cache()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    mesh = None
    if args.shards > 1:
        if jax.device_count() % args.shards:
            raise SystemExit(
                f"--shards {args.shards} does not divide the "
                f"{jax.device_count()} available devices (on CPU pass "
                f"--devices {args.shards})")
        mesh = make_mesh((args.shards,), ("model",))
    engine = ServeEngine(model, params, engine_cfg, mesh=mesh)
    tier_names = [name for name, _ in tiers]
    if args.poisson > 0:
        requests = poisson_requests(
            args.requests, cfg.vocab, rate=args.poisson,
            base_prompt=args.prompt_len, base_gen=args.gen, seed=args.seed,
            tiers=tier_names)
    else:
        requests = synthetic_requests(
            args.requests, cfg.vocab, base_prompt=args.prompt_len,
            base_gen=args.gen, seed=args.seed,
            arrival_every=args.arrival_every, tiers=tier_names)
    report = engine.run(requests)

    numerics = (f"tiers {args.tiers}" if args.tiers
                else f"policy {args.policy}" if args.policy else args.variant)
    arrivals = (f"poisson rate {args.poisson}" if args.poisson > 0
                else f"every {args.arrival_every}" if args.arrival_every
                else "all at once")
    print(f"== {args.arch} ({numerics}) — {args.requests} requests, "
          f"{args.slots} rows/group, {engine.cfg.blocks} x "
          f"{args.block_size}-token KV pages, arrivals {arrivals} ==")
    for ev in report.events:
        print(event_line(ev))
    print(report.summary())
    if args.tiers or args.policy or args.variant != "exact":
        print(engine.resolution_report())
    if report.completed:
        sample = report.completed[0]
        print(f"sample (req {sample.request_id}): {sample.output}")
    default_workload = all(
        getattr(args, k) == p.get_default(k)
        for k in ("requests", "slots", "gen", "prompt_len", "arrival_every",
                  "poisson", "block_size", "blocks", "prefill_chunk"))
    if args.smoke and default_workload:
        # the gate is calibrated to the default smoke workload (staggered
        # lengths oversubscribing 2 rows); custom shapes — one row, spaced
        # arrivals, equal-length retire waves — may legitimately never join
        if report.joined_mid_stream < 2:  # explicit: survives python -O
            raise SystemExit(
                "smoke workload must exercise continuous batching "
                f"(got {report.joined_mid_stream} mid-stream joins)")
        print("SMOKE-OK: continuous batching exercised "
              f"({report.joined_mid_stream} mid-stream joins)")
    if args.smoke and args.tiers and report.policy_groups < 2:
        raise SystemExit(
            "smoke --tiers workload must exercise >= 2 policy groups "
            f"(got {report.policy_groups})")
    if args.smoke and args.tiers:
        print(f"SMOKE-OK: {report.policy_groups} policy groups served "
              "mixed-tier traffic")
    if args.smoke and args.shards > 1:
        if report.shards != args.shards:
            raise SystemExit(
                f"smoke --shards {args.shards} ran on {report.shards} "
                "shard(s)")
        print(f"SMOKE-OK: served tensor-parallel over {report.shards} "
              "shards")
    if args.smoke and args.preempt and args.blocks:
        # an explicitly undersized pool (--blocks) must actually exercise
        # the swap path; auto-sized pools never exhaust
        if not (report.preemptions and report.resumes):
            raise SystemExit(
                "smoke --preempt with a constrained pool must preempt and "
                f"resume (got {report.preemptions} preemption(s), "
                f"{report.resumes} resume(s))")
        if any(s.finish_reason not in ("eos", "length")
               for s in report.completed):
            raise SystemExit("smoke --preempt: a request finished abnormally")
        print(f"SMOKE-OK: {report.preemptions} preemption(s) / "
              f"{report.resumes} resume(s) under page exhaustion")
    if args.smoke and args.spec_k:
        if not report.spec_steps:
            raise SystemExit(
                "smoke --spec-k workload never took a speculative verify "
                "step (draft group ineligible or controller disabled it "
                "before the first step)")
        if report.spec_tokens_per_step < 1.0:
            raise SystemExit(
                "smoke --spec-k: tokens per verify step "
                f"{report.spec_tokens_per_step:.2f} < 1.0 — the bonus-token "
                "guarantee is broken")
        print(f"SMOKE-OK: speculative decoding took {report.spec_steps} "
              f"verify step(s), accept rate {report.spec_accept_rate:.2f}, "
              f"{report.spec_tokens_per_step:.2f} tokens/step")


def event_line(ev) -> str:
    """One line of the timeline for an engine event (``ServeEngine.events``)."""
    head = f"step {ev['step']:4d}  "
    if ev["event"] == "admit":
        joined = " (joined running batch)" if ev["joined_running"] else ""
        cached = (f", {ev['cached_blocks']} cached"
                  if ev.get("cached_blocks") else "")
        return (f"{head}admit  req {ev['request_id']} "
                f"-> {ev['group']}/row {ev['slot']} "
                f"[{ev['blocks']} pages{cached}]{joined}")
    if ev["event"] == "preempt":
        return (f"{head}preempt req {ev['request_id']} "
                f"({ev['group']}/row {ev['slot']}: {ev['blocks']} pages "
                "swapped to host)")
    if ev["event"] == "resume":
        return (f"{head}resume req {ev['request_id']} "
                f"-> {ev['group']}/row {ev['slot']} "
                f"[{ev['blocks']} pages restored]")
    if ev["event"] == "spec_off":
        return (f"{head}spec off for group {ev['group']} (acceptance EWMA "
                f"{ev['ewma']})")
    return (f"{head}retire req {ev['request_id']} "
            f"({ev['group']}/row {ev['slot']} freed, {ev['reason']})")


if __name__ == "__main__":
    main()
