"""Chip smoke test: full-width TinyLlama-1.1B served on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # 4-way sharded vs one chip

The default run drives ``repro.serve.ServeEngine`` the way
``repro.launch.serve`` builds it, at the published widths of
``tinyllama_1_1b`` (22 layers, d_model 2048, 32/4 heads, d_ff 5632, vocab
32000) with random weights from ``--seed``:

1. preflight: daism-lint of the approximate tier finds no error and no
   Pallas site left in interpret mode (TIL003);
2. kernel: one real-width ``daism_matmul_pallas`` (PC3_TR) call on the
   chip matches the jnp backend within f32 accumulation-order bounds;
3. serve: 8 bf16 requests (prompts 128-512 tokens, 32 new tokens each)
   over two tiers, ``exact`` and ``approx`` (``*=pc3_tr:pallas``, the
   compiled DAISM GEMM), 16-token pages, chunked prefill, async tick loop
   with donated KV buffers;
4. preempt: a float32 engine with a pool too small for its batch swaps
   requests out and back in; every request's greedy tokens equal a plain
   greedy decode through ``model.forward``.

``--four-chips`` runs only the sharded-serving comparison: the same float32
requests served with ``shards=4`` on a 4-way ``model`` mesh and with
``shards=1`` on one chip must give identical tokens, and each chip must hold
about a quarter of the parameter and KV bytes.

Any failure raises (non-zero exit). There is no CPU fallback: without a TPU
the script exits non-zero before doing any work. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "tinyllama_1_1b"
TIERS = (("exact", "*=exact"), ("approx", "*=pc3_tr:pallas"))
BLOCK = 16        # KV page size (tokens)
GEN = 32          # new tokens per request
# (tier, prompt length): the approximate tier gets the shorter prompts
SERVE_REQUESTS = (("exact", 512), ("approx", 128), ("exact", 384),
                  ("approx", 192), ("exact", 448), ("approx", 160),
                  ("exact", 320), ("approx", 256))
# 6 pages per prompt, 8 per finished request: two rows outgrow 14 pages
PREEMPT_PROMPTS = (90, 94, 85)
FOUR_CHIP_PROMPTS = (128, 96, 112, 80)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since ``reset``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def reset(self) -> float:
        spent, self.seconds = self.seconds, 0.0
        return spent


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); this script runs only on the chip")
    return dev


def full_config(dtype: str):
    from repro.configs import get_config

    cfg = get_config(ARCH)
    return dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)


def init_params(model, seed: int):
    import jax

    return jax.jit(lambda key: model.init(key)[0])(jax.random.PRNGKey(seed))


def prompts_of(lengths, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lengths]


def greedy_reference(model, params, prompts, n_new: int):
    """Plain greedy decode: a full causal ``model.forward`` per new token
    over one fixed padded shape (padding after a row's length cannot reach
    its earlier positions)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def next_tokens(params, tokens, last):
        logits, _ = model.forward(params, {"tokens": tokens})
        at = jnp.take_along_axis(logits, last[:, None, None], axis=1)
        return jnp.argmax(at[:, 0], -1)

    lens = np.array([len(p) for p in prompts])
    tokens = np.zeros((len(prompts), lens.max() + n_new), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    rows = np.arange(len(prompts))
    for _ in range(n_new):
        tokens[rows, lens] = np.asarray(next_tokens(params, tokens, lens - 1))
        lens += 1
    return [tokens[i, len(p):len(p) + n_new].tolist()
            for i, p in enumerate(prompts)]


def bytes_per_device(tree):
    import jax

    out = collections.Counter()
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] += shard.data.nbytes
    return out


def outputs_of(report):
    return {s.request_id: s.output for s in report.completed}


def phase_preflight(cfg, ecfg):
    from repro.analyze import analyze

    spec = dict(TIERS)["approx"]
    report = analyze(cfg, spec, engine_cfg=ecfg)
    codes = sorted({f.code for f in report.findings})
    assert not report.errors, [str(f) for f in report.errors]
    assert "TIL003" not in codes, "a Pallas site would run in interpret mode"
    log(f"[preflight] {spec}: findings {codes or 'none'}; no TIL003")


def phase_kernel(seed: int, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Backend, DaismConfig, Variant, daism_matmul
    from repro.kernels import daism_matmul_pallas

    m, k, n = 32, 2048, 5632
    ka, kw = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    got = np.asarray(daism_matmul_pallas(a, w, cfg))
    ref = np.asarray(daism_matmul(
        a, w, dataclasses.replace(cfg, backend=Backend.JNP)))
    # two f32 summation orders of the same K products differ by at most
    # 2(K-1) eps sum|p|, and every approximate |p| <= |a||w|
    abs_sum = np.abs(np.asarray(a, np.float32)) @ np.abs(
        np.asarray(w, np.float32))
    bound = 2 * (k - 1) * np.finfo(np.float32).eps * abs_sum
    err = np.abs(got - ref)
    assert got.shape == (m, n) and np.isfinite(got).all()
    assert (err <= bound).all(), float((err - bound).max())
    log(f"[kernel] daism_matmul_pallas pc3_tr {m}x{k}x{n} vs jnp: max abs "
        f"diff {err.max():.3e} (per-element bound {bound.min():.3e} to "
        f"{bound.max():.3e}); compile {clock.reset():.3f}s")


def phase_serve(cfg, ecfg, seed: int, clock: CompileClock):
    from repro.models.registry import build_model
    from repro.serve import Request, ServeEngine

    model = build_model(cfg)
    params = init_params(model, seed)
    prompts = prompts_of([n for _, n in SERVE_REQUESTS], cfg.vocab, seed + 1)
    requests = [Request(prompt=p, max_new_tokens=GEN, policy=tier)
                for (tier, _), p in zip(SERVE_REQUESTS, prompts)]
    engine = ServeEngine(model, params, ecfg)
    t0 = time.perf_counter()
    report = engine.run(requests)
    wall = time.perf_counter() - t0
    outs = list(outputs_of(report).values())
    assert len(outs) == len(requests), (len(outs), len(requests))
    assert report.policy_groups == 2, report.policy_groups
    assert all(len(o) == GEN and all(0 <= t < cfg.vocab for t in o)
               for o in outs)
    log(f"[serve] {len(requests)} requests, tiers "
        f"{[name for name, _ in TIERS]}: {report.generated_tokens} tokens "
        f"served in {wall:.3f}s wall (compile {clock.reset():.3f}s), peak "
        f"concurrency {report.peak_active_requests}")
    log(engine.resolution_report())
    log(report.summary())


def phase_preempt(cfg, ecfg, seed: int, clock: CompileClock):
    from repro.models.registry import build_model
    from repro.serve import Request, ServeEngine

    model = build_model(cfg)
    params = init_params(model, seed)
    prompts = prompts_of(PREEMPT_PROMPTS, cfg.vocab, seed + 100)
    engine = ServeEngine(model, params, ecfg)
    report = engine.run([Request(prompt=p, max_new_tokens=GEN,
                                 policy="exact") for p in prompts])
    assert report.preemptions >= 1, "the undersized pool never preempted"
    assert report.resumes == report.preemptions, (report.preemptions,
                                                  report.resumes)
    expected = greedy_reference(model, params, prompts, GEN)
    got = [s.output for s in sorted(report.completed,
                                    key=lambda s: s.request_id)]
    assert got == expected, "engine tokens differ from greedy model.forward"
    log(f"[preempt] float32, {ecfg.blocks}-page pool: "
        f"{report.preemptions} preemption(s) / {report.resumes} resume(s); "
        f"{len(got)} requests x {GEN} tokens identical to greedy "
        f"model.forward (compile {clock.reset():.3f}s)")


def phase_four_chips(cfg, seed: int, clock: CompileClock):
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve import EngineConfig, Request, ServeEngine

    assert jax.device_count() >= 4, jax.devices()
    model = build_model(cfg)
    params = init_params(model, seed)
    prompts = prompts_of(FOUR_CHIP_PROMPTS, cfg.vocab, seed + 200)

    def serve(ecfg, mesh=None):
        engine = ServeEngine(model, params, ecfg, mesh=mesh)
        t0 = time.perf_counter()
        report = engine.run([Request(prompt=p, max_new_tokens=GEN)
                             for p in prompts])
        log(f"[four-chips] shards={report.shards}: "
            f"{report.generated_tokens} tokens in "
            f"{time.perf_counter() - t0:.3f}s wall (compile "
            f"{clock.reset():.3f}s)")
        return engine, outputs_of(report)

    ecfg = EngineConfig(num_slots=4, max_seq=160, block_size=BLOCK,
                        num_blocks=40, prefill_chunk=64)
    _, single = serve(ecfg)
    engine, sharded = serve(dataclasses.replace(ecfg, shards=4),
                            make_mesh((4,), ("model",)))
    assert sharded == single, "sharded tokens differ from one chip"
    for name, tree in (("params", engine.params), ("kv", engine.kv)):
        per = bytes_per_device(tree)
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        shares = {d: per[d] / total for d in sorted(per)}
        log(f"[four-chips] {name}: {total} bytes in all; per device "
            + ", ".join(f"{d}: {per[d]} ({s:.4f})"
                        for d, s in shares.items()))
        assert len(per) == 4 and all(0.24 <= s <= 0.30
                                     for s in shares.values()), shares
    log(f"[four-chips] {len(prompts)} requests x {GEN} tokens identical "
        "between shards=4 and one chip")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-way sharded vs one-chip comparison")
    args = p.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro.launch.cache import configure_compile_cache
    from repro.serve import EngineConfig

    log(f"device: {dev.platform} / {dev.device_kind} x {jax.device_count()}; "
        f"jax {jax.__version__}; compile cache {configure_compile_cache()}")
    clock = CompileClock()
    t_start = time.perf_counter()
    f32 = full_config("float32")
    # the MXU takes float32 operands in one bf16 pass unless asked for
    # more; the float32 token-identity checks need true float32 products
    f32_precision = jax.default_matmul_precision("highest")
    if args.four_chips:
        with f32_precision:
            phase_four_chips(f32, args.seed, clock)
    else:
        bf16 = full_config("bfloat16")
        serve_cfg = EngineConfig(
            num_slots=4, max_seq=512 + GEN, block_size=BLOCK,
            num_blocks=len(SERVE_REQUESTS) * (512 + GEN) // BLOCK,
            prefill_chunk=128, tiers=TIERS)
        phase_preflight(bf16, serve_cfg)
        phase_kernel(args.seed, clock)
        phase_serve(bf16, serve_cfg, args.seed, clock)
        with f32_precision:
            phase_preempt(f32, EngineConfig(
                num_slots=2, max_seq=128, block_size=BLOCK, num_blocks=14,
                prefill_chunk=64, tiers=TIERS[:1], preempt=True),
                args.seed, clock)
    stats = dev.memory_stats() or {}
    log(f"done in {time.perf_counter() - t_start:.3f}s; device_kind "
        f"{dev.device_kind}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
