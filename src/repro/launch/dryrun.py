import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init). 512 placeholder host devices back the production
meshes: 16x16 single-pod and 2x16x16 multi-pod.

For every live cell (DESIGN.md §4 skip table):
  * build the real train/prefill/decode step via launch.steps (the same
    functions the trainer executes — no separate dry-run graph),
  * ``.lower(**ShapeDtypeStructs).compile()``,
  * record ``memory_analysis()`` / ``cost_analysis()`` / the HLO collective
    schedule -> roofline terms (roofline/analysis.py),
  * append to ``results/dryrun.json`` (resumable: done cells are skipped).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  ... --arch tinyllama_1_1b --shape train_4k --mesh multi      # one cell
  ... --rules seqcache                                         # perf variant
"""

import argparse
import contextlib
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp


RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results")

# The chip the roofline terms model: the dry-run compiles on placeholder
# host devices, so it names its target rather than asking jax.
TARGET_KIND = "TPU v5 lite"


def _mesh(kind: str):
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def _specs_with_shardings(tree, shardings):
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
        tree, shardings)


def loop_accounting(cfg, kind: str, seq: int):
    """Scan-undercount correction plan (EXPERIMENTS.md §Roofline methodology).

    Returns [{cat, k, mult}]: compile a probe with scan category ``cat``
    unrolled by ``k``; true_cost = base + (probe - base) * mult / (k - 1).
    ``mult`` encodes trip counts and loop nesting per DESIGN.md §4 model
    structure; derivation in the module docstring of parallel/unroll.py.
    """
    import math
    chunk = cfg.attn_chunk
    cache_len = min(cfg.window, seq) if cfg.window else seq
    kv_len = cache_len if kind == "decode" else seq
    nc = max(1, math.ceil(kv_len / chunk))
    s_time = 1 if kind == "decode" else seq
    probes = []

    def add(cat, k, mult):
        if mult > 0 and k > 1:
            probes.append({"cat": cat, "k": k, "mult": float(mult)})

    fam = cfg.family
    if fam in ("dense", "moe"):
        t = cfg.n_layers
        add("layers", 2, t - 1)
        add("attn", 2, (nc - 1) * t)
    elif fam == "vlm":
        t = cfg.cross_every                      # 8 loops x trip 5
        add("layers", t, t - 1)                  # k=5 (divides trip exactly)
        add("attn", 2, (nc - 1) * t)
    elif fam == "audio":
        t = cfg.n_layers                         # enc + dec loops, trip 32
        add("layers", 2, t - 1)
        add("attn", 2, (nc - 1) * t)             # decoder self-attn
        if kind != "decode":                     # encoder runs in fwd only
            nc_enc = max(1, math.ceil(cfg.enc_frames / chunk))
            add("attn_enc", 2, (nc_enc - 1) * t)
    elif fam == "ssm":                           # xlstm
        group = (cfg.slstm_every or cfg.n_layers + 1) - 1
        add("layers", group, group - 1)          # k = trip (7: prime)
        # mlstm time scans sit inside layer loops (enclosing trip = group);
        # slstm time scans are top-level (python-applied blocks) -> exact
        add("time", 2, (s_time - 1) * group)
        add("time_s", 2, (s_time - 1))
    elif fam == "hybrid":                        # zamba
        every = cfg.shared_attn_every
        n_sites = cfg.n_layers // every if every else 0
        if n_sites:
            tail = cfg.n_layers - n_sites * every
            n_loops = n_sites + (1 if tail else 0)
            # homogeneous mamba bodies: sum(T_l - 1) spread over n_loops
            add("layers", 2, cfg.n_layers - n_loops)
            # shared-attn blocks are top-level -> exact (n-1) factor
            add("attn", 2, nc - 1)
            # time scans inside layer loops: scale by mean enclosing trip
            add("time", 2, (s_time - 1) * (cfg.n_layers / n_loops))
        else:
            add("layers", 2, cfg.n_layers - 1)
            add("time", 2, (s_time - 1))
    return probes


def lower_cell(cfg, mesh, rules, shape_name: str, *, probe_cat=None,
               probe_k=1):
    """Build fresh artifacts (fresh jit objects: trace caches must not leak
    across unroll probes) and lower the cell's step. Returns (lowered,
    flops_thunk) where flops_thunk() walks the jaxpr for exact FLOPs."""
    from repro.configs import SHAPES, input_specs
    from repro.launch.steps import build_artifacts
    from repro.parallel.unroll import use_unroll
    from repro.roofline.flops import count_flops

    seq, batch, kind = SHAPES[shape_name]
    art = build_artifacts(cfg, mesh, rules=rules)
    specs, kind = input_specs(cfg, shape_name)
    batch_specs = _specs_with_shardings(specs, art.batch_sharding(specs))
    params = _specs_with_shardings(art.param_shapes, art.param_shardings)

    ctx = (use_unroll(**{probe_cat: probe_k}) if probe_cat
           else contextlib.nullcontext())
    with ctx:
        if kind == "train":
            opt = _specs_with_shardings(art.opt_shapes, art.opt_shardings)
            lowered = art.train_step.lower(params, opt, batch_specs)
            flops_thunk = lambda: count_flops(
                art.train_step, params, opt, batch_specs)
        elif kind == "prefill":
            lowered = art.prefill_step.lower(params, batch_specs)
            flops_thunk = lambda: count_flops(
                art.prefill_step, params, batch_specs)
        else:  # decode
            cache_shapes = art.make_cache_shapes(batch, seq)
            cache = _specs_with_shardings(
                cache_shapes, art.cache_shardings_for(cache_shapes))
            toks = dict(batch_specs).pop("tokens")
            extra = batch_specs.get("image_embeds")
            lowered = art.decode_step.lower(params, toks, cache, extra)
            flops_thunk = lambda: count_flops(
                art.decode_step, params, toks, cache, extra)
    return lowered, flops_thunk, kind



def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             rules_name: str = "base", remat: str = "auto",
             tweaks: str = "", probes: bool = True,
             verbose: bool = True) -> Dict:
    from repro.configs import SHAPES, cell_enabled, get_config
    from repro.parallel.sharding import base_rules
    from repro.roofline import analysis as ra

    cfg = get_config(arch)
    if not cell_enabled(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skip", "reason": "mandated skip (DESIGN.md §4)"}
    seq, batch, kind0 = SHAPES[shape_name]
    if remat == "auto":
        remat = "full" if kind0 == "train" else "none"
    cfg = dataclasses.replace(cfg, remat=remat)
    if tweaks:  # e.g. "attn_score_dtype=bfloat16,rnn_state_dtype=bfloat16"
        kv = dict(t.split("=") for t in tweaks.split(","))
        cfg = dataclasses.replace(cfg, **kv)

    mesh = _mesh(mesh_kind)
    n_chips = mesh.devices.size
    rules = base_rules(mesh_kind == "multi",
                       seq_sharded_cache=(rules_name in ("seqcache",
                                                         "serve")),
                       sp_activations=(rules_name == "sp"),
                       serve=(rules_name == "serve"))

    t0 = time.monotonic()
    lowered, flops_thunk, kind = lower_cell(cfg, mesh, rules, shape_name)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0
    mem = compiled.memory_analysis()

    def costs_of(compiled_):
        cost = compiled_.cost_analysis()
        stats = ra.collective_bytes_from_hlo(compiled_.as_text(), n_chips)
        return (float(cost.get("bytes accessed", 0.0)), stats.wire_bytes,
                dict(stats.by_op))

    bytes0, wire0, by_op = costs_of(compiled)
    bytes_c, wire_c = bytes0, wire0
    probe_log = []
    if probes:  # scan-undercount correction (see loop_accounting)
        for probe in loop_accounting(cfg, kind, seq):
            plow, _, _ = lower_cell(cfg, mesh, rules, shape_name,
                                    probe_cat=probe["cat"],
                                    probe_k=probe["k"])
            pb, pw, pby = costs_of(plow.compile())
            scale = probe["mult"] / (probe["k"] - 1)
            bytes_c += max(pb - bytes0, 0.0) * scale
            wire_c += max(pw - wire0, 0.0) * scale
            for op, v in pby.items():
                extra = max(v - by_op.get(op, 0.0), 0.0) * scale
                by_op[op] = by_op.get(op, 0.0) + extra
            probe_log.append({**probe, "d_bytes": pb - bytes0,
                              "d_wire": pw - wire0})

    flops_global = flops_thunk()
    flops_dev = flops_global / n_chips
    model_flops = ra.model_flops_estimate(cfg, kind, seq, batch)
    peaks = ra.chip_peaks(TARGET_KIND)
    compute_s = flops_dev / peaks.bf16_flops
    memory_s = bytes_c / peaks.hbm_bw
    coll_s = wire_c / peaks.link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "rules": rules_name, "remat": remat, "tweaks": tweaks,
        "status": "ok",
        "kind": kind, "chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "arg_bytes": int(mem.argument_size_in_bytes),
        "out_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "mem_per_device_gb": round(
            (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes) / 2**30, 3),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_c,
        "wire_bytes_per_device": wire_c,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s,
        "bottleneck": bottleneck,
        "model_flops": model_flops,
        "useful_ratio": round(model_flops / max(flops_global, 1.0), 4),
        "collective_by_op": {k: round(v) for k, v in by_op.items()},
        "probes": probe_log,
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_kind} ({rules_name})] "
              f"compile {t_compile:.0f}s mem/dev {rec['mem_per_device_gb']}GB "
              f"terms c={compute_s:.4f}s m={memory_s:.4f}s "
              f"coll={coll_s:.4f}s -> {bottleneck} "
              f"useful={rec['useful_ratio']:.2f}", flush=True)
    return rec


def _result_path(tag: str) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    return os.path.join(RESULTS, f"dryrun_{tag}.json")


def load_results(tag: str = "main") -> Dict[str, Dict]:
    path = _result_path(tag)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def main():
    from repro.configs import ARCH_IDS, SHAPES

    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    p.add_argument("--rules", default="base")
    p.add_argument("--remat", default="auto")
    p.add_argument("--tweaks", default="")
    p.add_argument("--tag", default="main")
    p.add_argument("--force", action="store_true")
    args = p.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    results = load_results(args.tag)
    path = _result_path(args.tag)
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = (f"{arch}|{shape}|{mesh_kind}|{args.rules}|"
                       f"{args.remat}|{args.tweaks}")
                if key in results and not args.force \
                        and results[key].get("status") in ("ok", "skip"):
                    continue
                try:
                    # roofline probes only on the single-pod mesh (the
                    # roofline table is single-pod; multi-pod proves the
                    # pod axis shards + records memory)
                    rec = run_cell(arch, shape, mesh_kind,
                                   rules_name=args.rules, remat=args.remat,
                                   tweaks=args.tweaks,
                                   probes=(mesh_kind == "single"))
                except Exception as e:  # record failures: they are bugs
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "rules": args.rules, "tweaks": args.tweaks,
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                results[key] = rec
                with open(path, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skip")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"dry-run: {n_ok} ok, {n_skip} mandated skips, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
