"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --smoke --steps 200 --batch 8 --seq 64 --ckpt /tmp/ckpt

On a real fleet this process runs per host under jax.distributed with the
production mesh (launch/mesh.py); in this container it drives the same code
path on however many local devices exist (--devices N forces fake devices,
set BEFORE jax init). Fault tolerance: re-running the same command resumes
from the newest intact checkpoint (runtime/fault_tolerance.py).
"""
import argparse
import os
import sys


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (CPU-trainable)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--devices", type=int, default=0,
                   help="force N fake host devices (must be first jax use)")
    p.add_argument("--mesh", default="auto",
                   help="'auto' | 'DATAxMODEL' e.g. 4x2")
    p.add_argument("--daism", default="exact",
                   help="DEPRECATED (use --policy): uniform multiplier "
                        "variant for parameter GEMMs "
                        "(exact|fla|hla|pc2|pc3|pc2_tr|pc3_tr)")
    p.add_argument("--policy", default="",
                   help="per-site approximation policy spec, e.g. "
                        "'*/layer_0/*=exact,@lm_head=exact,*=pc3_tr'")
    p.add_argument("--no-preflight", action="store_true",
                   help="skip the daism-lint static preflight")
    args = p.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={args.devices}")
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.config import Backend, DaismConfig, Variant
    from repro.data.synthetic import lm_batches, shard_batch
    from repro.launch.cache import configure_compile_cache
    from repro.launch.mesh import best_effort_mesh, make_mesh
    from repro.launch.steps import build_artifacts
    from repro.optim import AdamWConfig
    from repro.runtime.fault_tolerance import TrainLoopConfig, run

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.policy:
        cfg = cfg.with_policy(args.policy)
    elif args.daism != "exact":
        import warnings

        warnings.warn("--daism is deprecated; use --policy "
                      f"'*={args.daism}'", DeprecationWarning, stacklevel=1)
        cfg = dataclasses.replace(
            cfg, daism=DaismConfig(variant=Variant(args.daism),
                                   backend=Backend.JNP))
    if not args.no_preflight:
        # static lint of the (model, policy) pair before any compilation:
        # zero-match rules, illegal backends, scan shatter all fail here
        # in O(seconds) instead of mid-trace (launch/lint.py standalone)
        from repro.analyze import preflight

        preflight(cfg, serving=False, label=f"train {args.arch}")
    if args.mesh == "auto":
        mesh = best_effort_mesh(model_parallel=1 if jax.device_count() == 1
                                else 2)
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    configure_compile_cache()

    art = build_artifacts(cfg, mesh, opt_cfg=AdamWConfig(lr=args.lr),
                          total_steps=args.steps,
                          warmup=max(args.steps // 20, 1))
    params = art.init_params(jax.random.PRNGKey(0))
    opt = art.init_opt(params)
    gen = lm_batches(cfg.vocab, args.batch, args.seq, seed=0)
    bsh = art.batch_sharding(next(gen))

    def put(b):
        return shard_batch(b, bsh)

    def log(step, m):
        print(f"step {step:5d} loss {float(m['loss']):.4f} "
              f"gnorm {float(m['grad_norm']):.3f} lr {float(m['lr']):.2e}",
              flush=True)

    loop = TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                           ckpt_every=args.ckpt_every, log_every=10)
    params, opt, state = run(loop, art.train_step, params, opt, gen, put,
                             metrics_hook=log,
                             param_shardings=art.param_shardings,
                             opt_shardings=art.opt_shardings)
    print(f"done at step {state.step}; stragglers seen: {state.stragglers}")
    if args.policy or args.daism != "exact":
        from repro.policy import site_report

        print(site_report(cfg.approx_policy))


if __name__ == "__main__":
    main()
