"""Paper-table harness: one module per paper table or figure.

Prints ``name,us_per_call,derived`` CSV rows plus per-module claim checks.
These modules reproduce the paper's figures and tables from analytical
models and accuracy runs; they are not speed measurements. Speed is
measured on the chip by ``bench/run.py`` (cells in ``BENCHMARK.json``).
The dry-run/roofline tables come from ``repro.launch.dryrun``, which needs
the 512-device environment and is run separately.

    PYTHONPATH=src:. python benchmarks/run.py [module ...]
"""
from __future__ import annotations

import json
import sys
import time

_MODULES = ("error_distance", "energy", "arch_cycles", "accuracy",
            "policy_sweep")


def main() -> None:
    only = sys.argv[1:] or _MODULES
    all_claims = {}
    for name in only:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        rows, claims = mod.run()
        dt = time.perf_counter() - t0
        print(f"# {name} ({dt:.1f}s)", flush=True)
        for r in rows:
            derived = {k: v for k, v in r.items()
                       if k not in ("name", "us_per_call")}
            print(f"{r['name']},{r['us_per_call']},{json.dumps(derived)}",
                  flush=True)
        for k, v in claims.items():
            print(f"claim,{name}.{k},{v}", flush=True)
        all_claims.update({f"{name}.{k}": v for k, v in claims.items()})
    failed = [k for k, v in all_claims.items() if v is False]
    print(f"# claims: {sum(1 for v in all_claims.values() if v is True)} "
          f"hold, {len(failed)} failed: {failed}")


if __name__ == "__main__":
    main()
