"""Calibration runs of one cell: several seeds, and for an open loop
several arrival rates, in one process (one start-up, one compile).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
        [--rates 0.5,1,2] [--control 3]

Each run is ``bench/run.py``'s run of the cell (``--rates`` replaces the
traffic file's rate) and prints one JSON line: the seed, the rate, the
end-to-end numbers, how long the drain after the window took (a growing
backlog drains long: the sweep that finds the knee reads it), the widest
gap and, on the first ``--control`` seeds of each rate, the float8
control's gap and whether it passes the cell's limit. The benchmark's own
runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", default="")
    p.add_argument("--control", type=int, default=0)
    args = p.parse_args(argv)

    bench, cell, conf, wl = run.cell_files(args.workload)
    devices = run.take_chip(args.workload, cell)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            cell_wl = dict(wl) if rate is None else dict(wl, rate_per_s=rate)
            r = run.run_cell(args.workload, bench, cell, conf, cell_wl, seed,
                             args.seconds, False, i < args.control, devices)
            print(json.dumps({
                "seed": seed, "rate": cell_wl.get("rate_per_s"),
                "correct": r["correct"], "attempted": r["attempted"],
                "failed": r["failed"], "metrics": {
                    k: v["value"] for k, v in r["metrics"].items()},
                "info": r["info"], "control": r.get("control"),
                "memory_peak_bytes": r["device"]["memory_peak_bytes"],
                "compared": r["compared"]}), flush=True)


if __name__ == "__main__":
    main()
