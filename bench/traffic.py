"""The one traffic generator: reads a cell's workload file, makes requests.

Every seed replays one schedule of sizes and arrivals and draws only the
token ids, so a run's amount of work, and the moments at which it falls
due, do not depend on its seed (under queueing, a tail of the latency
swings with the order of the arrivals far more than with the host's
noise). An open loop's prompt and output lengths are the quantiles of the
workload's distributions at ``(i + 0.5) / n``, and its gaps between arrivals
the quantiles of the exponential distribution at its rate, scaled so that
all ``n = rate * seconds`` requests fall due inside the window; each list is
put in one fixed order drawn from ``ORDER_SEED``.

A closed loop's clients each send their next request when the last is
done, until the window closes: the plan holds ``CLOSED_PER_CLIENT``
requests for each, more than a window reaches. A window reaches only the
first few of them, so the ``k``-th request takes the quantile at
``frac(k * GOLDEN)``, a sequence whose every prefix spreads evenly over the
distribution; the seed draws which client gets which requests.

Length distributions (``{"dist": ...}``): ``uniform`` with ``min``/``max``,
or ``lognormal`` with ``median``, ``sigma`` and ``min``/``max`` clipping.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

CLOSED_PER_CLIENT = 4096
ORDER_SEED = 0
GOLDEN = (math.sqrt(5) - 1) / 2


@dataclasses.dataclass
class Planned:
    """One request of the schedule: due ``due_s`` after the window opens
    (open loop), or the ``index``-th request of client ``client``."""

    prompt: List[int]
    max_new_tokens: int
    due_s: float = 0.0
    client: int = -1


def quantile(dist: Dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"] + 1)
        return int(min(math.floor(v), dist["max"]))
    if kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        v = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return int(min(max(round(v), dist["min"]), dist["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> List[int]:
    return [int(v) for v in rng.permutation(
        [quantile(dist, (i + 0.5) / n) for i in range(n)])]


def dealt(dist: Dict, n: int, offset: float) -> List[int]:
    """The closed loop's sizes, the same for every seed."""
    return [quantile(dist, (offset + k * GOLDEN) % 1.0) for k in range(n)]


def arrival_gaps(rate: float, seconds: float, n: int,
                 rng: np.random.Generator) -> List[float]:
    gaps = np.array([-math.log(1 - (i + 0.5) / n) / rate for i in range(n)])
    gaps *= 0.98 * seconds / gaps.sum()
    return [float(g) for g in rng.permutation(gaps)]


def schedule(wl: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    """The requests of one run, in the order they are due or dealt."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(ORDER_SEED)
    if wl["loop"] == "open":
        n = max(1, int(wl["rate_per_s"] * seconds))
        prompts = lengths(wl["prompt"], n, order)
        outputs = lengths(wl["output"], n, order)
    else:
        n = wl["clients"] * CLOSED_PER_CLIENT
        prompts = dealt(wl["prompt"], n, 0.25)
        outputs = dealt(wl["output"], n, 0.5)
    planned = [Planned(prompt=rng.integers(0, vocab, p).tolist(),
                       max_new_tokens=o)
               for p, o in zip(prompts, outputs)]
    if wl["loop"] == "open":
        due = np.cumsum(arrival_gaps(wl["rate_per_s"], seconds, n, order))
        for p, t in zip(planned, due):
            p.due_s = float(t)
    else:
        clients = rng.permutation(wl["clients"])
        for i, p in enumerate(planned):
            p.client = int(clients[i % wl["clients"]])
    return planned


def warmup_prompt(wl: Dict, vocab: int) -> List[int]:
    """One prompt that fills one prefill chunk (seed-independent)."""
    rng = np.random.default_rng(12345)
    return rng.integers(0, vocab, wl["engine"]["prefill_chunk"]).tolist()
