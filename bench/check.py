"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the served requests, drawn from the seed and always holding the longest,
is packed into ``check.rows`` rows. The plain reference (``bench/reference.py``)
runs once over each prompt with its served tokens, and the number compared
is the widest gap by which a served token's logit lies below the
reference's best logit at that position (``gap_numbers``; a cell's
``check.limits`` names the numbers it compares and their limits): greedy
decoding serves the reference's argmax up to rounding, so a sound run
reads a small gap.

With ``control``, the float8 control (the reference with both operands of
every weight GEMM rounded to float8 e4m3) reads the same positions in the
program's place: its numbers are the reference's gaps of the control's own
argmax, held to the same limits, and sound limits make it not correct.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference, weights

Record = Tuple[List[int], List[int], bool]   # prompt, served tokens, finished


def sample(records: List[Record], seed: int, rows: int,
           served_target: int) -> List[Record]:
    """The longest finished request and then other finished ones in an
    order drawn from the seed, while they fit into ``rows`` and until
    ``served_target`` served tokens."""
    pool = [r for r in records if r[1] and r[2]
            and len(r[0]) + len(r[1]) <= rows]
    if not pool:
        return []
    longest = max(range(len(pool)), key=lambda i: len(pool[i][0])
                  + len(pool[i][1]))
    order = np.random.default_rng([int(seed), 7]).permutation(len(pool))
    chosen = [pool[longest]]
    used = len(pool[longest][0]) + len(pool[longest][1])
    served = len(pool[longest][1])
    for i in order:
        if served >= served_target:
            break
        if i == longest:
            continue
        n = len(pool[i][0]) + len(pool[i][1])
        if used + n <= rows:
            chosen.append(pool[i])
            used += n
            served += len(pool[i][1])
    return chosen


def pack(chosen: List[Record], rows: int):
    """Packed rows: tokens, segment ids (-1 = padding), positions, the
    served token that each row predicts, and which rows predict one."""
    tokens = np.zeros(rows, np.int32)
    seg = np.full(rows, -1, np.int32)
    pos = np.zeros(rows, np.int32)
    target = np.zeros(rows, np.int32)
    mask = np.zeros(rows, bool)
    at = 0
    for i, (prompt, out, _) in enumerate(chosen):
        seq = list(prompt) + list(out)
        n = len(seq)
        tokens[at:at + n] = seq
        seg[at:at + n] = i
        pos[at:at + n] = np.arange(n)
        # row j predicts seq[j + 1]; served tokens start at len(prompt)
        lo, hi = at + len(prompt) - 1, at + n - 1
        target[lo:hi] = seq[len(prompt):]
        mask[lo:hi] = True
        at += n
    return tokens, seg, pos, target, mask


def gap_numbers(best, at) -> Dict[str, float]:
    """The numbers a cell may compare, over the served tokens: the widest
    and the mean amount by which a served token's logit lies below the
    reference's best logit at its position."""
    gap = best - at
    return {"widest_gap": float(gap.max()), "mean_gap": float(gap.mean())}


def compare(conf: Dict, wl: Dict, spec, seed: int, records: List[Record], *,
            control: bool) -> Dict:
    chk = wl["check"]
    arch = reference.reference_arch(conf["model"])
    chosen = sample(records, seed, chk["rows"], chk["served_tokens"])
    limits = chk["limits"]
    if not chosen:
        return {"correct": False, "numbers": {
            k: {"value": None, "limit": v} for k, v in limits.items()}}

    def passes(numbers):
        return all(limits[k] is not None and numbers[k] <= limits[k]
                   for k in limits)

    tokens, seg, pos, target, mask = pack(chosen, chk["rows"])
    src = weights.WeightSource(spec, seed)
    targets = target[:, None]
    out = {}
    if control:
        _, _, ctrl_top = reference.forward(
            arch, src.get, tokens, seg, pos, targets, gemm=chk["gemm"],
            quant="fp8")
        targets = np.stack([target, ctrl_top], 1)
    best, at, top = reference.forward(arch, src.get, tokens, seg, pos,
                                      targets, gemm=chk["gemm"])
    got = gap_numbers(best[mask], at[mask, 0])
    if control:
        # the control stands in the program's place: its argmax is judged
        # by the same numbers against the same limits
        ctrl = gap_numbers(best[mask], at[mask, 1])
        out["control"] = dict(ctrl, correct=passes(ctrl), argmax_agreement=(
            float(np.mean(top[mask] == ctrl_top[mask]))))
    out["correct"] = passes(got)
    out["numbers"] = {k: {"value": got[k], "limit": v}
                      for k, v in limits.items()}
    out["sample"] = dict(got, requests=len(chosen),
                         served_tokens=int(mask.sum()),
                         argmax_agreement=float(np.mean(top[mask]
                                                        == target[mask])))
    return out
