"""Model FLOP/s utilisation of the traced step programs
(bench/trace.py:step_mfu_pct)."""
from bench import trace


def read(run):
    return trace.step_mfu_pct(run)
