"""Device idle share of the traced window (bench/trace.py:idle_pct)."""
from bench import trace


def read(run):
    return trace.idle_pct(run)
