"""Share of the traced window in which no device op runs while the host is
inside the engine's ``engine.tick`` span (bench/spans.py:idle_in_tick_pct):
the part of ``device.idle_pct.serve`` that the engine's own host work
holds."""
from bench import spans


def read(run):
    return spans.idle_in_tick_pct(run)
