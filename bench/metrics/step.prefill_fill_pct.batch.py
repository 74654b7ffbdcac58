"""Live prompt tokens over the padded rows of the traced prefill launches
(the ``engine.launch`` spans of kind ``prefill``:
bench/spans.py:prefill_fill_pct)."""
from bench import spans


def read(run):
    return spans.prefill_fill_pct(run)
