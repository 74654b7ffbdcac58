"""95th percentile over the requests due in the window of the wait from
the due time to admission. A request's admission is stamped with the end
of the tick whose index is its ``admit_step``."""
import numpy as np


def read(run):
    ends = {t.step: t.end for t in run["ticks"]}
    waits = [(ends[s.state.admit_step] - s.due) * 1e3
             for s in run["in_window_states"] if s.state.admit_step in ends]
    return float(np.percentile(waits, 95)) if waits else None
