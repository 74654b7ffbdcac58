"""95th percentile, over the requests due in the window that were
admitted, of the wait from the due time to the engine's own admission
stamp (``RequestState.admit_time``, the first admission)."""
import numpy as np


def read(run):
    waits = [(s.state.admit_time - s.due) * 1e3
             for s in run["in_window_states"]
             if getattr(s.state, "admit_time", 0.0)]
    return float(np.percentile(waits, 95)) if waits else None
