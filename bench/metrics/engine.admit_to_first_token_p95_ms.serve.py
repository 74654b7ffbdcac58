"""95th percentile, over the requests due in the window that were
admitted and gave a first token, of the time from the engine's admission
stamp (``RequestState.admit_time``) to the first token: the part of the
time to first token spent in chunked prefill."""
import numpy as np


def read(run):
    times = [(s.state.first_token_time - s.state.admit_time) * 1e3
             for s in run["in_window_states"]
             if getattr(s.state, "admit_time", 0.0)
             and s.state.first_token_time]
    return float(np.percentile(times, 95)) if times else None
