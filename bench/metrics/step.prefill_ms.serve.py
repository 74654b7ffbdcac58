"""Mean device time of one prefill-step program in the traced window."""
from bench import trace


def read(run):
    return trace.mean_step_ms(run, "prefill")
