"""Mean device time of one decode-step program in the traced window."""
from bench import trace


def read(run):
    return trace.mean_step_ms(run, "decode")
