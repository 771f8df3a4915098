"""Decode loop: total host time of the ``ContinuousBatcher.step`` spans
started in the window over their number."""
from bench.harness import in_window


def read(run):
    s = in_window(run, "decode")
    return 1e3 * sum(x.t1 - x.t0 for x in s) / len(s) if s else None
