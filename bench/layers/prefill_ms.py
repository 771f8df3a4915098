"""Admission prefill: mean host time of one ``admit_batch`` call started
in the window; each call ends in its own host sync of the first tokens."""
from bench.harness import in_window


def read(run):
    s = in_window(run, "admit")
    return 1e3 * sum(x.t1 - x.t0 for x in s) / len(s) if s else None
