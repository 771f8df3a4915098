"""Admission prefill: share of the prefill rows x bucket tokens of the
traced window that are padding, 1 - real tokens / (rows x bucket), over
the program's ``pb.prefill`` spans."""
from bench import spans


def read(run):
    s = spans.traced(run, "pb.prefill")
    padded = sum(p.meta["rows"] * p.meta["bucket"] for p in s)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(p.meta["real_tokens"] for p in s) / padded)
