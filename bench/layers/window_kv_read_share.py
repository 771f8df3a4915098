"""Kernels: share of the K/V blocks the decode kernel would fetch on the
sliding layers without their window that it fetches with it, summed over
the program's ``pb.decode`` spans of the traced window (their
``kv_blocks_window`` and ``kv_blocks_nowindow``).  A program whose spans
lack them reads nothing."""
from bench import spans


def read(run):
    steps = [d for d in spans.traced(run, "pb.decode")
             if "kv_blocks_nowindow" in d.meta]
    total = sum(d.meta["kv_blocks_nowindow"] for d in steps)
    if not total:
        return None
    return 100.0 * sum(d.meta["kv_blocks_window"] for d in steps) / total
