"""Kernels: share of the decode kernel's K/V blocks that it fetches, the
blocks up to each slot's valid length (free slots at the length their
last request left) over all blocks of the cache, summed over the
program's ``pb.decode`` spans of the traced window (their ``kv_blocks``
and ``kv_blocks_all``).  A program whose spans lack them reads nothing."""
from bench import spans


def read(run):
    steps = [d for d in spans.traced(run, "pb.decode")
             if "kv_blocks_all" in d.meta]
    total = sum(d.meta["kv_blocks_all"] for d in steps)
    if not total:
        return None
    return 100.0 * sum(d.meta["kv_blocks"] for d in steps) / total
