"""Decode loop: operations the active slots' tokens need (attention over
their valid lengths only) in the traced window over the device time of
the decode programs in the trace, as a share of the chip's bf16 peak."""
from bench import work
from bench.harness import traced


def read(run):
    s = traced(run, ("decode",))
    dev = run.trace["module_s"].get("decode", 0.0) if run.trace else 0.0
    if not s or dev <= 0 or run.peak is None:
        return None
    flops = sum(work.decode_flops(run.dims, x.meta["cache_lens"]) for x in s)
    return 100.0 * flops / dev / run.peak["bf16_flops_per_s"]
