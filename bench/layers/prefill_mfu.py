"""Admission prefill: operations of the real (unpadded) prompt tokens
admitted in the traced window over the device time of the prefill
programs in the trace, as a share of the chip's bf16 peak."""
from bench import work
from bench.harness import traced


def read(run):
    s = traced(run, ("admit",))
    dev = run.trace["module_s"].get("prefill", 0.0) if run.trace else 0.0
    if not s or dev <= 0 or run.peak is None:
        return None
    flops = sum(work.prefill_flops(run.dims, n)
                for x in s for n in x.meta["prompt_lens"])
    return 100.0 * flops / dev / run.peak["bf16_flops_per_s"]
