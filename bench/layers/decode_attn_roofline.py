"""Kernel: the least time the chip needs for what the decode-attention
calls of the traced window need (q, output, and each active slot's valid
K/V read once; see ``bench.work.decode_attn_call``), over the device time
of the Pallas decode kernel in the trace.  One call per layer per step."""
from bench import work
from bench.harness import traced


def read(run):
    s = traced(run, ("decode",))
    dev = run.trace["kernel_s"].get("decode_attention", 0.0) \
        if run.trace else 0.0
    if not s or dev <= 0 or run.peak is None:
        return None
    need = sum(run.dims.L * work.roofline_s(
        *work.decode_attn_call(run.dims, x.meta["cache_lens"]), run.peak)
        for x in s)
    return 100.0 * need / dev
