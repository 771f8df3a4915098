"""Kernels: the least time the chip needs for what the grouped expert FFN
calls of the traced window need (each routed pair's operations, each hit
expert's weights read once a call; ``bench.work_moe``), from the
program's ``moe_pairs`` / ``moe_experts_hit`` on its ``pb.decode`` and
``pb.prefill`` spans, over the device time of the ``moe_gmm`` ops in the
trace.  A program without those counts reads nothing."""
from bench import spans, work_moe


def read(run):
    recs = [r for name in ("pb.decode", "pb.prefill")
            for r in spans.traced(run, name) if "moe_pairs" in r.meta]
    path = spans.trace_file(run)
    if not recs or path is None or run.peak is None:
        return None
    dev = work_moe.kernel_s(work_moe.extract_ops(
        path, int(run.cell.entry["chips"])))
    if dev <= 0:
        return None
    need = sum(work_moe.need_s(r.meta, run.cell.config, run.peak)
               for r in recs)
    return 100.0 * need / dev
