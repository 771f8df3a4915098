"""Device: share of the traced window in which the device is idle while
the host is inside a program span (``pb.*``), mean over the cell's chips.
Idle is the gaps between the device's merged operations; the spans are
placed on the device's clock by their marks on the trace's host plane.
Also logs the longest gaps of the first chip with the innermost program
span over each."""
import sys

from bench import spans


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or run.trace_window is None:
        return None
    a, b = run.trace_window
    recs = [r for r in spans.records() if a <= r.t0 and r.t1 <= b]
    path = spans.trace_file(run)
    if not recs or path is None:
        return None
    r = spans.idle_host(spans.extract(path, int(run.cell.entry["chips"])),
                        recs)
    if r is None:
        return None
    print(f"[bench] idle {r['idle_s']:.6f} s between device ops, "
          f"{r['idle_host_s']:.6f} s of it inside program spans; longest "
          f"gaps {r['gaps']}", file=sys.stderr, flush=True)
    return 100.0 * r["idle_host_s"] / t["window_s"]
