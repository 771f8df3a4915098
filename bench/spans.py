"""The program's own spans, for the per-layer readers.

The serving path (``repro.tracing``) records ``pb.*`` spans on the host
clock while a profiler session collects, so a ``--trace 1`` run holds
them for its traced window: ``pb.schedule`` > ``pb.admit`` >
``pb.prefill`` > ``pb.prefill.wait`` at admissions, ``pb.decode`` >
``pb.decode.wait`` at decode steps, and ``pb.compile`` and ``pb.gc``
from the tracer's hooks.  Each annotated span also lands on the host
plane of the trace with its record id (``pb_id``), which places the
records on the device's clock.  A program without the tracer gives no
spans, and the readers that use them then read nothing.
"""
from __future__ import annotations

import statistics
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from bench import trace as trace_mod
from bench.harness import OUT_SUBDIR

ROOT = Path(__file__).resolve().parents[1]


def records() -> List:
    """Every record the program's tracer holds (none without one)."""
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.spans()


def traced(run, name: str) -> List:
    """Records named ``name`` that lie wholly inside the traced window."""
    if run.trace_window is None:
        return []
    a, b = run.trace_window
    return [r for r in records() if r.name == name and a <= r.t0
            and r.t1 <= b]


def extract(path: str, chips: int) -> Dict:
    """Device operation intervals of the first ``chips`` device planes and
    the annotated program spans' starts, from one ``.xplane.pb``:
    {"ops": {plane: [(start_ns, end_ns)]}, "marks": {pb_id: start_ns}}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List] = {}
    marks: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith(trace_mod.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pb."):
                    pb_id = dict(e.stats).get("pb_id")
                    if pb_id is not None:
                        marks[int(pb_id)] = float(e.start_ns)
    keep = sorted(ops, key=lambda p: int(p[len(trace_mod.DEVICE_PREFIX):]
                                         or 0))[:chips]
    return {"ops": {p: ops[p] for p in keep}, "marks": marks}


def _overlap(a: List, b: List) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_host(ev: Dict, recs: Sequence) -> Optional[Dict]:
    """The device's idle time inside program spans, from ``extract``'s
    lists and the tracer's records.

    Records go onto the device clock by the median offset between each
    annotated record's start and its mark.  Idle intervals are the gaps
    between a chip's merged operations.  Returns ``idle_s`` and
    ``idle_host_s`` (means over the chips) and the longest gaps of the
    first chip, each labelled with the innermost record over its middle
    (``host`` where the program was in none), or None where no record
    has a mark."""
    byid = {r.id: r for r in recs}
    offs = [m * 1e-9 - byid[i].t0 for i, m in ev["marks"].items()
            if i in byid]
    if not offs or not ev["ops"]:
        return None
    off = statistics.median(offs)
    spans = [((r.t0 + off) * 1e9, (r.t1 + off) * 1e9, r) for r in recs]
    host = trace_mod.union_ns([(a, b) for a, b, _ in spans])
    idle = idle_in = 0.0
    top: List = []
    for k, (_, iv) in enumerate(sorted(ev["ops"].items())):
        merged = trace_mod.union_ns(iv)
        gaps = [(b0, a1) for (_, b0), (a1, _) in zip(merged, merged[1:])]
        idle += sum(b - a for a, b in gaps) * 1e-9
        idle_in += _overlap(gaps, host) * 1e-9
        if k == 0:
            top = sorted(gaps, key=lambda g: g[0] - g[1])[:trace_mod.TOP]
    n = len(ev["ops"])

    def label(t):
        inside = [(b - a, r.name) for a, b, r in spans if a <= t <= b]
        return min(inside)[1] if inside else "host"
    return {"idle_s": idle / n, "idle_host_s": idle_in / n,
            "gaps": [[label(0.5 * (a + b)), (b - a) * 1e-9] for a, b in top]}


def trace_file(run) -> Optional[str]:
    d = ROOT / OUT_SUBDIR / "trace" / run.cell.name
    try:
        return trace_mod.find_xplane(d)
    except FileNotFoundError:
        return None
