"""Reduce a JAX profiler trace to the numbers the per-layer readers use.

``extract`` turns an ``.xplane.pb`` into plain event lists; ``reduce``
works on those lists alone, so it is tested on a small recorded fixture
without a chip.  Device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per device operation, their ``XLA Modules`` line one
per jitted program run.  The host plane holds the harness's own
``bench.*`` annotations (admission, decode step, generator wait).

Names matched (as the chip prints them; see PERF.md):

* programs: ``jit_fused_prefill(<id>)`` and ``jit_fused_decode(<id>)``
  (the batcher's jits) on the modules line;
* ops: the HLO instruction name before `` = ``; ops of one kind
  (``copy_bitcast_fusion.4``, ``.5``) are summed under the name without
  the number.  The Pallas flash-decode call is ``decode_attention.<n>``.
  Loops (``while``) hold other ops and are left out of the op times.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
MODULES = {"prefill": "fused_prefill", "decode": "fused_decode"}
KERNELS = {"decode_attention": "decode_attention"}
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops
TOP = 10

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def extract(path: str) -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]} from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key == "ops":
                    d[key].extend((_name(e), float(e.start_ns),
                                   float(e.duration_ns)) for e in line.events)
                elif key:
                    d[key].extend((e.name, float(e.start_ns),
                                   float(e.duration_ns)) for e in line.events)
        else:
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _name(e) -> str:
    """An op event's HLO instruction name: the chip prints the whole
    instruction (``%decode_attention.5 = bf16[...] custom-call(...)``)."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def _kind(op: str) -> str:
    """``decode_attention.5`` -> ``decode_attention``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def union_ns(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(host: List[Event], t: float) -> str:
    """The innermost host annotation covering ``t`` (the shortest)."""
    best, dur = "host", None
    for name, s, d in host:
        if s <= t <= s + d and (dur is None or d < dur):
            best, dur = name[len(HOST_PREFIX):], d
    return best


def reduce(ev: Dict, window_s: float, chips: Optional[List[str]] = None
           ) -> Dict:
    """busy and idle, program and kernel device time, and the breakdown.

    Times are averaged over the device planes in ``chips`` (all if None).
    ``window_s`` is the traced window's length on the host clock."""
    names = sorted(ev["devices"]) if chips is None else chips
    n = max(1, len(names))
    busy = 0.0
    module_s = {k: 0.0 for k in MODULES}
    kernel_s = {k: 0.0 for k in KERNELS}
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for i, plane in enumerate(names):
        d = ev["devices"][plane]
        merged = union_ns([(s, s + dur) for _, s, dur in d["ops"]])
        busy += sum(b - a for a, b in merged) * 1e-9
        for name, _, dur in d["modules"]:
            for k, pat in MODULES.items():
                if pat in name:
                    module_s[k] += dur * 1e-9
        for name, _, dur in d["ops"]:
            kind = _kind(name)
            if kind in CONTAINERS:
                continue
            per_op[kind] = per_op.get(kind, 0.0) + dur * 1e-9
            for k, pat in KERNELS.items():
                if kind == pat:
                    kernel_s[k] += dur * 1e-9
        if i == 0:
            gaps = [((a1 - b0) * 1e-9, 0.5 * (b0 + a1))
                    for (_, b0), (a1, _) in zip(merged, merged[1:])]
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps, key=lambda g: -g[0])[:TOP]
    return {
        "busy_s": busy / n,
        "window_s": window_s,
        "module_s": {k: v / n for k, v in module_s.items()},
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[_label(ev["host"], mid), s] for s, mid in gaps],
        },
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir, trace_window: Tuple[float, float],
               chips: int = 1) -> Dict:
    ev = extract(find_xplane(trace_dir))
    planes = sorted(ev["devices"], key=lambda p: int(p[len(DEVICE_PREFIX):]
                                                     or 0))[:chips]
    return reduce(ev, trace_window[1] - trace_window[0], planes)
