"""Work and device time of the grouped expert FFN (``moe_gmm``).

Counted from the configuration's shapes and the program's own counts:
a (token, held expert) pair needs the three matmuls of one expert row,
6 * D * F operations, and a call needs each expert some pair was routed
to read once, 3 * D * F weights.  Pairs routed to experts held on other
chips, pad tokens and free slots are no work.

The kernel's device time is read by a second pass over the trace's
``.xplane.pb`` (``bench/trace.py`` sums only the kernels it names):
``extract_ops`` keeps each device plane's op names and durations, and
``kernel_s`` sums those of one kind, as ``bench/trace.py`` names kinds.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import trace as trace_mod
from bench import work

KERNEL = "moe_gmm"


def moe_gmm_call(pairs: float, experts_hit: float, D: int, F: int,
                 act_bytes: int) -> Tuple[float, float]:
    """(flops, bytes) one call of the kernel needs."""
    return 6.0 * D * F * pairs, 3.0 * D * F * act_bytes * experts_hit


def need_s(meta: Dict, cfg: Dict, peak: Dict) -> float:
    """Least time the chip needs for the MoE layers of one span (a decode
    step or an admission prefill) whose ``moe_pairs`` / ``moe_experts_hit``
    are summed over its MoE layers: as many calls as MoE layers, each at
    the layers' mean counts.  Each layer is bound by the same side (its
    weights at decode, its operations at prefill), and where one is not
    the mean understates the need, never overstates it."""
    n = cfg["num_hidden_layers"] - cfg.get("num_dense_layers", 0)
    d = work.dims(cfg)
    return n * work.roofline_s(*moe_gmm_call(
        meta["moe_pairs"] / n, meta["moe_experts_hit"] / n, d.D,
        cfg["moe_intermediate_size"], d.act_bytes), peak)


def extract_ops(path: str, chips: int) -> Dict[str, List[Tuple[str, float]]]:
    """{device plane: [(op name, duration_ns)]} of the first ``chips``
    device planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Tuple[str, float]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_mod.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == trace_mod.OPS_LINE:
                out.setdefault(plane.name, []).extend(
                    (trace_mod._name(e), float(e.duration_ns))
                    for e in line.events)
    keep = sorted(out, key=lambda p: int(p[len(trace_mod.DEVICE_PREFIX):]
                                         or 0))[:chips]
    return {p: out[p] for p in keep}


def kernel_s(ops: Dict[str, List[Tuple[str, float]]],
             kind: str = KERNEL) -> float:
    """Device seconds of the ops of ``kind`` (``moe_gmm.3`` is of kind
    ``moe_gmm``), mean over the planes."""
    if not ops:
        return 0.0
    tot = sum(d for evs in ops.values() for name, d in evs
              if trace_mod._kind(name) == kind)
    return tot * 1e-9 / len(ops)
