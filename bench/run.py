"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit
(also the last lines of standard error).  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
It exits non-zero, printing no result, where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind ``bench/peaks.json`` lacks.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep it inside
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[bench] no TPU: JAX found {devs[0].platform}", file=sys.stderr)
        return 2
    peaks = json.load(open(ROOT / "bench" / "peaks.json"))
    if devs[0].device_kind not in peaks:
        print(f"[bench] no peaks for device kind {devs[0].device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 2
    chips = int(cell.entry["chips"])
    if len(devs) < chips:
        print(f"[bench] the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 2

    def device_info():
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:chips])
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": peak}

    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, device_info)
    for k, v in out["checks"].items():
        print(f"[bench] check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
