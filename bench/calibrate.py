"""Readings that set a cell's output-check limit, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

In one process, for each seed: one short window of the cell at its own
load (as ``bench/run.py`` drives it), then over the same sample of served
requests the program's reading (the widest gap of a served token below
the float32 reference's best) and the control's (the same, for the token
the reference computed in fp8 puts first).  Prints one JSON line per seed
and appends it to ``.bench_out/calibrate-<cell>.jsonl``.  Not run by the
benchmark's own runs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import harness
    dev = jax.devices()[0]
    info = lambda: {"platform": dev.platform, "kind": dev.device_kind,
                    "count": jax.device_count(), "memory_peak_bytes": 0}
    out = ROOT / harness.OUT_SUBDIR / f"calibrate-{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in [int(x) for x in args.seeds.split(",")]:
        s = harness.serve(ROOT, harness.load_cell(ROOT, args.workload),
                          seed, args.seconds, False, time.perf_counter(),
                          info)
        t = time.perf_counter()
        prog, n = harness.reference_gap(ROOT, s.cell, seed, s.sample)
        t_ref = time.perf_counter() - t
        ctrl, _ = harness.reference_gap(ROOT, s.cell, seed, s.sample,
                                        control=args.control)
        row = {"workload": args.workload, "seed": seed, "program": prog,
               "control": ctrl, "control_precision": args.control,
               "tokens": n, "requests": len(s.sample), "reference_s": t_ref,
               "e2e": s.e2e}
        print(json.dumps(row), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
