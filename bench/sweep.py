"""One-off sweep of arrival rates on the chip, to find a cell's knee.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 1,2,3

For each rate, in one process: the cell's lead-in and one window at that
rate (its own copy of the mix, with the rate replaced, and no drain),
printing the end-to-end metrics, the requests due in the window, and the
backlog (requests due and still without a first token) when the window
opened and when it closed.  The knee is the highest rate whose backlog
does not grow over the window.  Not run by the benchmark's own runs; the
rate found goes into the mix's file as a number.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import harness
    dev = jax.devices()[0]
    info = lambda: {"platform": dev.platform, "kind": dev.device_kind,
                    "count": jax.device_count(), "memory_peak_bytes": 0}
    for rate in [float(x) for x in args.rates.split(",")]:
        cell = harness.load_cell(ROOT, args.workload)
        cell.mix["arrival"]["rate_per_s"] = rate
        cell.spec["drain_s"] = 0
        s = harness.serve(ROOT, cell, args.seed, args.seconds, False,
                          time.perf_counter(), info)
        print(json.dumps({"workload": args.workload, "rate": rate,
                          "attempted": s.attempted,
                          "backlog_at_open": s.backlog[0],
                          "backlog_at_close": s.backlog[1], "e2e": s.e2e}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
