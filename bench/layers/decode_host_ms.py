"""Decode loop: host time of a decode step outside its wait for the
sampled tokens (inputs, dispatch, token bookkeeping): the program's
``pb.decode`` spans of the traced window less their ``pb.decode.wait``,
mean over the steps.  Also logs the traced window's compiles by function
and its admissions beside those that rode the pipeline belt."""
import sys

from bench import spans


def _log(run):
    recs = spans.records()
    comp = {}
    for r in spans.traced(run, "pb.compile"):
        comp[r.meta["fun_name"]] = comp.get(r.meta["fun_name"], 0) + 1
    pre = spans.traced(run, "pb.prefill")
    reqs = sum(len(p.meta["rids"]) for p in pre)
    belt = sum(len(p.meta["rids"]) for p in pre
               if p.meta["backend"] == "pipeline")
    print(f"[bench] traced window: compiles by function {comp}; admissions "
          f"{reqs} requests in {len(pre)} prefill calls, {belt} on the "
          f"pipeline belt (n_prefill_pipeline); {len(recs)} program spans",
          file=sys.stderr, flush=True)


def read(run):
    steps = spans.traced(run, "pb.decode")
    if not steps:
        return None
    _log(run)
    wait = {w.parent: w.t1 - w.t0 for w in spans.traced(run,
                                                          "pb.decode.wait")}
    host = [d.t1 - d.t0 - wait.get(d.id, 0.0) for d in steps]
    return 1e3 * sum(host) / len(host)
