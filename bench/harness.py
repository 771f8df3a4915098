"""One run of one cell: set up, drive the window, reduce, check.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration, a
traffic mix and a chip count.  Everything else about it is found by name:

* ``bench/configs/<config>.json``: the published configuration as run,
  with a ``program`` section naming the reference (``bench/refs``);
* ``bench/traffic/<traffic>.json``: the mix (see ``bench.traffic``);
* ``bench/cells/<cell>.json``: the server (``bench/servers/<kind>.py``),
  its slots and ``max_len``, and the limit of the output check;
* ``bench/layers/<metric>.py``: one reader per per-layer metric.

The window drives ``ServingEngine.submit`` / ``ServingEngine.step(now=)``
from an open-loop generator on the host clock.  Every request is timed
from when it was due.  The harness wraps the batcher's ``admit_batch`` and
``step`` on the instance, so the first token is stamped at the host sync
that ends the admission prefill and every later token at the host sync of
its decode step; the wrappers also give the spans the per-layer readers
use.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

CACHE_SUBDIR = Path(".bench_cache") / "jax"
OUT_SUBDIR = Path(".bench_out")
# A --trace 1 run traces the last seconds of its window only: a decode
# step is ~2000 device ops, and writing and reading a whole window's trace
# would take minutes.  Stopping the trace blocks the host for seconds, so
# it ends with the window; the host-clock per-layer metrics cover the
# whole window.
TRACE_S = 10.0


def _load_json(p: Path) -> Dict:
    with open(p) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a harness plug-in file (a reader, a server, a reference)."""
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: Dict                 # the BENCHMARK.json workloads entry
    config_name: str
    config: Dict                # bench/configs/<config>.json
    mix: Dict                   # bench/traffic/<traffic>.json
    spec: Dict                  # bench/cells/<cell>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` with everything its files say."""
    bench = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layers = [m for m in bench["per_layer"]
              if name in m.get("workloads", [name])]
    return Cell(name=name, entry=entry, config_name=conf["name"],
                config=_load_json(root / conf["file"]),
                mix=_load_json(root / "bench" / "traffic"
                               / f"{entry['traffic']}.json"),
                spec=_load_json(root / "bench" / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=layers)


def program_config(name: str, cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    prog = cfg["program"]
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    fields = dict(
        name=name, family=prog["family"], n_layers=cfg["num_hidden_layers"],
        d_model=D, n_heads=H, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or D // H,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        act=cfg["hidden_act"], dtype=cfg["torch_dtype"],
        source=cfg["source"])
    fields.update({k: v for k, v in prog.items()
                   if k not in ("family", "reference")})
    return ArchConfig(**fields)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

@dataclass
class Span:
    name: str
    t0: float
    t1: float
    meta: Dict = field(default_factory=dict)


class Recorder:
    """Host-clock spans and per-token times, from wrappers on the batcher."""

    def __init__(self, batcher, clock: Callable[[], float]):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.clock = clock
        self.spans: List[Span] = []
        self.tok_times: Dict[int, List[float]] = {}
        self._admit = batcher.admit_batch
        self._step = batcher.step
        self.batcher = batcher
        batcher.admit_batch = self.admit_batch
        batcher.step = self.step

    def admit_batch(self, reqs):
        t0 = self.clock()
        with self._ann("bench.admit"):
            self._admit(reqs)
        t1 = self.clock()
        for r in reqs:
            self.tok_times.setdefault(r.rid, []).extend(
                [t1] * len(r.generated))
        self.spans.append(Span("admit", t0, t1,
                               {"prompt_lens": [len(r.tokens) for r in reqs]}))

    def step(self):
        active = list(self.batcher.active.values())
        before = [len(r.generated) for r in active]
        lens = [len(r.tokens) + n - 1 for r, n in zip(active, before)]
        t0 = self.clock()
        with self._ann("bench.decode_step"):
            out = self._step()
        t1 = self.clock()
        for r, n in zip(active, before):
            self.tok_times[r.rid].extend([t1] * (len(r.generated) - n))
        self.spans.append(Span("decode", t0, t1, {"cache_lens": lens}))
        return out


@dataclass
class Run:
    """What a per-layer reader reads."""
    cell: Cell
    dims: Any                   # bench.work.Dims
    peak: Optional[Dict]
    spans: List[Span]
    window: Tuple[float, float]
    trace_window: Optional[Tuple[float, float]]
    trace: Optional[Dict]


def traced(run: Run, names: Tuple[str, ...]) -> List[Span]:
    """Spans of ``names`` that lie wholly inside the traced window."""
    if run.trace_window is None:
        return []
    a, b = run.trace_window
    return [s for s in run.spans if s.name in names and s.t0 >= a
            and s.t1 <= b]


def in_window(run: Run, name: str) -> List[Span]:
    a, b = run.window
    return [s for s in run.spans if s.name == name and a <= s.t0 < b]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def buckets_for(mix: Dict, max_len: int) -> List[int]:
    """The prefill buckets the mix's prompts can land in."""
    from repro.serving.engine import bucket_sizes
    sizes = bucket_sizes(max_len)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    first = next(b for b in sizes if b >= lo)
    last = next(b for b in sizes if b >= hi)
    return [b for b in sizes if first <= b <= last]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_compile_cache(root: Path) -> Tuple[str, int]:
    """JAX's persistent cache at the fixed path inside the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says; returns (path, entries
    found before this run)."""
    import jax
    d = root / CACHE_SUBDIR
    d.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(d), sum(1 for _ in d.iterdir())


def make_weights(arch, seed: int):
    import jax
    from repro.models import transformer
    from bench import weights
    shapes = jax.eval_shape(lambda k: transformer.init_params(arch, k),
                            jax.random.PRNGKey(0))
    params = weights.make_params(seed, shapes, arch.vocab_size)
    jax.block_until_ready(params)
    return params


def warm_up(srv, cell: Cell) -> None:
    """Compile and run every shape the window uses: one admission per
    bucket the mix can reach, and the decode step."""
    from repro.serving.engine import ServeRequest
    for i, b in enumerate(buckets_for(cell.mix, cell.spec["max_len"])):
        n = max(1, min(b, cell.mix["prompt_tokens"]["max"]) - 1)
        srv.submit(ServeRequest(-1 - i, np.ones((n,), np.int32),
                                max_new_tokens=2))
    srv.run()
    srv.completed.clear()


def serve_window(srv, rec: Recorder, reqs, t_base: float, lead_in: float,
                 seconds: float, drain_s: float, trace_dir: Optional[Path]):
    """Drive the open loop; returns (window, trace_window, lateness)."""
    import jax
    from repro.serving.engine import ServeRequest
    clock = rec.clock
    T0, T1 = t_base + lead_in, t_base + lead_in + seconds
    watch = {r.rid for r in reqs if lead_in <= r.due_s < lead_in + seconds}
    pending_first = set(watch)
    lateness: List[float] = []
    longest: List[Any] = [0.0, []]      # longest engine step in the window
    pauses: List[List[float]] = []      # the collector's pauses: [t0, s]

    def on_gc(phase, _info):
        if phase == "start":
            pauses.append([clock(), 0.0])
        elif pauses:
            pauses[-1][1] = clock() - pauses[-1][0]
    gc.callbacks.append(on_gc)
    i, n = 0, len(reqs)
    started = stopped = False
    trace_window = None
    while True:
        now = clock()
        if not started and now >= T0:
            started = True
        if (trace_dir is not None and trace_window is None
                and now >= max(T0, T1 - TRACE_S)):
            jax.profiler.start_trace(str(trace_dir))
            trace_window = (clock(), None)
        while i < n and t_base + reqs[i].due_s <= now:
            q = reqs[i]
            srv.submit(ServeRequest(q.rid, q.prompt,
                                    max_new_tokens=q.max_new_tokens,
                                    arrival=t_base + q.due_s))
            lateness.append(now - (t_base + q.due_s))
            i += 1
        if (trace_window is not None and trace_window[1] is None
                and now >= T1):
            trace_window = (trace_window[0], clock())
            jax.profiler.stop_trace()
        if started and not stopped and now >= T1:
            stopped = True
        if stopped:
            pending_first = {r for r in pending_first
                             if not rec.tok_times.get(r)}
            if not pending_first or now >= T1 + drain_s:
                break
        if srv.batcher.active or srv.queued_requests():
            n_admit = len(rec.spans)
            srv.step(now=now)
            dt = clock() - now
            if started and not stopped and dt > longest[0]:
                longest[:] = [dt, [len(x.meta["prompt_lens"]) for x in
                                   rec.spans[n_admit:] if x.name == "admit"]]
            continue
        wake = [T0] if not started else []
        if started and not stopped:
            wake.append(T1)
        if i < n:
            wake.append(t_base + reqs[i].due_s)
        if not wake:
            break
        with jax.profiler.TraceAnnotation("bench.generator_wait"):
            time.sleep(max(0.0, min(wake) - clock()))
    gc.callbacks.remove(on_gc)
    _log(f"[bench] longest engine step in the window: "
         f"{1e3 * longest[0]:.3f} ms, admissions of {longest[1]} requests")
    inside = [d for t, d in pauses if T0 <= t < T1]
    _log(f"[bench] garbage collections in the window: {len(inside)}, "
         f"longest {1e3 * max(inside, default=0.0):.3f} ms")
    return (T0, T1), trace_window, lateness


def e2e_metrics(reqs, rec: Recorder, window, t_base: float,
                seconds: float) -> Tuple[Dict[str, float], int, int]:
    from bench.stats import percentile
    T0, T1 = window
    due = {r.rid: t_base + r.due_s for r in reqs}
    attempted = [r for r in reqs if T0 <= due[r.rid] < T1]
    ttft, failed = [], 0
    for r in attempted:
        ts = rec.tok_times.get(r.rid)
        if not ts:
            failed += 1
            continue
        ttft.append(ts[0] - due[r.rid])
    gaps, n_tok = [], 0
    for ts in rec.tok_times.values():
        for a, b in zip(ts, ts[1:]):
            if T0 <= b < T1:
                gaps.append(b - a)
        n_tok += sum(1 for t in ts if T0 <= t < T1)
    out = {}
    for name, xs in (("ttft", ttft), ("tbt", gaps)):
        for q in (50, 95):
            p = percentile(xs, q)
            if p is not None:
                out[f"{name}_p{q}_ms"] = p * 1e3
        if xs:
            out[f"{name}_mean_ms"] = float(np.mean(xs)) * 1e3
    if n_tok:
        out["out_tok_s"] = n_tok / seconds
    return out, len(attempted), failed


def pick_check_sample(finished, seed: int, n_req: int) -> List:
    """The longest finished request and others drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.generated), r.rid))
    rest = order[1:]
    rng = np.random.default_rng((int(seed) % 2**64) ^ 0x5EED)
    k = min(len(rest), n_req - 1)
    picks = [rest[j] for j in sorted(rng.choice(len(rest), k, replace=False))
             ] if k > 0 else []
    return [order[0]] + picks


def _ref_inputs(sample):
    seqs, pos, toks = [], [], []
    for r in sample:
        P = len(r.tokens)
        g = np.asarray(r.generated, np.int64)
        seqs.append(np.concatenate([np.asarray(r.tokens, np.int64), g]))
        pos.append(np.arange(P - 1, P - 1 + len(g)))
        toks.append(g)
    return seqs, pos, np.concatenate(toks)


def reference_gap(root: Path, cell: Cell, seed: int, sample,
                  control: Optional[str] = None) -> Tuple[float, int]:
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample.

    With ``control`` (a precision, ``fp8``) the tokens are instead the ones
    the reference computed in that precision puts first, at the same
    positions of the same sequences: the control's reading."""
    ref = load_module(root / "bench" / "refs"
                      / f"{cell.config['program']['reference']}.py")
    seqs, pos, toks = _ref_inputs(sample)
    h = ref.final_hidden(cell.config, seed, seqs, pos)
    if control is None:
        gap = ref.gaps(cell.config, seed, h, toks)
    else:
        hc = ref.final_hidden(cell.config, seed, seqs, pos, quant=control)
        gap = ref.gaps(cell.config, seed, h, toks, h_test=hc, quant=control)
    return float(np.max(gap)), int(gap.size)


@dataclass
class _Served:
    rid: int
    tokens: np.ndarray
    generated: List[int]


@dataclass
class Served:
    """What one window left behind, with the program's state freed."""
    cell: Cell
    run: Run
    e2e: Dict[str, float]
    attempted: int
    failed: int
    device: Dict
    sample: List[_Served]
    trace_dir: Optional[Path]
    backlog: Tuple[int, int]    # due, without a first token, at T0 and T1


def serve(root: Path, cell: Cell, seed: int, seconds: float, trace: bool,
          t_start: float, device_info: Callable[[], Dict]) -> Served:
    """Set up, drive the window, read the device, free the program.

    ``t_start`` is the host clock at process start (``time.perf_counter``)
    and ``device_info()`` describes the devices (read after the window,
    before the program's state is freed)."""
    from bench import traffic as traffic_mod
    from bench import work
    cache_dir, n_cached = setup_compile_cache(root)
    _log(f"[bench] compile cache {cache_dir}: "
         f"{'warm' if n_cached else 'cold'} ({n_cached} entries)")
    name = cell.name
    arch = program_config(cell.config_name, cell.config)
    spec, mix = cell.spec, cell.mix
    server = load_module(root / "bench" / "servers" / f"{spec['server']}.py")
    t_w = time.perf_counter()
    params = make_weights(arch, seed)
    _log(f"[bench] weights {time.perf_counter() - t_w:.3f}s")
    srv = server.build(arch, params, spec)
    t_w = time.perf_counter()
    warm_up(srv, cell)
    compiles0 = srv.batcher.compile_stats()
    _log(f"[bench] warm-up {time.perf_counter() - t_w:.3f}s, compiles "
         f"{compiles0}")
    drain_s = float(spec.get("drain_s", 60))
    reqs = traffic_mod.generate(mix, seed, traffic_mod.stretches(
        mix, seconds, drain_s), arch.vocab_size)
    rec = Recorder(srv.batcher, time.perf_counter)
    trace_dir = None
    if trace:
        trace_dir = root / OUT_SUBDIR / "trace" / name
        if trace_dir.exists():
            import shutil
            shutil.rmtree(trace_dir)
        trace_dir.mkdir(parents=True)
    t_base = time.perf_counter()
    window, trace_window, lateness = serve_window(
        srv, rec, reqs, t_base, float(mix["lead_in_s"]), seconds, drain_s,
        trace_dir)
    setup_s = window[0] - t_start
    compiles1 = srv.batcher.compile_stats()
    _log(f"[bench] compiles inside the window: "
         f"{sum(compiles1[k] - compiles0[k] for k in compiles0)}")
    _log(f"[bench] generator lateness: mean "
         f"{1e3 * float(np.mean(lateness)):.3f} ms, max "
         f"{1e3 * float(np.max(lateness)):.3f} ms over {len(lateness)}")
    e2e, attempted, failed = e2e_metrics(reqs, rec, window, t_base, seconds)
    e2e["setup_s"] = setup_s
    _log("[bench] window: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
    for label, a, b in (("last 10 s of the lead-in", window[0] - 10,
                         window[0]), ("window", window[0], window[1])):
        occ = [len(x.meta["cache_lens"]) for x in rec.spans
               if x.name == "decode" and a <= x.t0 < b]
        if occ:
            _log(f"[bench] active slots per decode step, {label}: mean "
                 f"{float(np.mean(occ)):.2f} over {len(occ)} steps")
    backlog = tuple(sum(1 for r in reqs if t_base + r.due_s < t
                        and not [x for x in rec.tok_times.get(r.rid, ())
                                 if x < t]) for t in window)
    device = device_info()
    finished = [r for r in srv.completed if r.rid >= 0]
    sample = [_Served(r.rid, np.asarray(r.tokens), list(r.generated))
              for r in pick_check_sample(finished, seed,
                                         int(spec["check"]["requests"]))]
    peaks = _load_json(root / "bench" / "peaks.json")
    run = Run(cell=cell, dims=work.dims(cell.config),
              peak=peaks.get(device["kind"]), spans=rec.spans,
              window=window, trace_window=trace_window, trace=None)
    del srv, params, rec
    gc.collect()
    return Served(cell, run, e2e, attempted, failed, device, sample,
                  trace_dir, backlog)


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, device_info: Callable[[], Dict]) -> Dict:
    """One run of cell ``name``; returns the result line as a dict."""
    s = serve(root, load_cell(root, name), seed, seconds, trace, t_start,
              device_info)
    cell, run, device = s.cell, s.run, s.device
    breakdown = None
    if trace:
        from bench import trace as trace_mod
        red = trace_mod.reduce_dir(s.trace_dir, run.trace_window,
                                   int(cell.entry["chips"]))
        run.trace = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        metrics = {}
        for m in cell.per_layer:
            v = load_module(root / "bench" / "layers" / f"{m['name']}.py"
                            ).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": s.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in s.e2e}
    t_ref = time.perf_counter()
    gap, n_cmp = reference_gap(root, cell, seed, s.sample) if s.sample \
        else (None, 0)
    limit = float(cell.spec["check"]["max_logit_gap"])
    _log(f"[bench] reference over {len(s.sample)} requests, {n_cmp} served "
         f"tokens, {time.perf_counter() - t_ref:.3f}s")
    out: Dict[str, Any] = {
        "correct": bool(s.failed == 0 and n_cmp > 0 and gap is not None
                        and gap <= limit),
        "attempted": s.attempted, "failed": s.failed, "metrics": metrics,
        "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {"max_logit_gap": {"value": gap, "limit": limit},
                     "failed": {"value": s.failed, "limit": 0}}
    return out
