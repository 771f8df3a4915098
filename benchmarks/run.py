"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
Latency benches run on the byte/bandwidth-accurate cold-start simulator
calibrated to the paper's testbed (core/simulator.py, GPU_PAPER) plus the
TPU-v5e target constants; functional benches execute the real engine on
reduced models (CPU wall-clock).

Map (paper artifact -> bench):
  Fig. 1/9, Table 1  -> bench_cold_start_breakdown, bench_breakdown_lora
  Fig. 8             -> bench_ttft
  Fig. 6             -> bench_strategy_crossover
  Fig. 10            -> bench_ttft_lora
  Fig. 11/12         -> bench_scaling_shapes
  Fig. 13            -> bench_scaling_devices
  Fig. 14            -> bench_adapter_epochs
  Fig. 15/16         -> bench_recovery_loading
  Fig. 17            -> bench_recovery_inference
  (engine, CPU)      -> bench_engine_functional, bench_kernels
  (cluster, CPU)     -> bench_cluster_burst (see also cluster_bench.py for
                        the JSON-emitting trajectory entry)
  (hot path, CPU)    -> bench_decode_hotpath (steps/sec + compile counts
                        -> BENCH_decode_hotpath.json)
  (recovery, CPU)    -> bench_recovery (post-crash TTFT: KV migration vs
                        re-prefill -> BENCH_recovery.json)
  (cold start, CPU)  -> bench_coldstart (overlapped vs load-then-serve
                        TTFT -> BENCH_coldstart.json)
  (chaos, CPU)       -> bench_chaos (elastic repartition vs full
                        migration under seeded fault schedules
                        -> BENCH_chaos.json)
  (multicast, CPU)   -> bench_multicast (peer-to-peer burst scale-out vs
                        host-only cold starts, with a mid-propagation
                        source crash -> BENCH_multicast.json)
  (state tier, CPU)  -> bench_prefix (cross-request prefix-cache prefill
                        savings + spill/resurrect TTFT
                        -> BENCH_prefix.json)

Run ``python benchmarks/run.py [bench_name ...] [--small]`` to run a
subset (CI smoke uses ``bench_recovery --small``).  JSON trajectories are
keyed by (commit, config): re-running a bench on the same commit replaces
its entry in place instead of duplicating it.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.paper_models import (FALCON_7B, MISTRAL_7B, OPT_1_3B,
                                     OPT_2_7B, OPT_6_7B, OPT_13B,
                                     PAPER_MODELS)
from repro.configs.base import get_arch
from repro.core import simulator as sim
from repro.core.adapter_scheduler import (EagerPolicy, EpochSchedulerPolicy,
                                          simulate_adapter_serving)
from repro.core.simulator import GPU_PAPER, TPU_V5E
from repro.launch.compile_cache import enable_compile_cache

ROWS = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def _git_commit() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


def append_keyed_entry(path: str, entry: dict) -> int:
    """Append ``entry`` to a ``{"entries": [...]}`` trajectory file,
    replacing in place any existing entry with the same
    (``commit``, ``config``) key — re-running a bench on the same commit
    and configuration must update its row, not duplicate it.  Returns the
    entry count."""
    doc = {"entries": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception:
            # never silently erase trajectory history: shelve the
            # unreadable file and start a fresh one
            corrupt = path + ".corrupt"
            os.replace(path, corrupt)
            print(f"# WARN: {path} was unreadable; moved to {corrupt}")
    entries = doc.setdefault("entries", [])
    for i, e in enumerate(entries):
        if (e.get("commit"), e.get("config")) == (entry.get("commit"),
                                                  entry.get("config")):
            entries[i] = entry
            break
    else:
        entries.append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return len(entries)


# ---------------------------------------------------------------------------
# Fig. 8 / Fig. 10: TTFT across models and systems
# ---------------------------------------------------------------------------

def bench_ttft(lora_rank: int = 0):
    """Fig. 8/10: modeled cold-start TTFT, PipeBoost vs ServerlessLLM
    and Transformers across the paper models."""
    tag = "lora_" if lora_rank else ""
    for cfg in PAPER_MODELS:
        rows = {}
        for strat in ("transformers", "serverlessllm", "pipeboost"):
            r = sim.simulate_cold_start(cfg, GPU_PAPER, 2, strat,
                                        lora_rank=lora_rank)
            rows[strat] = r.ttft
            emit(f"ttft_{tag}{cfg.name}_{strat}", r.ttft * 1e6)
        red_sl = 100 * (1 - rows["pipeboost"] / rows["serverlessllm"])
        red_tr = 100 * (1 - rows["pipeboost"] / rows["transformers"])
        emit(f"ttft_{tag}{cfg.name}_reduction", 0.0,
             f"vs_sllm={red_sl:.1f}% vs_transformers={red_tr:.1f}%")


def bench_ttft_lora():
    """Fig. 10: the TTFT comparison with rank-16 LoRA stages enabled."""
    bench_ttft(lora_rank=16)


# ---------------------------------------------------------------------------
# Fig. 1/9, Table 1: startup breakdown
# ---------------------------------------------------------------------------

def bench_cold_start_breakdown():
    """Fig. 1/9, Table 1: cold-start stage breakdown (load vs compute
    share of TTFT) per system."""
    for cfg in (MISTRAL_7B, OPT_13B):
        for strat in ("serverlessllm", "pipeboost"):
            r = sim.simulate_cold_start(cfg, GPU_PAPER, 2, strat)
            for stage, t in r.breakdown.items():
                if stage == "total":
                    continue
                emit(f"breakdown_{cfg.name}_{strat}_{stage}", t * 1e6,
                     f"{100 * t / r.ttft:.1f}%_of_ttft")
            load = r.breakdown["load_ckpt_dram"] + r.breakdown["load_params"]
            emit(f"breakdown_{cfg.name}_{strat}_load_share", 0.0,
                 f"{100 * load / r.ttft:.1f}%")


def bench_breakdown_lora():
    """Table 1: LoRA stages add negligible overhead."""
    for cfg in (MISTRAL_7B, OPT_13B):
        base = sim.simulate_cold_start(cfg, GPU_PAPER, 2, "pipeboost")
        lora = sim.simulate_cold_start(cfg, GPU_PAPER, 2, "pipeboost",
                                       lora_rank=16)
        over = 100 * (lora.ttft - base.ttft) / base.ttft
        emit(f"lora_overhead_{cfg.name}", (lora.ttft - base.ttft) * 1e6,
             f"{over:.2f}%_ttft_increase")


# ---------------------------------------------------------------------------
# Fig. 6: strategy crossover
# ---------------------------------------------------------------------------

def bench_strategy_crossover():
    """Fig. 6: mean request latency, pipeline vs per-device single
    strategy, across request rates (the switch crossover)."""
    for rps in (0.5, 2.0, 8.0, 20.0, 40.0):
        p = sim.simulate_request_latency(OPT_1_3B, GPU_PAPER, 4, rps,
                                         strategy="pipeline")
        s = sim.simulate_request_latency(OPT_1_3B, GPU_PAPER, 4, rps,
                                         strategy="single")
        emit(f"crossover_rps{rps}_pipeline", p["mean"] * 1e6)
        emit(f"crossover_rps{rps}_single", s["mean"] * 1e6,
             f"single_wins={s['mean'] < p['mean']}")


# ---------------------------------------------------------------------------
# Fig. 11/12: input length & batch scaling
# ---------------------------------------------------------------------------

def bench_scaling_shapes():
    """Fig. 11/12: TTFT reduction across input lengths and batch
    sizes."""
    for prompt in (200, 500):
        sl = sim.simulate_cold_start(MISTRAL_7B, GPU_PAPER, 2,
                                     "serverlessllm", prompt=prompt)
        pb = sim.simulate_cold_start(MISTRAL_7B, GPU_PAPER, 2, "pipeboost",
                                     prompt=prompt)
        emit(f"inputlen{prompt}_mistral7b_sllm", sl.ttft * 1e6)
        emit(f"inputlen{prompt}_mistral7b_pipeboost", pb.ttft * 1e6,
             f"reduction={100 * (1 - pb.ttft / sl.ttft):.1f}%")
    for batch in (64, 256):
        sl = sim.simulate_cold_start(FALCON_7B, GPU_PAPER, 2,
                                     "serverlessllm", batch=batch)
        pb = sim.simulate_cold_start(FALCON_7B, GPU_PAPER, 2, "pipeboost",
                                     batch=batch)
        emit(f"batch{batch}_falcon7b_sllm", sl.ttft * 1e6)
        emit(f"batch{batch}_falcon7b_pipeboost", pb.ttft * 1e6,
             f"reduction={100 * (1 - pb.ttft / sl.ttft):.1f}%")


# ---------------------------------------------------------------------------
# Fig. 13: device-count scaling
# ---------------------------------------------------------------------------

def bench_scaling_devices():
    """Fig. 13: TTFT scaling with device count (more devices -> less
    model per device -> faster first token)."""
    base = None
    for n in (1, 2, 4, 8):
        pb = sim.simulate_cold_start(MISTRAL_7B, GPU_PAPER, n, "pipeboost")
        sl = sim.simulate_cold_start(MISTRAL_7B, GPU_PAPER, n,
                                     "serverlessllm")
        base = base or pb.ttft
        emit(f"devices{n}_mistral7b_pipeboost", pb.ttft * 1e6,
             f"vs_1dev={100 * (1 - pb.ttft / base):.1f}% "
             f"vs_sllm={100 * (1 - pb.ttft / sl.ttft):.1f}%")


# ---------------------------------------------------------------------------
# Fig. 14: epoch-based adapter switching
# ---------------------------------------------------------------------------

def bench_adapter_epochs():
    """Fig. 14: epoch-based adapter scheduling vs eager switching
    (latency mean/variance and merge counts across rates)."""
    for rps in (5.0, 10.0, 15.0, 20.0, 25.0):
        ep = simulate_adapter_serving(
            EpochSchedulerPolicy(epoch_budget=8, max_batch=8), rps=rps,
            horizon=30.0, switch_prob=0.2)
        eg = simulate_adapter_serving(EagerPolicy(max_batch=8), rps=rps,
                                      horizon=30.0, switch_prob=0.2)
        emit(f"adapter_rps{rps}_epoch", ep["mean"] * 1e6,
             f"var={ep['var']:.4f} merges={ep['merges']:.0f}")
        emit(f"adapter_rps{rps}_eager", eg["mean"] * 1e6,
             f"var={eg['var']:.4f} merges={eg['merges']:.0f} "
             f"epoch_cut={100 * (1 - ep['mean'] / max(eg['mean'], 1e-9)):.1f}%")


# ---------------------------------------------------------------------------
# Fig. 15/16: recovery during loading
# ---------------------------------------------------------------------------

def bench_recovery_loading():
    """Fig. 15/16: modeled recovery from device failure during loading
    (pipeline-parallel reassignment vs full reload)."""
    pp = sim.simulate_loading_failure(MISTRAL_7B, GPU_PAPER, 4,
                                      failed=[1, 2], mode="pp")
    fl = sim.simulate_loading_failure(MISTRAL_7B, GPU_PAPER, 4,
                                      failed=[1, 2], mode="full")
    norm = sim.simulate_cold_start(MISTRAL_7B, GPU_PAPER, 4, "pipeboost")
    emit("recovery_load_pp", pp.recovery_time * 1e6,
         f"ttft={pp.ttft:.2f}s")
    emit("recovery_load_full", fl.recovery_time * 1e6,
         f"ttft={fl.ttft:.2f}s cut={100 * (1 - pp.recovery_time / fl.recovery_time):.1f}%")
    emit("recovery_load_normal_ttft", norm.ttft * 1e6, "no-crash baseline")
    for n in (2, 3, 4):
        r = sim.simulate_loading_failure(MISTRAL_7B, GPU_PAPER, n,
                                         failed=[0], mode="pp")
        emit(f"recovery_devices{n}_ttft", r.ttft * 1e6)


# ---------------------------------------------------------------------------
# Fig. 17: recovery during inference
# ---------------------------------------------------------------------------

def bench_recovery_inference():
    """Fig. 17: throughput halt and dip when devices fail mid-inference
    (pipeline-parallel recovery vs full restart)."""
    for mode in ("pp", "full"):
        tl = sim.simulate_inference_failure(MISTRAL_7B, GPU_PAPER, 4,
                                            mode=mode)
        post = [thr for t, thr in tl if t > 6.0]
        halt = sum(1 for x in post if x == 0.0) * 0.25
        dip = min(post)
        emit(f"recovery_infer_{mode}_halt", halt * 1e6,
             f"min_thr={dip:.0f}tok/s steady={tl[-1][1]:.0f}tok/s")


# ---------------------------------------------------------------------------
# Functional benches: the real engine on reduced models (CPU wall-clock)
# ---------------------------------------------------------------------------

def bench_engine_functional():
    """Real-engine wall-clock on a reduced model: cold prefill off one
    load round, 8 decode steps, and crash+recover with KV reuse."""
    from repro.core.engine import PipeBoostEngine, generate
    from repro.models import transformer as T
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    eng = PipeBoostEngine(cfg, params, n_devices=4, max_len=64)
    t0 = time.perf_counter()
    eng.load_round()
    logits = eng.prefill(batch)
    t1 = time.perf_counter()
    emit("engine_cold_prefill_reduced", (t1 - t0) * 1e6,
         f"segments_loaded=1/4_per_device ready={eng.ready}")
    t0 = time.perf_counter()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(8):
        tok = jnp.argmax(eng.decode(tok), -1).astype(jnp.int32)
    t1 = time.perf_counter()
    emit("engine_decode8_reduced", (t1 - t0) * 1e6)
    # crash + recover wall-clock (functional)
    eng.crash([1, 2])
    t0 = time.perf_counter()
    stats = eng.recover()
    t1 = time.perf_counter()
    emit("engine_recover_reduced", (t1 - t0) * 1e6,
         f"kv_reused={stats['reconstruct']['kv_reused']} "
         f"full_prefill={stats['reconstruct']['full_prefill']}")


def bench_cluster_burst():
    """Serverless cluster (functional): burst wave over 2 servers with a
    mid-burst whole-server crash + re-route; TTFT/TBT percentiles."""
    from repro.cluster import (Autoscaler, AutoscalerConfig, ClusterConfig,
                               ClusterRouter, burst_wave_trace)
    from repro.models import transformer as T
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    trace = burst_wave_trace(16, base_rate=2.0, wave_rate=16.0, wave_at=0.5,
                             wave_len=1.0, seed=0)
    router = ClusterRouter(
        cfg, params, n_servers=2,
        ccfg=ClusterConfig(n_devices=2, n_slots=4),
        autoscaler=Autoscaler(AutoscalerConfig(target_queue_per_server=4,
                                               max_servers=4)))
    t0 = time.perf_counter()
    router.run(trace, crash_after_completions=4, crash_server_id=1,
               rejoin_after_ticks=20)
    wall = time.perf_counter() - t0
    s = router.metrics.summary()
    emit("cluster_burst_ttft_p50", s["ttft_p50"] * 1e6)
    emit("cluster_burst_ttft_p99", s["ttft_p99"] * 1e6,
         f"completed={s['n_completed']:.0f}/{s['n_requests']:.0f} "
         f"rerouted={s['n_rerouted']:.0f}")
    emit("cluster_burst_tbt_p50", s["tbt_p50"] * 1e6)
    emit("cluster_burst_tbt_p99", s["tbt_p99"] * 1e6,
         f"gpu_seconds={s['gpu_seconds']:.1f}")
    emit("cluster_burst_wall", wall * 1e6,
         f"servers_max={s['servers_max']:.0f}")


def bench_decode_hotpath():
    """Zero-copy decode hot path vs the pre-PR batcher (functional, CPU).

    Steady-state decode steps/sec: the donated fused decode+sample step
    (in-place cache update, one host transfer) against a faithful replica
    of the legacy loop (non-donated decode jit returning a fresh cache,
    eager host-side sampler, tokens rebuilt on host every step).  Also
    runs a mixed-length burst of 16 prompts through the bucketed prefill
    and reports compile counts.  Results append to the
    ``BENCH_decode_hotpath.json`` trajectory.
    """
    from repro.serving.engine import (ContinuousBatcher, ServeRequest,
                                      ServingEngine, bucket_sizes,
                                      quantized_greedy)
    from repro.models import transformer as T

    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2, head_dim=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    n_slots, max_len, steps = 8, 2048, 30
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=8 + i) for i in range(n_slots)]

    # -- legacy replica: the pre-PR ContinuousBatcher hot loop -------------
    class _LegacyBatcher:
        def __init__(self):
            self.cache = T.init_cache(cfg, n_slots, max_len,
                                      jnp.dtype(cfg.dtype))
            self.cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
            self._decode = jax.jit(
                lambda p, t, c: T.decode_step(cfg, p, {"tokens": t}, c))

        def admit(self, slot, prompt):
            logits, c1 = T.forward(cfg, params,
                                   {"tokens": jnp.asarray(prompt)[None]},
                                   mode="prefill", max_len=max_len)
            for k in ("attn", "ssm", "rec"):          # per-leaf host loop
                if k in c1:
                    for leaf in c1[k]:
                        self.cache[k][leaf] = \
                            self.cache[k][leaf].at[:, slot].set(
                                c1[k][leaf][:, 0])
            self.cache["pos"] = self.cache["pos"].at[slot].set(
                int(c1["pos"][0]))
            return int(np.asarray(quantized_greedy(logits))[0])

        def step(self, toks):
            logits, self.cache = self._decode(params, jnp.asarray(toks),
                                              self.cache)
            return np.asarray(quantized_greedy(logits))

    legacy = _LegacyBatcher()
    toks = np.zeros((n_slots,), np.int32)
    for s, p in enumerate(prompts):
        toks[s] = legacy.admit(s, p)
    legacy.step(toks)                                  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        toks = legacy.step(toks)
    legacy_sps = steps / (time.perf_counter() - t0)
    emit("hotpath_legacy_steps_per_s", 1e6 / legacy_sps,
         f"{legacy_sps:.1f}steps/s")

    # -- fused donated path ------------------------------------------------
    cb = ContinuousBatcher(cfg, params, n_slots=n_slots, max_len=max_len,
                           sampler=quantized_greedy)
    for i, p in enumerate(prompts):
        cb.admit(ServeRequest(i, p, max_new_tokens=steps + 64))
    cb.step()                                          # compile
    cb.n_decode_steps, cb.decode_time_s = 0, 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        cb.step()
    fused_sps = steps / (time.perf_counter() - t0)
    speedup = fused_sps / legacy_sps
    emit("hotpath_fused_steps_per_s", 1e6 / fused_sps,
         f"{fused_sps:.1f}steps/s speedup={speedup:.2f}x "
         f"tokens_per_s={fused_sps * n_slots:.1f}")

    # -- bucketed prefill compile counts on a mixed-length burst -----------
    eng = ServingEngine(cfg, params, n_slots=4, max_len=128)
    eng.batcher.sampler = quantized_greedy
    burst_lens = rng.permutation(np.arange(5, 121))[:16]
    for i, L in enumerate(burst_lens):
        eng.submit(ServeRequest(100 + i, rng.integers(0, 250, size=int(L)),
                                max_new_tokens=3))
    eng.run()
    cs = eng.batcher.compile_stats()
    n_buckets = len(bucket_sizes(128))
    emit("hotpath_prefill_compiles", float(cs["prefill_compiles"]),
         f"buckets={n_buckets} lengths=16 "
         f"decode_compiles={cs['decode_compiles']}")

    # -- JSON trajectory (keyed: re-runs replace, never duplicate) ---------
    path = "BENCH_decode_hotpath.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"arch": cfg.name, "n_slots": n_slots, "max_len": max_len,
                   "steps": steps},
        "ts": time.time(),
        "fused_steps_per_s": fused_sps,
        "legacy_steps_per_s": legacy_sps,
        "speedup": speedup,
        "tokens_per_s": fused_sps * n_slots,
        "prefill_compiles": cs["prefill_compiles"],
        "decode_compiles": cs["decode_compiles"],
        "n_buckets": n_buckets,
    })
    print(f"# wrote {path} ({n} entries)")


def bench_recovery(small: bool = False):
    """Crash recovery: KV-snapshot migration vs re-prefill (functional).

    Drains mid-decode requests off a "crashed" serving engine (exporting
    their KV snapshots) and hands them to two identical warmed survivors.
    Post-crash TTFT = wall-clock from the hand-off until every displaced
    request has produced its next token: the migrated survivor imports
    snapshots (zero re-prefilled prompt tokens — asserted via its prefill
    counters), the baseline re-submits and re-prefills prompt+prefix.
    Asserts the migrated path is strictly faster AND that both finish
    with identical greedy continuations (the equivalence oracle).  The
    full lane also times a partial crash routed through
    ``reconstruct_cache``.  Appends to ``BENCH_recovery.json`` keyed by
    commit+config (the CI fast-lane smoke runs this with ``--small``).
    """
    from repro.cluster import ClusterConfig
    from repro.models import transformer as T
    from repro.serving.engine import (ServeRequest, ServingEngine,
                                      quantized_greedy)

    n_layers = 2 if small else 4
    n_victims = 3
    prompt_len, max_len = 72, 96
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=prompt_len)
               for _ in range(n_victims)]

    def make_engine():
        e = ServingEngine(cfg, params, n_slots=4, max_len=max_len)
        e.batcher.sampler = quantized_greedy
        return e

    # "crashed" server: victims mid-decode, drained with their snapshots
    crashed = make_engine()
    victims = [ServeRequest(i, p, max_new_tokens=30)
               for i, p in enumerate(prompts)]
    for r in victims:
        crashed.submit(r)
    for _ in range(6):
        crashed.step()
    drained = crashed.drain_inflight()
    assert len(drained) == n_victims \
        and all(r.snapshot is not None for r in drained)

    def clone(r):
        c = ServeRequest(r.rid, r.tokens, r.max_new_tokens, r.adapter,
                         r.arrival, generated=list(r.generated))
        c.snapshot = r.snapshot       # numpy rows: shared read-only
        return c

    def make_survivor():
        # warm every post-crash code path OUTSIDE the timed window: the
        # prefill bucket the victims land in, the decode step, and the
        # snapshot-import jit — so the window measures steady-state
        # recovery work, not XLA compiles
        b = make_engine()
        b.submit(ServeRequest(999, prompts[0], max_new_tokens=2))
        b.run()
        b.batcher.warm_import()
        return b

    def time_to_next_token(survivor, reqs, *, migrate: bool) -> float:
        """Post-crash TTFT: hand the displaced requests to the survivor
        and run until each has produced its next token."""
        before = {r.rid: len(r.generated) for r in reqs}
        t0 = time.perf_counter()
        for r in reqs:
            if migrate:
                assert survivor.admit_with_state(r), "import refused"
            else:
                survivor.submit(r)
        while not all(len(r.generated) > before[r.rid] or r.done
                      for r in reqs):
            survivor.step()
        return time.perf_counter() - t0

    def median_window(survivor, *, migrate: bool, reps: int = 5):
        """Median over repeated hand-off windows (the displaced requests
        are re-cloned and the survivor re-drained between reps, so each
        window measures the same steady-state recovery work)."""
        ts = []
        for _ in range(reps):
            batch = [clone(r) for r in drained]
            if not migrate:
                for r in batch:
                    r.snapshot = None     # the state died with the server
            ts.append(time_to_next_token(survivor, batch, migrate=migrate))
            survivor.drain_inflight(export_state=False)
        # a final untimed admission rides to completion for the
        # equivalence check below
        final = [clone(r) for r in drained]
        for r in final:
            if migrate:
                assert survivor.admit_with_state(r), "import refused"
            else:
                r.snapshot = None
                survivor.submit(r)
        return float(np.median(ts)), final

    b_mig, b_rep = make_survivor(), make_survivor()

    # tokens the baseline recomputes = prompt + generated prefix at
    # re-submission; migration moves their state instead (pos = that - 1)
    reprefill_tokens = sum(len(r.tokens) + len(r.generated) for r in drained)
    migrated_tokens = sum(r.snapshot.pos for r in drained)

    prefills_before = b_mig.batcher.n_prefill_reqs
    t_mig, mig_reqs = median_window(b_mig, migrate=True)
    t_rep, rep_reqs = median_window(b_rep, migrate=False)
    assert b_mig.batcher.n_prefill_reqs == prefills_before, \
        "migration re-prefilled — zero-re-prefill invariant broken"
    assert b_mig.batcher.n_migrated_in > 0
    assert t_mig < t_rep, (
        f"post-crash TTFT regression: migrate {t_mig * 1e3:.1f}ms is not "
        f"faster than re-prefill {t_rep * 1e3:.1f}ms")
    # equivalence oracle: both recovery modes must finish with identical
    # greedy continuations
    b_mig.run()
    b_rep.run()
    for m, p in zip(mig_reqs, rep_reqs):
        assert m.generated == p.generated, (m.rid, m.generated, p.generated)
    emit("recovery_migrate_post_crash_ttft", t_mig * 1e6,
         f"migrated={n_victims} migrated_tokens={migrated_tokens} "
         f"reprefilled_tokens=0")
    emit("recovery_reprefill_post_crash_ttft", t_rep * 1e6,
         f"rerouted={n_victims} reprefilled_tokens={reprefill_tokens} "
         f"speedup={t_rep / t_mig:.2f}x")

    # modeled snapshot-transfer cost (satellite to the measured numbers):
    # the byte payload each migration moves, priced over the paper
    # testbed's two links — NVLink-class device P2P vs PCIe-class
    # host-link (core/simulator.py estimator, GPU_PAPER bandwidths)
    model_bytes = sum(sim.kv_snapshot_bytes(cfg, r.snapshot.pos, max_len)
                      for r in drained)
    actual_bytes = sum(r.snapshot.nbytes() for r in drained)
    t_nvlink = sim.snapshot_transfer_time(model_bytes, GPU_PAPER, "nvlink")
    t_pcie = sim.snapshot_transfer_time(model_bytes, GPU_PAPER, "pcie")
    emit("recovery_snapshot_xfer_nvlink", t_nvlink * 1e6,
         f"payload={model_bytes}B (in-memory rows {actual_bytes}B)")
    emit("recovery_snapshot_xfer_pcie", t_pcie * 1e6,
         f"vs_measured_migrate={t_pcie / max(t_mig, 1e-9):.3f}x")

    # partial crash: in-place per-layer reconstruction (full lane only)
    recon = {}
    if not small:
        from repro.cluster import ClusterServer
        ccfg = ClusterConfig(n_devices=4, n_slots=4)
        server = ClusterServer(0, cfg, params, ccfg)
        while server.state == "loading":
            server.tick(0.0)
        for i in range(3):
            server.submit(ServeRequest(i, rng.integers(0, 250, size=32),
                                       max_new_tokens=16))
        # two serving ticks: requests decode while the chain still spans
        # several devices (full load would collapse ownership onto one)
        for _ in range(2):
            server.tick(0.0)
        # pick a device whose death loses SOME layers (partial, not total)
        cands = [d for d in range(ccfg.n_devices)
                 if 0 < sum(server.engine.lost_state_layers([d]))
                 < cfg.n_layers]
        assert cands, "no partial-loss device — chain collapsed early"
        # fewest lost layers = most surviving KV for the Q-only reuse path
        cands.sort(key=lambda d: sum(server.engine.lost_state_layers([d])))
        t0 = time.perf_counter()
        server.crash([cands[0]])
        t_recon = time.perf_counter() - t0
        recon = dict(server.last_recovery)
        assert recon.get("reconstructed_reqs", 0) > 0
        assert recon.get("layers_skipped", 0) + recon.get("kv_reused", 0) > 0
        emit("recovery_partial_reconstruct", t_recon * 1e6,
             f"kv_reused={recon.get('kv_reused', 0):.0f} "
             f"full_prefill={recon.get('full_prefill', 0):.0f} "
             f"layers_skipped={recon.get('layers_skipped', 0):.0f}")

    path = "BENCH_recovery.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"arch": cfg.name, "n_layers": n_layers,
                   "n_victims": n_victims, "prompt_len": prompt_len,
                   "small": small},
        "ts": time.time(),
        "migrate_post_crash_ttft_s": t_mig,
        "reprefill_post_crash_ttft_s": t_rep,
        "speedup": t_rep / t_mig,
        "migrated_reqs": n_victims,
        "migrated_tokens": migrated_tokens,
        "reprefill_tokens_baseline": reprefill_tokens,
        "snapshot_payload_bytes": model_bytes,
        "snapshot_rows_bytes": actual_bytes,
        "snapshot_xfer_nvlink_s": t_nvlink,
        "snapshot_xfer_pcie_s": t_pcie,
        "partial_reconstruct": recon,
    })
    print(f"# wrote {path} ({n} entries)")


def bench_coldstart(small: bool = False):
    """Overlapped cold start vs load-then-serve TTFT (the tentpole claim).

    Runs the REAL engine twice on the same reduced model and prompts:

    * **overlapped** — fill rounds advance via the engine's generator-step
      driver; the prefill dispatches the moment ``ready`` flips (each
      device holds ~1/N of the model) and decoding continues while the
      remaining segments stream in, strategy-switching when full.
    * **load-then-serve** — every segment loads before the first prefill
      (the ServerlessLLM-style baseline sequencing).

    Time is discrete-event hybrid: compute (prefill/decode) is measured
    wall-clock on the functional model; the load channel is the paper's
    A100 testbed constants (``GPU_PAPER.host_link_bw``) applied to the
    FULL architecture's per-segment bytes — devices transfer in parallel,
    so a round costs its slowest device.  Asserts the paper's §4.3
    invariants: overlapped and fully-loaded token streams are
    BIT-IDENTICAL, the decode step compiles exactly once across the
    strategy switch, and overlapped TTFT beats the baseline.  Appends to
    ``BENCH_coldstart.json`` keyed by commit+config.
    """
    from repro.core.engine import PipeBoostEngine
    from repro.core import analytic
    from repro.core.planner import make_plan
    from repro.models import transformer as T

    n_layers = 2 if small else 8
    n_devices = 2 if small else 4
    n_tokens = 4 if small else 12
    full_cfg = get_arch("qwen3-1.7b")
    cfg = full_cfg.reduced(n_layers=n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, min(cfg.vocab_size, 250))}

    # load-channel model: the full arch's bytes on the reduced plan's
    # segment ring (same ring topology, paper-scale transfer times)
    full_plan = make_plan(analytic.layer_bytes_list(full_cfg), n_devices)
    seg_bytes = {s.idx: s.bytes for s in full_plan.segments}
    bw = GPU_PAPER.host_link_bw

    def round_load_s(round_):
        per_dev = {}
        for dev, seg in round_.segments:
            per_dev[dev] = per_dev.get(dev, 0) + seg_bytes[seg % len(seg_bytes)]
        return max(per_dev.values()) / bw if per_dev else 0.0

    def run_engine(overlap: bool):
        eng = PipeBoostEngine(cfg, params, n_devices=n_devices, max_len=64)
        # warm the XLA compiles outside the timed window: the bench
        # measures cold-start *serving* latency (load channel + compute),
        # not compilation — a real fleet reuses the compile cache
        lg_w, c_w = eng._prefill_jit(eng._merged_params, batch)
        tok_w = jnp.argmax(lg_w, -1).astype(jnp.int32)
        jax.block_until_ready(eng._decode_jit(eng._merged_params, tok_w, c_w))
        fill = eng.fill_steps()
        t_load = 0.0                      # load-channel clock
        if overlap:
            while not eng.ready:
                t_load += round_load_s(next(fill))
        else:
            for r in fill:
                t_load += round_load_s(r)
            assert eng.fully_loaded
        t0 = time.perf_counter()
        logits = eng.prefill(batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        prefill_wall = time.perf_counter() - t0
        ttft = t_load + prefill_wall
        toks = [tok]
        for i in range(1, n_tokens):
            if overlap:
                # background fill: one round rides alongside each decode
                # step (its time is on the load channel, not the TTFT path)
                for r in fill:
                    break
                eng.maybe_switch_strategy(request_rate=1.0)
            tok = jnp.argmax(eng.decode(tok), -1).astype(jnp.int32)
            toks.append(tok)
        while overlap and not eng.fully_loaded:
            next(fill)
        if overlap:
            eng.maybe_switch_strategy(request_rate=1.0)
        return ttft, np.asarray(jnp.stack(toks, axis=1)), eng

    ttft_ov, toks_ov, eng_ov = run_engine(overlap=True)
    ttft_ser, toks_ser, eng_ser = run_engine(overlap=False)

    # the paper's correctness invariant: serving mid-load changes NOTHING
    np.testing.assert_array_equal(toks_ov, toks_ser)
    cs = eng_ov.compile_stats()
    if cs["decode_compiles"] >= 0:
        assert cs["decode_compiles"] == 1, (
            f"decode compiled {cs['decode_compiles']}x across the strategy "
            "switch (must be 1)")
    assert eng_ov.strategy == "single" and eng_ov.fully_loaded
    assert ttft_ov < ttft_ser, (
        f"overlapped TTFT {ttft_ov * 1e3:.1f}ms not better than "
        f"load-then-serve {ttft_ser * 1e3:.1f}ms")

    stats = eng_ov.cold_start_stats()
    emit("coldstart_overlapped_ttft", ttft_ov * 1e6,
         f"ready_after={stats['round_bytes'][0]}B_of_"
         f"{stats['total_bytes']}B rounds={stats['n_rounds']}")
    emit("coldstart_load_then_serve_ttft", ttft_ser * 1e6,
         f"speedup={ttft_ser / ttft_ov:.2f}x tokens_identical=True "
         f"decode_compiles={cs['decode_compiles']}")

    path = "BENCH_coldstart.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"arch": cfg.name, "n_layers": n_layers,
                   "n_devices": n_devices, "n_tokens": n_tokens,
                   "small": small},
        "ts": time.time(),
        "overlapped_ttft_s": ttft_ov,
        "load_then_serve_ttft_s": ttft_ser,
        "speedup": ttft_ser / ttft_ov,
        "tokens_identical": True,
        "decode_compiles": cs["decode_compiles"],
        "time_to_ready_wall_s": stats["time_to_ready"],
        "time_to_fully_loaded_wall_s": stats["time_to_fully_loaded"],
        "loaded_bytes": stats["loaded_bytes"],
        "total_bytes": stats["total_bytes"],
    })
    print(f"# wrote {path} ({n} entries)")


def bench_fleet(small: bool = False):
    """Multi-model fleet scheduling: SLO-aware vs least-loaded dispatch.

    Two model pools ("chat" / "code") over SHARED base params, two servers
    each plus a per-pool autoscaler, replaying a bursty multi-model trace
    that mixes long adapter-tuned requests with a wave of short
    tight-deadline base requests — the regime where *which server gets
    the request* decides TTFT: least-loaded happily queues a short
    request behind a long merged-LoRA epoch (the batch must drain before
    the adapter can switch), while SLO-aware dispatch prices that drain
    stall, the cold-start progress of warming servers, and the in-flight
    decode load, and routes around it (deadline-priority picks the most
    urgent queued request first).

    Runs the SAME trace through three fleets differing only in the
    injected ``DispatchPolicy`` and asserts SLO-aware p99 TTFT strictly
    beats least-loaded (the tentpole claim); adapter-affine rides along
    as the third point.  Appends ``BENCH_fleet.json`` keyed by
    commit+config.
    """
    from repro.cluster import (AdapterAffine, Autoscaler, AutoscalerConfig,
                               ClusterConfig, Fleet, LeastLoaded, PoolSpec,
                               SloAware, burst_wave_trace, merge_traces,
                               poisson_trace)
    from repro.lora.adapters import init_lora, merge_lora, randomize_lora
    from repro.models import transformer as T

    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    adapters = {}
    for name in ("a", "b"):
        lora = randomize_lora(jax.random.fold_in(jax.random.PRNGKey(7),
                                                 ord(name)),
                              init_lora(jax.random.PRNGKey(7), cfg, rank=4))
        adapters[name] = merge_lora(params, lora)

    n_short = 6 if small else 12
    long_toks = 10 if small else 18
    horizon = 1.2 if small else 2.5
    ccfg = ClusterConfig(n_devices=2, n_slots=4, epoch_budget=4)

    def pool_trace(pool: str, seed: int):
        # adapter "b": long generations; adapter "a": short, tight TTFT
        # deadline.  Least-loaded interleaves both classes across both
        # servers, so every wave admission crosses the epoch barrier
        # behind a long "b" batch; SLO-aware prices that drain and
        # de-facto partitions the adapters across the pool.
        longs = poisson_trace(1.2, horizon, seed=seed,
                              max_new_tokens=long_toks, adapters=("b",),
                              adapter_prob=1.0, model=pool,
                              ttft_deadline_s=1.5)
        shorts = burst_wave_trace(n_short, base_rate=2.0, wave_rate=16.0,
                                  wave_at=0.4, wave_len=0.8, seed=seed + 1,
                                  max_new_tokens=4, adapters=("a",),
                                  adapter_prob=1.0, model=pool,
                                  ttft_deadline_s=0.4)
        return merge_traces(longs, shorts)

    trace = merge_traces(pool_trace("chat", 0), pool_trace("code", 10))

    def run_fleet(make_dispatch_policy):
        pools = {
            name: PoolSpec(
                cfg, params, n_servers=2, ccfg=ccfg,
                adapter_params=dict(adapters),
                dispatch=make_dispatch_policy(),
                autoscaler=Autoscaler(AutoscalerConfig(
                    target_queue_per_server=6.0, ttft_slo_s=0.6,
                    max_servers=3, scale_up_cooldown_ticks=5)))
            for name in ("chat", "code")}
        fleet = Fleet(pools)
        t0 = time.perf_counter()
        done = fleet.run(trace)
        wall = time.perf_counter() - t0
        assert len(done) == len(trace), (len(done), len(trace))
        return fleet.metrics.summary(), fleet.metrics.summary_by_model(), \
            wall

    # deterministic scoring: pin the per-step cost to the logical tick so
    # the comparison is replayable (the default policy consults the
    # measured predicted_step_cost_s hook instead)
    slo = lambda: SloAware(step_cost_s=ccfg.tick_s)
    policies = {
        "least_loaded": LeastLoaded,
        "slo_aware": slo,
        "adapter_affine": lambda: AdapterAffine(slo=slo()),
    }
    results = {}
    for name, mk in policies.items():
        s, by_model, wall = run_fleet(mk)
        results[name] = s
        emit(f"fleet_{name}_ttft_p99", s["ttft_p99"] * 1e6,
             f"p50={s['ttft_p50']:.3f}s mean={s['ttft_mean']:.3f}s "
             f"completed={s['n_completed']:.0f} wall={wall:.1f}s")
        for model, ms in by_model.items():
            emit(f"fleet_{name}_{model}_ttft_p99", ms["ttft_p99"] * 1e6,
                 f"n={ms['n_completed']:.0f}")
    ll, sa = results["least_loaded"], results["slo_aware"]
    assert sa["ttft_p99"] < ll["ttft_p99"], (
        f"SLO-aware p99 TTFT {sa['ttft_p99']:.3f}s not better than "
        f"least-loaded {ll['ttft_p99']:.3f}s on the bursty trace")
    emit("fleet_slo_vs_least_loaded", 0.0,
         f"p99_cut={100 * (1 - sa['ttft_p99'] / ll['ttft_p99']):.1f}% "
         f"mean_cut={100 * (1 - sa['ttft_mean'] / ll['ttft_mean']):.1f}%")

    path = "BENCH_fleet.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"arch": cfg.name, "pools": 2, "n_short": n_short,
                   "long_toks": long_toks, "horizon": horizon,
                   "small": small},
        "ts": time.time(),
        "n_requests": len(trace),
        **{f"{name}_ttft_p99_s": results[name]["ttft_p99"]
           for name in policies},
        **{f"{name}_ttft_mean_s": results[name]["ttft_mean"]
           for name in policies},
        "slo_p99_cut_vs_least_loaded":
            1 - sa["ttft_p99"] / ll["ttft_p99"],
    })
    print(f"# wrote {path} ({n} entries)")


def _synth_azure_day_csv(path: str, *, n_functions: int, total: int,
                         n_minutes: int = 1440, seed: int = 0) -> int:
    """Write a synthetic Azure-Functions-shape CSV (one row per function,
    per-minute invocation counts over a day) whose counts sum to ~``total``.

    Shape matches the public dataset's findings: heavy-tailed per-function
    popularity (lognormal weights) and a diurnal curve — near-silent night,
    morning ramp, two daytime peaks — so the replay exercises both the
    dense daytime regime and the quiescent-gap jumps of the event engine.
    Deterministic in ``seed``.  Returns the written total invocation count.
    """
    import csv

    rng = np.random.default_rng(seed)
    day = (np.arange(n_minutes) + 0.5) / n_minutes
    gauss = lambda mu, sig: np.exp(-0.5 * ((day - mu) / sig) ** 2)
    shape = gauss(0.42, 0.09) + 0.85 * gauss(0.78, 0.11) \
        + 0.25 * gauss(0.60, 0.22)
    weights = rng.lognormal(0.0, 1.0, size=n_functions)
    jitter = rng.lognormal(0.0, 0.3, size=(n_functions, n_minutes))
    raw = weights[:, None] * shape[None, :] * jitter
    counts = np.rint(raw * (total / raw.sum())).astype(int)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["HashOwner", "HashApp", "HashFunction", "Trigger"]
                   + [str(m) for m in range(1, n_minutes + 1)])
        for fi in range(n_functions):
            w.writerow([f"owner{fi:04d}", f"app{fi:04d}", f"fn{fi:04d}",
                        "http"] + counts[fi].tolist())
    return int(counts.sum())


def bench_azure_day(small: bool = False):
    """Full-day Azure-shape trace replay through the event engine.

    The tentpole gate for the discrete-event refactor: synthesize a
    deterministic day of Azure-Functions-shape arrivals (~10⁶ full /
    ~5·10⁴ small), stream it through ``ClusterRouter.run`` (never
    materialized — ``iter_azure_trace`` generates minute-by-minute) over
    modeled ``SimServer`` backends, and demand the whole day replays in
    under 5 minutes of CPU wall time.  Everything above the server —
    dispatch, autoscaler, event engine, metrics — is the real code; only
    token generation is modeled (see cluster/simserver.py).

    Appends TTFT percentile curves and SLO attainment to
    ``BENCH_fleet.json`` keyed by commit+config.  ``--small`` additionally
    replays the same trace through the dense tick engine and reports the
    event-engine speedup (small only: the tick oracle polls every tick of
    the day, which at full scale is exactly the cost this refactor
    removes).
    """
    import tempfile

    from repro.cluster import (Autoscaler, AutoscalerConfig, ClusterConfig,
                               ClusterMetrics, ClusterRouter, LeastLoaded,
                               iter_azure_trace, sim_server_factory)

    total = 50_000 if small else 1_000_000
    n_functions = 16 if small else 64
    minute_s = 3.0                  # time-compress: 1440 min day -> 4320 s
    ccfg = ClusterConfig(n_devices=1, n_slots=16, tick_s=0.05)

    def replay(csv_path: str, engine: str):
        router = ClusterRouter(
            None, None, n_servers=2, ccfg=ccfg,
            autoscaler=Autoscaler(AutoscalerConfig(
                target_queue_per_server=8.0, ttft_slo_s=0.6,
                max_servers=24, min_servers=2, scale_up_cooldown_ticks=3,
                max_warming=4, idle_seconds_before_retire=10.0)),
            dispatch=LeastLoaded(), metrics=ClusterMetrics(),
            server_factory=sim_server_factory(),
            materialize_prompts=False)
        trace = iter_azure_trace(csv_path, minute_s=minute_s,
                                 ttft_deadline_s=0.5, seed=1)
        t0 = time.perf_counter()
        router.run(trace, max_ticks=200_000, engine=engine,
                   collect_finished=False)
        return router, time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        csv_path = os.path.join(td, "azure_day.csv")
        written = _synth_azure_day_csv(csv_path, n_functions=n_functions,
                                       total=total)
        router, wall = replay(csv_path, "event")
        m = router.metrics
        s = m.summary()
        curve = m.ttft_curve()
        slo_att, slo_n = m.slo_stats()
        n_req = int(s["n_requests"])
        emit("azure_day_replay", wall * 1e6,
             f"n={n_req} completed={s['n_completed']:.0f} "
             f"ttft_p50={curve['ttft_p50']:.3f}s "
             f"ttft_p99={curve['ttft_p99']:.3f}s slo={slo_att:.4f} "
             f"gpu_s={s['gpu_seconds']:.0f}")
        assert s["n_completed"] == n_req, (s["n_completed"], n_req)
        if not small:
            assert n_req >= 990_000, f"day synthesized only {n_req} arrivals"
            assert wall < 300.0, (
                f"full-day replay took {wall:.1f}s (gate: < 300 s CPU)")
        tick_wall = None
        if small:
            router_t, tick_wall = replay(csv_path, "tick")
            st = router_t.metrics.summary()
            assert st["n_completed"] == s["n_completed"], (
                st["n_completed"], s["n_completed"])
            assert abs(st["ttft_p99"] - s["ttft_p99"]) < 1e-9, (
                st["ttft_p99"], s["ttft_p99"])
            emit("azure_day_tick_oracle", tick_wall * 1e6,
                 f"event_speedup={tick_wall / max(wall, 1e-9):.2f}x")

    path = "BENCH_fleet.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"bench": "azure_day", "n_functions": n_functions,
                   "total": total, "minute_s": minute_s,
                   "n_slots": ccfg.n_slots, "small": small},
        "ts": time.time(),
        "n_requests": n_req,
        "n_completed": int(s["n_completed"]),
        "wall_s": wall,
        "tick_wall_s": tick_wall,
        "slo_attainment": slo_att,
        "slo_n": int(slo_n),
        "gpu_seconds": s["gpu_seconds"],
        **curve,
    })
    print(f"# wrote {path} ({n} entries)")


def bench_kernels():
    """Pallas kernel wall-clock (interpret mode on CPU; TPU target):
    flash attention and the fused LoRA merge."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    o = ops.flash_attention(q, k, v)  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        o = ops.flash_attention(q, k, v)
    jax.block_until_ready(o)
    emit("kernel_flash_attn_256_interp", (time.perf_counter() - t0) / 3 * 1e6,
         "interpret-mode (TPU target)")
    W = jax.random.normal(key, (4, 256, 256), jnp.float32)
    A = jax.random.normal(key, (4, 256, 8), jnp.float32)
    Bm = jax.random.normal(key, (4, 8, 256), jnp.float32)
    o = ops.lora_merge(W, A, Bm, 0.5)
    t0 = time.perf_counter()
    for _ in range(3):
        o = ops.lora_merge(W, A, Bm, 0.5)
    jax.block_until_ready(o)
    emit("kernel_lora_merge_interp", (time.perf_counter() - t0) / 3 * 1e6)


def bench_chaos(small: bool = False):
    """Elastic repartition vs full migration under partial crashes, plus
    seeded chaos-schedule replay (functional).

    Headline: a device of a 4-device server dies mid-decode.  Repartition
    re-splits the pipeline over the survivors in place — reload only the
    dead device's layers, re-lay live KV in one donated scatter, requests
    never leave the server — vs FULL migration, which abandons the warm
    server: drain with snapshots, cold-start a fresh server (pipelined
    load + first-use compiles, the honest price of standing up capacity),
    import, resume.  Post-crash TTFT = wall-clock from the crash until
    every victim has its next token.  Asserts repartition is strictly
    faster AND both paths finish with identical greedy continuations with
    ZERO re-prefilled tokens (batcher prefill counters pinned).

    Also replays a seeded ``ChaosSchedule`` (crash/partial_crash/rejoin)
    twice on the modeled fleet — same seed must reproduce identical token
    streams under BOTH the tick and event engines — and once against real
    servers with ``partial_recovery="repartition"``, asserting every
    request survives the fault sequence token-exact with
    ``reprefill_tokens == 0``.  Appends to ``BENCH_chaos.json`` (the CI
    fast-lane smoke runs this with ``--small``).
    """
    from repro.cluster import (Autoscaler, AutoscalerConfig, ChaosEvent,
                               ClusterConfig, ClusterRouter, ClusterServer,
                               LeastLoaded, SimProfile, poisson_trace,
                               random_chaos, sim_server_factory)
    from repro.models import transformer as T
    from repro.serving.engine import ServeRequest, quantized_greedy

    n_layers, n_devices = 4, 4        # need partial KV loss on one device
    n_victims = 2 if small else 3
    reps = 2 if small else 3
    prompt_len, max_new = 48, 16
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=prompt_len)
               for _ in range(n_victims)]
    ccfg = ClusterConfig(n_devices=n_devices, n_slots=4,
                         partial_recovery="repartition")

    def make_victim_server():
        # victims submitted BEFORE ready so they admit the same tick the
        # chain becomes viable — KV ownership still spans devices
        s = ClusterServer(0, cfg, params, ccfg)
        vs = [ServeRequest(i, p, max_new_tokens=max_new)
              for i, p in enumerate(prompts)]
        for r in vs:
            s.submit(r)
        while s.state == "loading":
            s.tick(0.0)
        cands = sorted(
            (d for d in range(n_devices)
             if 0 < sum(s.engine.lost_state_layers([d])) < cfg.n_layers),
            key=lambda d: sum(s.engine.lost_state_layers([d])))
        assert cands, "no partial-loss device — chain collapsed early"
        return s, vs, cands[0]

    def next_token_wait(server, vs, before):
        now = 1.0
        while not all(len(r.generated) > before[r.rid] or r.done
                      for r in vs):
            server.tick(now)
            now += ccfg.tick_s

    def repartition_window(s, vs, dev):
        before = {r.rid: len(r.generated) for r in vs}
        n_pref = s.srv.batcher.n_prefill_reqs
        t0 = time.perf_counter()
        s.crash([dev])
        next_token_wait(s, vs, before)
        dt = time.perf_counter() - t0
        assert s.srv.batcher.n_prefill_reqs == n_pref, \
            "repartition re-prefilled — zero-re-prefill invariant broken"
        return dt

    def migration_window(s, vs):
        before = {r.rid: len(r.generated) for r in vs}
        t0 = time.perf_counter()
        drained = s.crash()           # whole-server loss: snapshots out
        surv = ClusterServer(1, cfg, params, ccfg)
        while surv.state == "loading":
            surv.tick(0.0)
        for r in drained:
            assert surv.srv.admit_with_state(r), "import refused"
        next_token_wait(surv, vs, before)
        dt = time.perf_counter() - t0
        assert surv.srv.batcher.n_prefill_reqs == 0
        return dt, surv

    # untimed warmup pair: first-use eager-dispatch caches (the relay's
    # reconstruct path is un-jitted) land outside the timed windows
    s, vs, dev = make_victim_server()
    repartition_window(s, vs, dev)
    s, vs, _ = make_victim_server()
    migration_window(s, vs)

    t_rep, t_mig = [], []
    for _ in range(reps):
        s_r, vs_r, dev = make_victim_server()
        lost = sum(s_r.engine.lost_state_layers([dev]))
        t_rep.append(repartition_window(s_r, vs_r, dev))
        s_m, vs_m, _ = make_victim_server()
        dt, surv = migration_window(s_m, vs_m)
        t_mig.append(dt)
    t_r, t_m = float(np.median(t_rep)), float(np.median(t_mig))
    relayed = dict(s_r.last_recovery)
    # equivalence oracle: the last rep's two paths ride to completion and
    # must agree token-for-token (the bit-identical-streams claim)
    now = 2.0
    while any(not r.done for r in vs_r):
        s_r.tick(now)
        now += ccfg.tick_s
    while any(not r.done for r in vs_m):
        surv.tick(now)
        now += ccfg.tick_s
    for a, b in zip(vs_r, vs_m):
        assert a.generated == b.generated, (a.rid, a.generated, b.generated)
    assert t_r < t_m, (
        f"post-crash TTFT regression: repartition {t_r * 1e3:.1f}ms is not "
        f"faster than full migration {t_m * 1e3:.1f}ms")
    emit("chaos_repartition_post_crash_ttft", t_r * 1e6,
         f"lost_layers={lost} relayed={n_victims} reprefilled_tokens=0 "
         f"speedup={t_m / t_r:.2f}x")
    emit("chaos_full_migration_post_crash_ttft", t_m * 1e6,
         f"migrated={n_victims} cold_survivor_included")

    # seeded chaos replay on the modeled fleet: same seed => identical
    # replay, and the tick and event engines execute the schedule the same
    chaos_seed = 11
    chaos = random_chaos(2 if small else 4, horizon=4.0, n_servers=2,
                         seed=chaos_seed, rejoin_delay_s=1.0)
    again = random_chaos(2 if small else 4, horizon=4.0, n_servers=2,
                         seed=chaos_seed, rejoin_delay_s=1.0)
    assert [(e.time, e.kind, e.server, e.devices) for e in chaos] == \
        [(e.time, e.kind, e.server, e.devices) for e in again], \
        "random_chaos is not deterministic by seed"
    sim_trace = poisson_trace(30.0, 2.0, seed=7, max_new_tokens=4)

    def sim_run(engine):
        r = ClusterRouter(
            None, None, n_servers=2,
            ccfg=ClusterConfig(n_devices=1, n_slots=4),
            autoscaler=Autoscaler(AutoscalerConfig(
                target_queue_per_server=4.0, max_servers=4, min_servers=1,
                idle_seconds_before_retire=1.0)),
            dispatch=LeastLoaded(),
            server_factory=sim_server_factory(SimProfile(ready_ticks=2,
                                                         full_ticks=6)),
            materialize_prompts=False)
        t0 = time.perf_counter()
        done = r.run(list(sim_trace), engine=engine, chaos=chaos)
        return r, done, time.perf_counter() - t0

    runs = {name: sim_run(eng) for name, eng in
            (("event", "event"), ("tick", "tick"), ("event2", "event"))}
    streams = {name: {r.rid: tuple(r.generated) for r in done}
               for name, (_, done, _) in runs.items()}
    assert streams["event"] == streams["tick"] == streams["event2"], \
        "chaos replay diverged across engines / identical seeds"
    s_evt = runs["event"][0].metrics.summary()
    s_tick = runs["tick"][0].metrics.summary()
    for k in ("n_completed", "gpu_seconds", "degraded_seconds",
              "recovery_mode_repartition", "recovery_reprefill_tokens"):
        assert abs(s_evt[k] - s_tick[k]) < 1e-9, (k, s_evt[k], s_tick[k])
    emit("chaos_sim_replay", runs["event"][2] * 1e6,
         f"n_events={len(chaos)} n_reqs={len(sim_trace)} "
         f"tick==event seed={chaos_seed}")

    # real servers under a chaos schedule: a partial crash + device rejoin
    # mid-trace, elastic repartition recovery — every request survives the
    # fault sequence token-exact, with zero re-prefilled tokens
    real_trace = poisson_trace(8.0, 0.7, seed=3, max_new_tokens=4)
    real_chaos = [ChaosEvent(0.313, "partial_crash", 0, (1,)),
                  ChaosEvent(0.913, "rejoin", 0, (1,))]
    router = ClusterRouter(cfg, params, n_servers=1, ccfg=ccfg)
    t0 = time.perf_counter()
    done = router.run(list(real_trace), chaos=real_chaos)
    t_real = time.perf_counter() - t0
    assert len(done) == len(real_trace)
    summ = router.metrics.summary()
    assert summ["recovery_reprefill_tokens"] == 0.0

    def _solo(prompt, n):
        lg, cache = T.forward(cfg, params,
                              {"tokens": jnp.asarray(prompt)[None]},
                              mode="prefill", max_len=96)
        toks = [int(quantized_greedy(lg)[0])]
        for _ in range(n - 1):
            lg, cache = T.decode_step(
                cfg, params, {"tokens": jnp.asarray([toks[-1]], jnp.int32)},
                cache)
            toks.append(int(quantized_greedy(lg)[0]))
        return toks

    for r in done:
        assert r.generated == _solo(r.tokens, len(r.generated)), r.rid
    emit("chaos_real_router_replay", t_real * 1e6,
         f"reqs={len(done)} reprefill_tokens=0 "
         f"mode_repartition={summ['recovery_mode_repartition']:.0f} "
         f"degraded_s={summ['degraded_seconds']:.3f}")

    path = "BENCH_chaos.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"arch": cfg.name, "n_layers": n_layers,
                   "n_devices": n_devices, "n_victims": n_victims,
                   "prompt_len": prompt_len, "chaos_seed": chaos_seed,
                   "small": small},
        "ts": time.time(),
        "repartition_post_crash_ttft_s": t_r,
        "full_migration_post_crash_ttft_s": t_m,
        "speedup": t_m / t_r,
        "lost_layers": int(lost),
        "relay": relayed,
        "reprefill_tokens": 0,
        "sim_replay": {"n_chaos_events": len(chaos),
                       "n_completed": int(s_evt["n_completed"]),
                       "tick_event_equal": True},
        "real_replay": {
            "n_reqs": len(done),
            "mode_repartition": summ["recovery_mode_repartition"],
            "degraded_seconds": summ["degraded_seconds"],
        },
    })
    print(f"# wrote {path} ({n} entries)")


def bench_multicast(small: bool = False):
    """Peer-to-peer multicast scale-out vs host-only cold starts, with a
    seeded mid-propagation source crash (modeled fleet).

    Headline: an N-server burst spawn.  Host-only, every server reads its
    own model copy from DRAM and the streams contend for ``host_agg_bw``
    (throttled to 2 host links so contention bites at small N, priced via
    ``host_bw_effective``).  Under tree multicast one root reads from host
    at full link speed and every receiver relays segments onward over
    ``ici_bw`` — asserts burst TTFT and fill makespan strictly beat
    host-only and that aggregate host traffic stays ~one model copy
    instead of N.

    Robustness: the propagation root is crashed mid-transfer
    (``source_crash``).  Survivors re-root onto the warmest holders,
    resume from their last fully-received segment, and bootstrap the
    never-seeded tail from host — asserts every surviving spawn completes
    its copy, zero tokens are re-prefilled, the token streams are
    bit-identical to a crash-free run, and the same chaos script replays
    token-exactly under the tick and event engines.  Appends to
    ``BENCH_multicast.json`` (the CI fast-lane smoke runs ``--small``).
    """
    from dataclasses import replace

    from repro.cluster import (Arrival, ChaosEvent, ClusterConfig,
                               ClusterRouter, MulticastConfig, SimProfile,
                               sim_server_factory)

    n_spawn = 4 if small else 8
    n_segments, bytes_total = 8, 1 << 30
    host_agg_links = 1            # host_agg_bw = 1 host link: N streams
    # share one link's worth of DRAM read (contention bites at N >= 4)
    hw = replace(GPU_PAPER, host_agg_bw=host_agg_links * GPU_PAPER.host_link_bw)
    prof = SimProfile(ready_ticks=2, full_ticks=10, bytes_total=bytes_total,
                      n_segments=n_segments)

    def build(topology):
        ccfg = ClusterConfig(
            n_devices=1, n_slots=4, tick_s=0.05,
            multicast=MulticastConfig(topology=topology, hw=hw))
        return ClusterRouter(None, None, n_servers=n_spawn, ccfg=ccfg,
                             server_factory=sim_server_factory(prof),
                             materialize_prompts=False)

    def makespan(router):
        fulls = [r["time_to_fully_loaded"]
                 for r in router.metrics.coldstart.values()
                 if r.get("time_to_fully_loaded") is not None]
        return max(fulls, default=0.0)

    # -- burst TTFT: requests land while every server is still cold; the
    # sentinel arrival keeps the replay alive until the fills complete
    # (run() otherwise returns the moment the burst drains, mid-fill)
    burst = [Arrival(0.001 * i, prompt_len=8, max_new_tokens=4)
             for i in range(2 * n_spawn)]
    sentinel = [Arrival(5.0, prompt_len=8, max_new_tokens=1)]
    stats = {}
    for topo in ("tree", "host"):
        r = build(topo)
        t0 = time.perf_counter()
        done = r.run(burst + sentinel, engine="event")
        wall = time.perf_counter() - t0
        assert len(done) == len(burst) + 1, topo
        assert all(s.fully_loaded for s in r.servers), topo
        summ = r.metrics.summary()
        stats[topo] = {"ttft_mean": summ["ttft_mean"],
                       "host_bytes": summ["multicast_host_bytes"],
                       "makespan": makespan(r)}
        emit(f"multicast_burst_{topo}_n{n_spawn}", wall * 1e6,
             f"ttft_mean={summ['ttft_mean']:.3f}s "
             f"fill_makespan={makespan(r):.3f}s "
             f"host_bytes={summ['multicast_host_bytes']:.2e}")
    mc, ho = stats["tree"], stats["host"]
    assert mc["ttft_mean"] < ho["ttft_mean"], (
        f"multicast burst TTFT {mc['ttft_mean']:.3f}s is not strictly "
        f"faster than host-only {ho['ttft_mean']:.3f}s at N={n_spawn}")
    assert mc["makespan"] < ho["makespan"], (mc["makespan"], ho["makespan"])
    # ~one host read of aggregate traffic vs N full copies
    assert mc["host_bytes"] <= 1.25 * bytes_total, mc["host_bytes"]
    assert ho["host_bytes"] >= 0.99 * n_spawn * bytes_total, ho["host_bytes"]
    emit(f"multicast_ttft_speedup_n{n_spawn}", 0.0,
         f"{ho['ttft_mean'] / max(mc['ttft_mean'], 1e-9):.2f}x "
         f"host_read_ratio={mc['host_bytes'] / bytes_total:.2f}")

    # -- mid-propagation source crash: kill the root while it is sourcing
    # peer transfers; arrivals land after the fills so completions isolate
    # the load-stage fault (zero re-prefill is structural AND asserted)
    chaos_t = 0.0685              # off-grid, ~2 ticks into propagation
    chaos = [ChaosEvent(chaos_t, "source_crash", 0)]
    late = [Arrival(2.0 + 0.01 * i, prompt_len=8, max_new_tokens=4)
            for i in range(2 * n_spawn)]
    runs = {}
    for name, eng in (("event", "event"), ("tick", "tick"),
                      ("event2", "event")):
        r = build("tree")
        t0 = time.perf_counter()
        done = r.run(late + sentinel, chaos=list(chaos), engine=eng)
        runs[name] = (r, done, time.perf_counter() - t0)
    streams = {name: {q.rid: tuple(q.generated) for q in done}
               for name, (_, done, _) in runs.items()}
    assert streams["event"] == streams["tick"] == streams["event2"], \
        "source-crash replay diverged across engines / identical scripts"
    r_ref = build("tree")
    ref = r_ref.run(late + sentinel, engine="event")
    assert streams["event"] == {q.rid: tuple(q.generated) for q in ref}, \
        "token streams changed vs the crash-free run"
    r_evt, done_evt, wall_evt = runs["event"]
    s_evt = runs["event"][0].metrics.summary()
    s_tick = runs["tick"][0].metrics.summary()
    for k in ("n_completed", "multicast_reroots", "multicast_host_bytes",
              "multicast_host_fallbacks", "recovery_reprefill_tokens"):
        assert abs(s_evt[k] - s_tick[k]) < 1e-9, (k, s_evt[k], s_tick[k])
    assert s_evt["n_completed"] == len(late) + 1
    assert s_evt["multicast_reroots"] >= 1, \
        "the crash did not abort any in-flight transfer (bad chaos_t?)"
    assert s_evt["recovery_reprefill_tokens"] == 0.0
    # every SURVIVING spawn completed its copy despite losing the root
    assert all(s.fully_loaded for s in r_evt.servers
               if s.state not in ("down", "retired"))
    # resume-not-restart: the re-pulled tail stays bounded (<= ~2 copies
    # of host traffic total; restart-from-zero would approach N copies)
    assert s_evt["multicast_host_bytes"] <= 2.0 * bytes_total
    emit(f"multicast_source_crash_n{n_spawn}", wall_evt * 1e6,
         f"reroots={s_evt['multicast_reroots']:.0f} "
         f"host_fallbacks={s_evt['multicast_host_fallbacks']:.0f} "
         f"retries={s_evt['multicast_retries']:.0f} "
         f"reprefill_tokens=0 tick==event")

    path = "BENCH_multicast.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"bench": "multicast", "n_spawn": n_spawn,
                   "n_segments": n_segments, "bytes_total": bytes_total,
                   "topology": "tree", "host_agg_links": host_agg_links,
                   "chaos_t": chaos_t, "small": small},
        "ts": time.time(),
        "n_spawn": n_spawn,
        "mc_ttft_mean_s": mc["ttft_mean"],
        "host_ttft_mean_s": ho["ttft_mean"],
        "ttft_speedup": ho["ttft_mean"] / max(mc["ttft_mean"], 1e-9),
        "mc_fill_makespan_s": mc["makespan"],
        "host_fill_makespan_s": ho["makespan"],
        "mc_host_bytes": mc["host_bytes"],
        "host_only_host_bytes": ho["host_bytes"],
        "host_read_ratio": mc["host_bytes"] / bytes_total,
        "crash": {
            "reroots": s_evt["multicast_reroots"],
            "retries": s_evt["multicast_retries"],
            "host_fallbacks": s_evt["multicast_host_fallbacks"],
            "host_bytes": s_evt["multicast_host_bytes"],
            "reprefill_tokens": 0,
            "n_completed": int(s_evt["n_completed"]),
            "tick_event_equal": True,
        },
    })
    print(f"# wrote {path} ({n} entries)")


def bench_prefix(small: bool = False):
    """Fleet state tier: cross-request prefix cache + spill/resurrect.

    Part 1 (real engine, CPU): serve a population of prompts sharing a
    long prefix through a ``ContinuousBatcher`` with and without a
    ``PrefixCache`` attached.  Asserts the cached run's token streams are
    bit-identical to cold prefill, post-warmup prefill tokens drop to
    <= 2%% of the no-cache run (only the per-request suffix is walked),
    and the compile guard shows zero new decode/prefill compiles — the
    import rides the existing donated scatter + fused decode.

    Part 2 (real engine, CPU): wall-clock TTFT of a "resurrected" spawn —
    a fresh batcher whose cache was seeded from another server's
    ``export_entries`` bundle (what an idle retirement spills to the
    ``StateTier``) — vs a genuinely cold spawn serving the same prompt.
    Asserts resurrect strictly beats cold, and prices the bundle pull
    with the modeled ``state_resurrect_time``.

    Part 3 (modeled fleet): a two-wave repeated-prefix trace with an idle
    gap long enough for the autoscaler to retire the fleet down to one
    server; wave 2's burst respawns it.  With a ``StateTier`` wired in,
    the retirement spills the prefix cache and the respawn resurrects it
    warm.  Asserts >= 1 resurrection, prefix hits in both waves, and that
    the tick and event engines replay streams and every state-tier
    summary key identically.  Appends to ``BENCH_prefix.json`` (the CI
    fast-lane smoke runs ``--small``).
    """
    from repro.models import transformer as T
    from repro.serving.engine import (ContinuousBatcher, ServeRequest,
                                      quantized_greedy)
    from repro.serving.prefix_cache import PrefixCache

    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2, head_dim=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pre_len, suf_len = 192, 2
    n_reqs = 4 if small else 8
    rng = np.random.default_rng(0)
    pre = rng.integers(0, 250, size=pre_len)
    prompts = [np.concatenate([pre, rng.integers(0, 250, size=suf_len)])
               for _ in range(n_reqs)]

    def batcher(cache=None):
        cb = ContinuousBatcher(cfg, params, n_slots=4, max_len=256,
                               sampler=quantized_greedy)
        if cache is not None:
            cb.attach_prefix_cache(cache)
        return cb

    def serve(cb, ps, n_new=4):
        out = []
        for i, p in enumerate(ps):
            r = ServeRequest(1000 + i, p, max_new_tokens=n_new)
            cb.admit(r)
            while cb.n_active:
                cb.step()
            out.append(tuple(r.generated))
        return out

    # -- part 1: prefill savings, bit-identity, compile guard --------------
    cb_cold = batcher()
    t0 = time.perf_counter()
    cold = serve(cb_cold, prompts)
    cold_wall = time.perf_counter() - t0
    cold_stats = cb_cold.hotpath_stats()

    pc = PrefixCache()
    cb_warm = batcher(cache=pc)
    warm_first = serve(cb_warm, prompts[:1])   # warmup: miss + deposit
    warmup_tokens = cb_warm.n_prefill_tokens
    t0 = time.perf_counter()
    warm_rest = serve(cb_warm, prompts[1:])
    warm_wall = time.perf_counter() - t0
    warm_stats = cb_warm.hotpath_stats()
    assert warm_first + warm_rest == cold, \
        "prefix-cache streams diverged from cold prefill"
    post_cache = warm_stats["n_prefill_tokens"] - warmup_tokens
    post_cold = (n_reqs - 1) * (pre_len + suf_len)
    ratio = post_cache / max(post_cold, 1)
    assert ratio <= 0.02, (
        f"post-warmup prefill tokens {post_cache} are "
        f"{100 * ratio:.1f}% of the no-cache run (gate: <= 2%)")
    for k in ("decode_compiles", "prefill_compiles"):
        assert warm_stats[k] <= cold_stats[k], (
            k, warm_stats[k], cold_stats[k],
            "prefix import triggered a fresh compile")
    assert warm_stats["prefix_hits"] == n_reqs - 1
    emit(f"prefix_serve_cached_n{n_reqs}", warm_wall * 1e6,
         f"prefill_tokens={post_cache}/{post_cold} "
         f"ratio={100 * ratio:.2f}% hits={warm_stats['prefix_hits']:.0f} "
         f"hit_tokens={warm_stats['prefix_hit_tokens']:.0f}")
    emit(f"prefix_serve_cold_n{n_reqs}", cold_wall * 1e6,
         f"speedup={cold_wall / max(warm_wall, 1e-9):.2f}x "
         f"streams_identical=True compiles_unchanged=True")

    # -- part 2: resurrect-from-spill TTFT vs cold spawn -------------------
    bundle = pc.export_entries()
    bundle_bytes = sum(e.nbytes for _, e in bundle)
    probe_prompt = np.concatenate([pre, rng.integers(0, 250, size=suf_len)])

    # pre-warm prompt: same length, guaranteed 0-token overlap with the
    # cached prefix, so timed admissions measure prefill/import work, not
    # tracing
    warm_prompt = np.full(pre_len + suf_len, (int(pre[0]) + 1) % 250,
                          np.int64)

    def ttft(cb, repeats=3):
        cb.admit(ServeRequest(1, warm_prompt, max_new_tokens=2))
        while cb.n_active:
            cb.step()
        best = float("inf")
        for i in range(repeats):
            r = ServeRequest(10 + i, probe_prompt, max_new_tokens=1)
            t0 = time.perf_counter()
            cb.admit(r)
            while not r.generated:
                cb.step()
            best = min(best, time.perf_counter() - t0)
            while cb.n_active:
                cb.step()
        return best

    cold_ttft = ttft(batcher())
    pc_res = PrefixCache()
    assert pc_res.import_entries(bundle) >= 1
    res_ttft = ttft(batcher(cache=pc_res))
    assert res_ttft < cold_ttft, (
        f"resurrect TTFT {res_ttft * 1e3:.1f}ms did not beat cold spawn "
        f"{cold_ttft * 1e3:.1f}ms")
    modeled_pull = sim.state_resurrect_time(bundle_bytes, GPU_PAPER)
    emit("prefix_resurrect_ttft", res_ttft * 1e6,
         f"cold={cold_ttft * 1e3:.1f}ms speedup="
         f"{cold_ttft / max(res_ttft, 1e-9):.2f}x "
         f"bundle={bundle_bytes / 1e6:.1f}MB "
         f"modeled_pull={modeled_pull:.3f}s")

    # -- part 3: modeled fleet spill/resurrect, tick == event --------------
    import dataclasses
    import types

    from repro.cluster import (Autoscaler, AutoscalerConfig, ClusterConfig,
                               ClusterMetrics, ClusterRouter, LogicalClock,
                               SimProfile, SloAware, StateTier,
                               repeated_prefix_trace, sim_server_factory)

    n_w1 = 8 if small else 16
    n_w2 = 6 if small else 12

    def fleet_run(engine):
        ccfg = ClusterConfig(tick_s=0.05, n_slots=4,
                             prefix_cache_bytes=64 << 20)
        auto = Autoscaler(AutoscalerConfig(min_servers=1, max_servers=2,
                                           idle_ticks_before_retire=20))
        # gaps sit OFF the tick grid (see repeated_prefix_trace docstring)
        wave1 = repeated_prefix_trace(n_w1, prefix_len=24, suffix_len=4,
                                      gap_s=0.021, seed=0)
        wave2 = repeated_prefix_trace(n_w2, prefix_len=24, suffix_len=4,
                                      gap_s=0.011, seed=100)
        trace = wave1 + [dataclasses.replace(a, time=a.time + 8.003)
                         for a in wave2]
        mcfg = types.SimpleNamespace(vocab_size=250, name="m")
        r = ClusterRouter(mcfg, None, n_servers=2, ccfg=ccfg,
                          autoscaler=auto,
                          dispatch=SloAware(step_cost_s=0.05,
                                            prefix_bonus_s_per_token=0.001),
                          clock=LogicalClock(), metrics=ClusterMetrics(),
                          server_factory=sim_server_factory(SimProfile()),
                          state_tier=StateTier())
        done = r.run(trace, engine=engine)
        return {q.rid: tuple(q.generated) for q in done}, r.metrics.summary()

    t0 = time.perf_counter()
    runs = {name: fleet_run(eng) for name, eng in
            (("event", "event"), ("tick", "tick"), ("event2", "event"))}
    fleet_wall = time.perf_counter() - t0
    s_evt = runs["event"][1]
    assert runs["event"][0] == runs["tick"][0] == runs["event2"][0], \
        "state-tier fleet replay diverged across engines"
    for k in ("n_completed", "prefix_hits", "prefix_hit_tokens",
              "prefix_evictions", "spill_resurrections", "spilled_bytes"):
        assert abs(s_evt[k] - runs["tick"][1][k]) < 1e-9, \
            (k, s_evt[k], runs["tick"][1][k])
    assert s_evt["n_completed"] == n_w1 + n_w2
    assert s_evt["spill_resurrections"] >= 1, \
        "idle retirement never spilled / respawn never resurrected"
    assert s_evt["prefix_hits"] > 0
    emit(f"prefix_fleet_n{n_w1 + n_w2}", fleet_wall * 1e6,
         f"hits={s_evt['prefix_hits']:.0f} "
         f"hit_tokens={s_evt['prefix_hit_tokens']:.0f} "
         f"resurrections={s_evt['spill_resurrections']:.0f} "
         f"spilled_bytes={s_evt['spilled_bytes']:.0f} tick==event")

    path = "BENCH_prefix.json"
    n = append_keyed_entry(path, {
        "commit": _git_commit(),
        "config": {"bench": "prefix", "arch": cfg.name, "pre_len": pre_len,
                   "suf_len": suf_len, "n_reqs": n_reqs, "n_w1": n_w1,
                   "n_w2": n_w2, "small": small},
        "ts": time.time(),
        "prefill_tokens_nocache": int(post_cold),
        "prefill_tokens_cache": int(post_cache),
        "prefill_token_ratio": ratio,
        "tokens_identical": True,
        "prefix_hits": int(warm_stats["prefix_hits"]),
        "prefix_hit_tokens": int(warm_stats["prefix_hit_tokens"]),
        "decode_compiles": int(warm_stats["decode_compiles"]),
        "prefill_compiles": int(warm_stats["prefill_compiles"]),
        "cold_ttft_s": cold_ttft,
        "resurrect_ttft_s": res_ttft,
        "resurrect_speedup": cold_ttft / max(res_ttft, 1e-9),
        "bundle_bytes": int(bundle_bytes),
        "modeled_pull_s": modeled_pull,
        "fleet": {
            "n_completed": int(s_evt["n_completed"]),
            "prefix_hits": s_evt["prefix_hits"],
            "prefix_hit_tokens": s_evt["prefix_hit_tokens"],
            "spill_resurrections": s_evt["spill_resurrections"],
            "spilled_bytes": s_evt["spilled_bytes"],
            "tick_event_equal": True,
        },
    })
    print(f"# wrote {path} ({n} entries)")


# ---------------------------------------------------------------------------

BENCHES = [
    bench_ttft, bench_ttft_lora, bench_cold_start_breakdown,
    bench_breakdown_lora, bench_strategy_crossover, bench_scaling_shapes,
    bench_scaling_devices, bench_adapter_epochs, bench_recovery_loading,
    bench_recovery_inference, bench_engine_functional, bench_cluster_burst,
    bench_decode_hotpath, bench_recovery, bench_coldstart, bench_fleet,
    bench_azure_day, bench_chaos, bench_multicast, bench_prefix,
    bench_kernels,
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*",
                    help="bench function names to run (default: all)")
    ap.add_argument("--small", action="store_true",
                    help="reduced sizes for benches that support it "
                         "(CI fast-lane smoke)")
    ap.add_argument("--list", action="store_true",
                    help="print the bench registry (name, --small "
                         "support, one-line description) and exit")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.list:
        for b in BENCHES:
            doc = (inspect.getdoc(b) or "").split("\n")[0]
            small = ("--small"
                     if "small" in inspect.signature(b).parameters else "")
            print(f"{b.__name__:28s} {small:7s} {doc}")
        return
    sel = BENCHES
    if args.benches:
        by_name = {b.__name__: b for b in BENCHES}
        unknown = [n for n in args.benches if n not in by_name]
        if unknown:
            raise SystemExit(f"unknown benches {unknown}; "
                             f"available: {sorted(by_name)}")
        sel = [by_name[n] for n in args.benches]
    print("name,us_per_call,derived")
    for b in sel:
        if "small" in inspect.signature(b).parameters:
            b(small=args.small)
        else:
            b()


if __name__ == "__main__":
    main()
