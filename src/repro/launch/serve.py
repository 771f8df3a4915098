"""Serving launcher: PipeBoost cold start -> continuous-batched serving ->
strategy switch, with optional crash injection.

Single server (the seed path):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        [--devices 4] [--requests 8] [--crash-at 3] [--adapters 2]

Serverless cluster (router + autoscaler + cross-server crash re-routing):

    PYTHONPATH=src python -m repro.launch.serve --cluster \
        --servers 2 --requests 16 --crash-at 3

``--cluster`` replays a bursty arrival trace across N PipeBoost-backed
server replicas, optionally crashes one server after ``--crash-at``
completions (its in-flight requests re-route to survivors and it rejoins
via a fresh pipelined cold start), and prints TTFT/TBT percentiles, queue
depth, and GPU-seconds.  On the CPU the model is cut to a few layers at
toy width; on a TPU it runs at the configuration's full width.  Loading
is bookkeeping: the engine's load rounds record which segments each
logical device holds and move no bytes, because the parameters are made
whole on the device before serving starts.

See ``docs/ARCHITECTURE.md`` § "Launch".
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_arch
from repro.core.adapter_scheduler import EpochSchedulerPolicy
from repro.core.engine import PipeBoostEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.lora.adapters import init_lora, merge_lora, randomize_lora
from repro.models import transformer as T
from repro.serving.engine import ServeRequest, ServingEngine


def run_cluster(cfg, params, args):
    """Bursty trace -> router -> autoscaled PipeBoost servers; prints the
    TTFT/TBT percentile metrics the paper's cluster claims live on."""
    from repro.cluster import (Autoscaler, AutoscalerConfig, ClusterConfig,
                               ClusterRouter, WallClock, burst_wave_trace,
                               make_dispatch)
    key = jax.random.PRNGKey(0)
    adapter_params = {}
    for i in range(args.adapters):
        lora = randomize_lora(jax.random.fold_in(key, i),
                              init_lora(key, cfg, rank=4, name=f"lora{i}"))
        adapter_params[f"lora{i}"] = merge_lora(params, lora)
    trace = burst_wave_trace(args.requests, base_rate=2.0,
                             wave_rate=8.0 * max(args.servers, 1),
                             wave_at=0.5, wave_len=1.0, seed=args.seed,
                             max_new_tokens=args.new_tokens,
                             adapters=tuple(adapter_params))
    ccfg = ClusterConfig(n_devices=args.devices, n_slots=args.slots)
    scaler = Autoscaler(AutoscalerConfig(target_queue_per_server=args.slots,
                                         max_servers=args.max_servers,
                                         ttft_slo_s=1.0))
    # the same router/scheduler code runs logical ticks (default,
    # deterministic) or wall time (--wall-clock, real-slice mode): the
    # clock is injected, never branched on
    router = ClusterRouter(cfg, params, n_servers=args.servers, ccfg=ccfg,
                           autoscaler=scaler, adapter_params=adapter_params,
                           dispatch=make_dispatch(args.dispatch),
                           clock=WallClock() if args.wall_clock else None)
    t0 = time.perf_counter()
    crash = args.crash_at if args.crash_at >= 0 else None
    done = router.run(trace, crash_after_completions=crash,
                      crash_server_id=min(1, args.servers - 1),
                      rejoin_after_ticks=20 if crash is not None else None,
                      engine=args.engine)
    wall = time.perf_counter() - t0
    s = router.metrics.summary()
    print(f"cluster: {int(s['n_completed'])}/{len(trace)} requests completed "
          f"({wall:.1f}s wall, {int(s['servers_max'])} servers peak, "
          f"{scaler.n_scale_ups} scale-ups, "
          f"{int(s['n_rerouted'])} crash-rerouted)")
    print(f"  TTFT  p50={s['ttft_p50']:.3f}s  p99={s['ttft_p99']:.3f}s  "
          f"mean={s['ttft_mean']:.3f}s")
    print(f"  TBT   p50={s['tbt_p50']:.3f}s  p99={s['tbt_p99']:.3f}s  "
          f"mean={s['tbt_mean']:.3f}s")
    print(f"  queue_depth_max={int(s['queue_depth_max'])}  "
          f"gpu_seconds={s['gpu_seconds']:.1f}  "
          f"throughput={s['throughput_tok_s']:.1f}tok/s")
    for t, kind, detail in router.metrics.events:
        print(f"  [t={t:6.2f}] {kind:9s} {detail}")
    if args.metrics_json:
        router.metrics.to_json(args.metrics_json)
        print(f"  metrics written to {args.metrics_json}")
    if int(s["n_completed"]) != len(trace):
        raise SystemExit("cluster run did not complete all requests")
    return router


def main(argv=None, cfg=None):
    """Parse ``argv`` and serve.  ``cfg`` serves that configuration as
    given, in place of ``--arch`` and the CPU depth cut.  Returns the
    ``ServingEngine`` (single server) or the ``ClusterRouter``
    (``--cluster``) so a caller can check what was served."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-1.7b")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="single server: crash device 1 after this many "
                         "completions; --cluster: crash server 1 after this "
                         "many completions (re-route + rejoin)")
    ap.add_argument("--adapters", type=int, default=0)
    ap.add_argument("--cluster", action="store_true",
                    help="serverless cluster mode: bursty trace across "
                         "--servers autoscaled PipeBoost servers")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--max-servers", type=int, default=8)
    ap.add_argument("--dispatch", default="least_loaded",
                    choices=("least_loaded", "slo_aware", "adapter_affine"),
                    help="--cluster: dispatch policy "
                         "(cluster/scheduler.py)")
    ap.add_argument("--wall-clock", action="store_true",
                    help="--cluster: run the router off time.monotonic "
                         "instead of logical ticks (real-slice mode)")
    ap.add_argument("--engine", default="event", choices=("event", "tick"),
                    help="--cluster: replay loop — 'event' jumps the "
                         "clock across quiescent gaps (default), 'tick' "
                         "polls every tick (the equivalence oracle; "
                         "identical token streams)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-json", default="",
                    help="--cluster: also dump ClusterMetrics JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if cfg is None:
        cfg = get_arch(args.arch)
        if jax.default_backend() == "cpu":
            n_dev = args.devices if not args.cluster else min(args.devices, 2)
            if args.cluster and n_dev != args.devices:
                print(f"[cpu] clamping --devices {args.devices} -> {n_dev} "
                      f"per server (reduced functional configs)")
            period = max(1, len(cfg.block_pattern) or 1)
            depth = ((2 * n_dev + period - 1) // period) * period
            cfg = cfg.reduced(n_layers=depth)  # >= 1 segment per device
            if args.cluster:
                args.devices = n_dev
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no serve loop")
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)

    if args.cluster:
        return run_cluster(cfg, params, args)

    # overlapped cold start through the PipeBoost engine: one loading
    # round flips `ready` (each device holds ~1/N of the model); the rest
    # of the segments stream in on a background fill thread WHILE the
    # serving engine below admits and decodes
    eng = PipeBoostEngine(cfg, params, n_devices=args.devices, max_len=96)
    t0 = time.perf_counter()
    eng.load_round()
    print(f"ready after 1 loading round ({time.perf_counter()-t0:.2f}s "
          f"wall): chain={eng.chain()}")
    eng.start_fill()                   # background fill: load || serve

    adapter_params = {}
    for i in range(args.adapters):
        lora = randomize_lora(jax.random.fold_in(key, i),
                              init_lora(key, cfg, rank=4, name=f"lora{i}"))
        adapter_params[f"lora{i}"] = merge_lora(params, lora)

    srv = ServingEngine(cfg, params, n_slots=args.slots, max_len=96,
                        policy=EpochSchedulerPolicy(epoch_budget=4,
                                                    max_batch=args.slots),
                        adapter_params=adapter_params)
    if eng.enable_pipeline_prefill():
        # multi-device XLA: admission prefills ride the shard_map belt
        # until the engine's strategy switch (same wiring as ClusterServer)
        srv.batcher.set_pipeline_prefill(eng.serving_pipeline_prefill,
                                         fits=eng.serving_pipeline_fits)
        srv.batcher.prefill_backend = (
            lambda: "pipeline" if eng.strategy == "pipeline" else "single")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        adapter = (f"lora{i % args.adapters}" if args.adapters and i % 2
                   else None)
        srv.submit(ServeRequest(i, rng.integers(0, min(cfg.vocab_size, 250),
                                                size=8),
                                max_new_tokens=args.new_tokens,
                                adapter=adapter))
    done = srv.run()
    eng.stop_fill()
    while eng.load_round():     # finish any tail the thread didn't reach
        pass
    cs = eng.cold_start_stats()
    overlapped = cs["time_to_fully_loaded"] is None \
        or cs["time_to_fully_loaded"] > cs["time_to_ready"]
    print(f"served {len(done)} requests "
          f"({srv.n_adapter_switches} adapter switches)")
    print(f"  cold start: time_to_ready={cs['time_to_ready']:.3f}s "
          f"time_to_fully_loaded={cs['time_to_fully_loaded']:.3f}s "
          f"({cs['n_rounds']} fill rounds, {cs['loaded_bytes']}B; "
          f"serving overlapped loading={overlapped})")
    for r in done:
        print(f"  req{r.rid} adapter={r.adapter or 'base':6s} "
              f"-> {r.generated}")

    if args.crash_at >= 0:
        print(f"injecting crash on device 1 of the PipeBoost engine...")
        batch = {"tokens": jnp.asarray(rng.integers(
            0, min(cfg.vocab_size, 250), size=(1, 8)), jnp.int32)}
        logits = eng.prefill(batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(args.new_tokens):
            if i == args.crash_at:
                eng.crash([1])
                stats = eng.recover()
                print(f"  recovered: {stats.get('reconstruct')}")
            tok = jnp.argmax(eng.decode(tok), -1).astype(jnp.int32)
        print("  decode continued through the crash")
    return srv


if __name__ == "__main__":
    main()
