"""PipeBoost engine: functional multi-device orchestration of
pipeline-parallel loading, inference during loading, strategy switching,
crash injection and recovery (paper §4.1–§4.4).

This engine executes REAL models (repro.models) over *logical devices*.
The devices are bookkeeping entities, on the CPU and on a TPU alike: a
load round records which segments a device holds and whose KV lives
where, and moves no bytes — the parameters are handed in whole and
already on the device, so ``time_to_ready`` is not a load time.  With
several XLA devices the TTFT-critical prefill runs the shard_map belt of
repro/distributed/pipeline.py, stage-sharded over a real mesh.  Timing
comes from core/simulator.py; this module owns *correctness*:

  * a request admitted before full load produces EXACTLY the same tokens as
    a fully-loaded model (pipeline math is the same math);
  * a crash + recovery produces the same KV/state as a fresh prefill.

See ``docs/ARCHITECTURE.md`` § "Core: the PipeBoost engine".
"""
from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import analytic
from repro.core.kv_reconstruct import reconstruct_cache
from repro.core.planner import (LoadPlan, make_plan, reassign, viable_chain)
from repro.launch.mesh import auto_mesh
from repro.lora.adapters import LoRAAdapter, merge_lora, unmerge_lora
from repro.models import transformer


class EngineError(RuntimeError):
    pass


@dataclass
class DeviceState:
    idx: int
    alive: bool = True
    loaded: Set[int] = field(default_factory=set)      # fully-loaded segments
    kv_segments: Set[int] = field(default_factory=set)  # segments whose KV
                                                         # this device owns


@dataclass
class LoadRound:
    """Accounting for one background-fill round (overlapped cold start)."""
    idx: int
    t_start: float                       # seconds since engine construction
    wall_s: float                        # wall-clock spent inside the round
    bytes: int                           # segment bytes transferred this round
    segments: List[Tuple[int, int]]      # (device, segment) loads
    source: str = "host"                 # "host" fill round or "peer"
                                         # multicast delivery


@dataclass
class EngineStatus:
    ready: bool
    fully_loaded: bool
    strategy: str
    alive: List[int]
    loaded: Dict[int, List[int]]
    chain: Optional[List[Tuple[int, int]]]
    # overlapped cold-start instrumentation (None until the event happened)
    time_to_ready: Optional[float] = None
    time_to_fully_loaded: Optional[float] = None
    loaded_bytes: int = 0
    total_bytes: int = 0
    n_rounds: int = 0


class PipeBoostEngine:
    """State machine + functional inference for one GPU-server analogue."""

    def __init__(self, cfg: ArchConfig, params, n_devices: int,
                 n_segments: Optional[int] = None, max_len: int = 256,
                 adapters: Optional[Dict[str, LoRAAdapter]] = None,
                 segments_per_round: int = 1):
        self.cfg = cfg
        self._full_params = params          # "checkpoint in DRAM"
        self.n_devices = n_devices
        self.n_segments = n_segments
        lb = analytic.layer_bytes_list(cfg)
        self.plan: LoadPlan = make_plan(lb, n_devices, n_segments)
        self.devices = [DeviceState(i) for i in range(n_devices)]
        self.max_len = max_len
        self.strategy = "pipeline"          # -> "single" after switch
        self.adapters = adapters or {}
        self.active_adapter: Optional[str] = None
        self._merged_params = params        # params w/ active adapter merged
        self._cache: Optional[Dict] = None
        self._tokens_seen: Optional[jnp.ndarray] = None
        self.events: List[Tuple[str, Any]] = []
        # overlapped cold start: loading is re-entrant (background thread or
        # generator-stepped) and accounted per round
        self.segments_per_round = max(1, segments_per_round)
        self._load_lock = threading.RLock()
        self._fill_thread: Optional[threading.Thread] = None
        self._fill_stop = threading.Event()
        # remembered so a repartition can hand the fill off to a fresh
        # thread over the new plan (same cadence and budget)
        self._fill_interval_s = 0.0
        self._fill_budget: Optional[int] = None
        self._reset_load_accounting()
        # pipeline (shard_map) prefill path — disabled until enabled
        self._pipe_enabled = False
        self._pipe_requested = False
        self._pipe_mesh = None
        self._pipe_n_stages = 0
        self._pipe_n_micro = 0
        self._pipe_fns: Dict[Tuple[int, int, int], Callable] = {}
        self.prefill_backend_used: Optional[str] = None
        self._prefill_jit = jax.jit(
            lambda p, b: transformer.forward(cfg, p, b, mode="prefill",
                                             max_len=self.max_len))
        self._decode_jit = jax.jit(
            lambda p, t, c: transformer.decode_step(cfg, p, {"tokens": t}, c))

    # ---------------- loading ------------------------------------------------

    def _record_event(self, tag: str, payload: Any) -> None:
        """Append to the event log under the load lock — the background
        fill thread appends ``load`` events concurrently, and a plain
        ``list.append`` race would drop entries."""
        with self._load_lock:
            self.events.append((tag, payload))

    def _reset_load_accounting(self) -> None:
        with self._load_lock:
            self._t0 = time.perf_counter()
            self.rounds: List[LoadRound] = []
            self.time_to_ready: Optional[float] = None
            self.time_to_fully_loaded: Optional[float] = None

    def load_next_segment(self, device: int) -> Optional[int]:
        """Advance device's rotated loading order by one segment."""
        with self._load_lock:
            d = self.devices[device]
            if not d.alive:
                raise EngineError(f"device {device} is dead")
            for s in self.plan.order[device]:
                if s not in d.loaded:
                    d.loaded.add(s)
                    self.events.append(("load", (device, s)))
                    return s
            return None

    def load_round(self, budget: Optional[int] = None) -> Optional[LoadRound]:
        """One loading round across alive devices: each device loads up to
        ``budget`` segments (default: the engine's ``segments_per_round``).
        Safe to call from a background thread concurrently with serving.
        Returns the round's accounting, or None when nothing was left to
        load (a ``LoadRound`` is truthy, so boolean callers still work)."""
        budget = budget if budget is not None else self.segments_per_round
        t0 = time.perf_counter()
        loads: List[Tuple[int, int]] = []
        round_: Optional[LoadRound] = None
        with self._load_lock:
            for d in self.devices:
                if not d.alive:
                    continue
                for _ in range(budget):
                    s = self.load_next_segment(d.idx)
                    if s is None:
                        break
                    loads.append((d.idx, s))
            if loads:
                nbytes = sum(self.plan.segments[s].bytes for _, s in loads)
                round_ = LoadRound(
                    len(self.rounds), t0 - self._t0,
                    time.perf_counter() - t0, nbytes, loads)
                self.rounds.append(round_)
            # stamp the two cold-start milestones the moment they flip
            if self.time_to_ready is None and self.ready:
                self.time_to_ready = time.perf_counter() - self._t0
            if self.time_to_fully_loaded is None and self.fully_loaded:
                self.time_to_fully_loaded = time.perf_counter() - self._t0
        return round_

    def load_segment(self, device: int, segment: int,
                     source: str = "peer") -> Optional[LoadRound]:
        """Load one *specific* segment onto one device, out of the rotated
        host-fill order — the multicast delivery path: a peer finished
        streaming this segment over ICI, so it materialises here without a
        host read.  Records a ``LoadRound`` tagged with ``source`` (peer
        deliveries account separately from host rounds) and stamps the
        ready/fully-loaded milestones exactly like ``load_round``.
        Returns None when the device already held the segment."""
        t0 = time.perf_counter()
        with self._load_lock:
            d = self.devices[device]
            if not d.alive:
                raise EngineError(f"device {device} is dead")
            round_: Optional[LoadRound] = None
            if segment not in d.loaded:
                d.loaded.add(segment)
                self.events.append(("load", (device, segment)))
                round_ = LoadRound(
                    len(self.rounds), t0 - self._t0,
                    time.perf_counter() - t0,
                    self.plan.segments[segment].bytes,
                    [(device, segment)], source)
                self.rounds.append(round_)
            if self.time_to_ready is None and self.ready:
                self.time_to_ready = time.perf_counter() - self._t0
            if self.time_to_fully_loaded is None and self.fully_loaded:
                self.time_to_fully_loaded = time.perf_counter() - self._t0
        return round_

    def peer_loaded_bytes(self) -> int:
        """Bytes that arrived via peer multicast rather than host reads."""
        with self._load_lock:
            return sum(r.bytes for r in self.rounds if r.source == "peer")

    # -- background fill driver (the overlap: loading runs concurrently
    #    with serving ticks instead of load-then-serve sequencing) ----------

    def fill_steps(self, budget: Optional[int] = None) -> Iterator[LoadRound]:
        """Generator-step fill API: yields one ``LoadRound`` of accounting
        per round until the model is fully loaded.  The caller interleaves
        ``next()`` with serving work (discrete-event overlap)."""
        while True:
            round_ = self.load_round(budget)
            if round_ is None:
                return
            yield round_

    def start_fill(self, interval_s: float = 0.0,
                   budget: Optional[int] = None) -> threading.Thread:
        """Start the asynchronous background fill: a daemon thread runs
        ``load_round`` until fully loaded (or ``stop_fill``).  Loading is
        pure host-side bookkeeping, so it overlaps with jitted serving
        steps on the main thread."""
        if self._fill_thread is not None and self._fill_thread.is_alive():
            return self._fill_thread
        self._fill_interval_s = interval_s
        self._fill_budget = budget
        self._fill_stop.clear()

        def _run():
            while not self._fill_stop.is_set():
                if not self.load_round(budget):
                    return
                if interval_s > 0:
                    self._fill_stop.wait(interval_s)

        t = threading.Thread(target=_run, name="pipeboost-fill", daemon=True)
        self._fill_thread = t
        t.start()
        return t

    def stop_fill(self, join: bool = True) -> None:
        self._fill_stop.set()
        if join and self._fill_thread is not None:
            self._fill_thread.join(timeout=30.0)
        self._fill_thread = None

    @property
    def fill_running(self) -> bool:
        return self._fill_thread is not None and self._fill_thread.is_alive()

    def loaded_map(self) -> Dict[int, List[int]]:
        with self._load_lock:
            return {d.idx: sorted(d.loaded) for d in self.devices if d.alive}

    def chain(self) -> Optional[List[Tuple[int, int]]]:
        with self._load_lock:
            return viable_chain(self.plan, self.loaded_map(),
                                [d.idx for d in self.devices if d.alive])

    @property
    def ready(self) -> bool:
        return self.chain() is not None

    def rounds_to_ready(self, budget: Optional[int] = None) -> int:
        """Predicted ``load_round`` calls until a viable chain exists
        (0 when already ready) — the cold-start-progress signal SLO-aware
        dispatch scores warming servers by.

        Pure bookkeeping: simulates the rotated load order on copies of
        the per-device loaded sets, never touching real state.  Returns a
        large sentinel if no amount of loading can complete a chain
        (e.g. every device dead)."""
        budget = budget if budget is not None else self.segments_per_round
        with self._load_lock:
            alive = [d.idx for d in self.devices if d.alive]
            loaded = {d.idx: set(d.loaded) for d in self.devices if d.alive}
            if not alive:
                return 1 << 20
            if viable_chain(self.plan, {i: sorted(s) for i, s in
                                        loaded.items()}, alive) is not None:
                return 0
            n_seg = len(self.plan.segments)
            for rounds in range(1, n_seg + 1):
                for i in alive:
                    todo = [s for s in self.plan.order[i]
                            if s not in loaded[i]][:max(1, budget)]
                    loaded[i].update(todo)
                if viable_chain(self.plan, {i: sorted(s) for i, s in
                                            loaded.items()},
                                alive) is not None:
                    return rounds
            return 1 << 20

    @property
    def fully_loaded(self) -> bool:
        with self._load_lock:
            n = len(self.plan.segments)
            return all(len(d.loaded) == n for d in self.devices if d.alive)

    def loaded_bytes(self) -> int:
        """Bytes resident across alive devices (each device transfers its
        own copy of a segment, so bytes count per device)."""
        with self._load_lock:
            return sum(self.plan.segments[s].bytes
                       for d in self.devices if d.alive for s in d.loaded)

    def total_bytes(self) -> int:
        """Bytes every alive device must eventually hold (fully_loaded)."""
        with self._load_lock:
            model = sum(s.bytes for s in self.plan.segments)
            return model * sum(1 for d in self.devices if d.alive)

    def cold_start_stats(self) -> Dict[str, Any]:
        """Flat cold-start accounting for metrics/benchmarks."""
        with self._load_lock:
            return {
                "time_to_ready": self.time_to_ready,
                "time_to_fully_loaded": self.time_to_fully_loaded,
                "loaded_bytes": self.loaded_bytes(),
                "total_bytes": self.total_bytes(),
                "n_rounds": len(self.rounds),
                "round_bytes": [r.bytes for r in self.rounds],
            }

    def status(self) -> EngineStatus:
        """One consistent snapshot (taken under the load lock, so a fill
        round can't land between the fields)."""
        with self._load_lock:
            return EngineStatus(self.ready, self.fully_loaded, self.strategy,
                                [d.idx for d in self.devices if d.alive],
                                self.loaded_map(), self.chain(),
                                self.time_to_ready,
                                self.time_to_fully_loaded,
                                self.loaded_bytes(), self.total_bytes(),
                                len(self.rounds))

    # ---------------- adapters (merged-LoRA, §4.3.2) -------------------------

    def switch_adapter(self, name: Optional[str]):
        if name == self.active_adapter:
            return
        params = self._full_params
        if name is not None:
            if name not in self.adapters:
                raise EngineError(f"unknown adapter {name!r}")
            params = merge_lora(params, self.adapters[name])
        self.active_adapter = name
        self._merged_params = params
        self._record_event("adapter_switch", name)

    # ---------------- inference ---------------------------------------------

    def _segment_layer_mask(self, segs: Set[int]) -> List[bool]:
        """Per-global-layer: is the layer inside one of ``segs``."""
        mask = [False] * self.cfg.n_layers
        with self._load_lock:        # a repartition may swap self.plan
            for s in segs:
                seg = self.plan.segments[s]
                for i in range(seg.layer_start, seg.layer_end):
                    mask[i] = True
        return mask

    def lost_state_layers(self, device_ids: Sequence[int]) -> List[bool]:
        """Per-global-layer: True if that layer's KV/recurrent state lives
        on one of ``device_ids`` under the current serving assignment.

        Ownership follows the viable pipeline chain (each chained segment's
        KV sits in its device's HBM); with no chain yet, nothing is owned.
        Must be called BEFORE ``crash`` marks the devices dead — the chain
        is computed over alive devices.  This is what lets a partial crash
        reconstruct only the layers that actually died (paper §4.4.2)
        instead of re-prefilling everything.
        """
        dead = set(device_ids)
        ch = self.chain()
        if ch is None:
            return [False] * self.cfg.n_layers
        return self._segment_layer_mask(
            {seg for dev, seg in ch if dev in dead})

    # -- pipeline (shard_map) prefill dispatch ------------------------------

    def enable_pipeline_prefill(self, mesh=None, n_micro: int = 2) -> bool:
        """Opt the TTFT-critical prefill into the shard_map pipeline
        lowering (distributed/pipeline.py): stage *i* runs the layers of
        the segments device *i* has loaded, so the first token computes on
        the partial chain while later segments keep streaming in.

        Auto-sizes the ('data', 'stage') mesh over the visible XLA devices
        when ``mesh`` is None.  Returns False (engine keeps the standard
        lowering) when the backend or architecture can't pipeline: fewer
        than 2 XLA devices, a hybrid layer stack, or an indivisible layer
        count.
        """
        kinds = set(self.cfg.layer_kinds())
        if len(kinds) != 1 or next(iter(kinds)) not in ("attn", "moe", "ssm"):
            return False
        if mesh is None:
            n_xla = len(jax.devices())
            if n_xla < 2:
                return False
            n_stages = 0
            for s in range(min(n_xla, self.cfg.n_layers), 1, -1):
                if self.cfg.n_layers % s == 0 and n_xla % s == 0:
                    n_stages = s
                    break
            if not n_stages:
                return False
            mesh = auto_mesh((n_xla // n_stages, n_stages), ("data", "stage"))
        else:
            n_stages = mesh.shape["stage"]
            if self.cfg.n_layers % n_stages:
                return False
        self._pipe_mesh = mesh
        self._pipe_n_stages = n_stages
        self._pipe_n_micro = max(1, n_micro)
        self._pipe_fns = {}
        self._pipe_enabled = True
        self._pipe_requested = True
        return True

    def _pipeline_fits(self, batch: Dict) -> bool:
        if not self._pipe_enabled or self._pipe_mesh is None \
                or self.strategy != "pipeline":
            return False
        tokens = batch.get("tokens", batch.get("embeds"))
        B = tokens.shape[0]
        n_data = self._pipe_mesh.shape["data"]
        if B % n_data:
            return False
        return (B // n_data) % self._pipe_n_micro == 0

    def _pipeline_prefill_fn(self, B: int, S: int) -> Callable:
        # Keyed by stage count as well as shape: a repartition that moves
        # to a stage count seen before reuses its compiles verbatim, and a
        # NEW stage count costs at most one lowering per shape — compiles
        # scale with distinct stage plans, never with crash events.
        key = (self._pipe_n_stages, B, S)
        if key not in self._pipe_fns:
            from repro.distributed.pipeline import build_pipeline_prefill
            self._pipe_fns[key] = jax.jit(build_pipeline_prefill(
                self.cfg, n_stages=self._pipe_n_stages,
                n_micro=self._pipe_n_micro, mesh=self._pipe_mesh,
                seq_len=S, max_len=self.max_len, return_cache=True))
        return self._pipe_fns[key]

    def serving_pipeline_fits(self, P: int, S: int) -> bool:
        """Shape pre-check for ``serving_pipeline_prefill`` (the batcher's
        dispatch): row count must split over the ('data', 'stage') mesh."""
        if not self._pipe_enabled or self._pipe_mesh is None:
            return False
        n_data = self._pipe_mesh.shape["data"]
        return P % n_data == 0 and (P // n_data) % self._pipe_n_micro == 0

    def serving_pipeline_prefill(self, params, batch: Dict):
        """``ContinuousBatcher.set_pipeline_prefill`` contract: lower an
        admission prefill (right-padded rows + per-row last_index) through
        the shard_map pipeline belt and hand the state back in the
        per-replica layout (committed, so the batcher's donated scatter
        and the fused decode step never retrace)."""
        tokens = batch["tokens"]
        fn = self._pipeline_prefill_fn(tokens.shape[0], tokens.shape[1])
        logits, state = fn(params, batch)
        return jax.device_put((logits, state), jax.devices()[0])

    def prefill(self, batch: Dict) -> jnp.ndarray:
        """Serve a prefill the moment a chain exists (the paper's point:
        this happens after each device loaded only ~1/N of the model).

        While the engine is in pipeline strategy on a multi-device backend
        (``enable_pipeline_prefill``), the prefill lowers through the
        shard_map belt — layers stay stage-sharded exactly like the loaded
        segments — and the returned cache feeds the SAME fused decode jit
        the single lowering uses (identical shapes: no retrace at the
        strategy switch)."""
        chain = self.chain()
        if chain is None:
            raise EngineError("no viable pipeline chain: model not ready")
        if self._pipeline_fits(batch):
            tokens = batch.get("tokens", batch.get("embeds"))
            B, S = tokens.shape[0], tokens.shape[1]
            logits, cache = self._pipeline_fn_call(B, S, batch)
            self.prefill_backend_used = "pipeline"
        else:
            logits, cache = self._prefill_jit(self._merged_params, batch)
            if self._pipe_enabled:
                # keep layouts (and committed-ness, part of the jit cache
                # key) identical to the pipeline hand-off's so alternating
                # backends never retraces the decode step
                logits, cache = jax.device_put((logits, cache),
                                               jax.devices()[0])
            self.prefill_backend_used = "single"
        self._cache = cache
        self._tokens_seen = batch.get("tokens")
        # KV ownership follows the serving chain
        with self._load_lock:
            for d in self.devices:
                d.kv_segments = set()
            for dev, seg in chain:
                self.devices[dev].kv_segments.add(seg)
            self.events.append(("prefill", chain))
            self.events.append(("prefill_backend",
                                self.prefill_backend_used))
        return logits

    def _pipeline_fn_call(self, B: int, S: int, batch: Dict):
        fn = self._pipeline_prefill_fn(B, S)
        logits, state = fn(self._merged_params, batch)
        # Strategy hand-off (§4.3.3): the pipeline leaves KV stage-sharded
        # where each segment's layers live; the per-replica fused decode
        # step owns the whole cache.  One explicit re-lay here keeps the
        # decode jit's input layouts identical to the standard lowering's —
        # the switch moves data once but NEVER retraces.
        cache: Dict[str, Any] = {"pos": jnp.full((B,), S, jnp.int32)}
        cache.update(state)
        # committed-ness is part of the jit cache key, so the whole cache
        # (pos included) must land identically to the standard lowering's
        logits, cache = jax.device_put((logits, cache), jax.devices()[0])
        return logits, cache

    def decode(self, tokens: jnp.ndarray) -> jnp.ndarray:
        if self._cache is None:
            raise EngineError("prefill first")
        if self.strategy == "pipeline" and self.chain() is None:
            raise EngineError("pipeline chain broken — recover() first")
        logits, self._cache = self._decode_jit(self._merged_params, tokens,
                                               self._cache)
        if self._tokens_seen is not None:
            self._tokens_seen = jnp.concatenate(
                [self._tokens_seen, tokens.reshape(-1, 1)], axis=1)
        return logits

    # ---------------- instrumentation ----------------------------------------

    def compile_stats(self) -> Dict[str, int]:
        """XLA compile counts of the engine's jitted paths.  The decode
        count must stay 1 across the pipeline->single strategy switch (the
        pipeline prefill's cache has the same shapes as the standard
        lowering's, so the switch never retraces)."""
        def _n(fn):
            try:
                return int(fn._cache_size())
            except Exception:       # private API moved — report -1, don't die
                return -1
        out = {"decode_compiles": _n(self._decode_jit),
               "prefill_compiles": _n(self._prefill_jit)}
        out["pipeline_prefill_compiles"] = (
            sum(max(0, _n(f)) for f in self._pipe_fns.values())
            if self._pipe_fns else 0)
        return out

    # ---------------- strategy switching (§4.3.3) ----------------------------

    def maybe_switch_strategy(self, request_rate: float,
                              crossover_rate: float = 0.0) -> bool:
        """Seamless switch to per-device independent serving once every
        device holds the full model (and the rate argues for it)."""
        if self.strategy == "single":
            return False
        if self.fully_loaded and request_rate >= crossover_rate:
            self.strategy = "single"
            self._record_event("strategy_switch", "single")
            return True
        return False

    # ---------------- failures + recovery (§4.4) -----------------------------

    def crash(self, device_ids: Sequence[int]):
        """Mark devices dead.  If the background fill thread is running it
        is stopped *cleanly*: the stop flag is raised before the devices
        are marked (a round in flight holds ``_load_lock`` and finishes
        atomically, so its ``LoadRound`` accounting lands exactly once),
        then the thread is joined OUTSIDE the lock — no leaked thread, no
        double-counted bytes, and no half-recorded round."""
        was_filling = self.fill_running
        if was_filling:
            self._fill_stop.set()
        with self._load_lock:
            for i in device_ids:
                self.devices[i].alive = False
        if was_filling:
            self.stop_fill(join=True)
        self._record_event("crash", list(device_ids))

    def restart(self, n_devices: Optional[int] = None):
        """Full server reboot (cluster rejoin path): every device comes back
        alive and empty with a fresh rotated load plan; serving state is
        dropped (in-flight requests were re-routed before the restart)."""
        self.stop_fill()
        with self._load_lock:
            if n_devices is not None:
                self.n_devices = n_devices
                self.n_segments = None  # segment override was per-dev-count
            lb = analytic.layer_bytes_list(self.cfg)
            self.plan = make_plan(lb, self.n_devices, self.n_segments)
            self.devices = [DeviceState(i) for i in range(self.n_devices)]
            self.strategy = "pipeline"
            self._cache = None
            self._tokens_seen = None
            self._reset_load_accounting()   # a rejoin is a fresh cold start
        self._record_event("restart", self.n_devices)

    def revive(self, device_ids: Sequence[int]):
        """Bring crashed devices back online with empty HBM and re-plan the
        segment ring over the enlarged alive set; the revived devices pick
        up their missing spans on subsequent ``load_round`` calls."""
        with self._load_lock:
            for i in device_ids:
                d = self.devices[i]
                if d.alive:
                    continue
                d.alive = True
                d.loaded = set()
                d.kv_segments = set()
            alive = [d.idx for d in self.devices if d.alive]
            self.plan = reassign(self.plan, self.loaded_map(), alive)
        self._record_event("revive", list(device_ids))

    def _repartition_pipeline(self) -> int:
        """Rebuild the shard_map prefill mesh for the current alive-device
        count (variable-stage mesh, FlexPipe direction).  Picks the largest
        stage count that divides the layer stack and fits the visible XLA
        devices — possibly over a SUBSET of them (``stage_mesh``), so stage
        counts that don't divide the device count still pipeline.  Falls
        back to the single lowering when no split works (decode is
        unaffected either way).  Never clears ``_pipe_fns``: entries are
        keyed by (n_stages, B, S), so a stage count seen before reuses its
        compiles and a new one costs at most one lowering per shape."""
        if not self._pipe_requested:
            return self._pipe_n_stages if self._pipe_enabled else 0
        with self._load_lock:
            n_alive = sum(1 for d in self.devices if d.alive)
        n_xla = len(jax.devices())
        n_stages = 0
        for s in range(min(n_alive, n_xla, self.cfg.n_layers), 1, -1):
            if self.cfg.n_layers % s == 0:
                n_stages = s
                break
        if not n_stages:
            self._pipe_enabled = False
            self._pipe_mesh = None
            self._pipe_n_stages = 0
            return 0
        if n_stages != self._pipe_n_stages or not self._pipe_enabled:
            from repro.distributed.pipeline import stage_mesh
            self._pipe_mesh = stage_mesh(n_stages)
            self._pipe_n_stages = n_stages
            self._pipe_enabled = True
        return n_stages

    def repartition(self, dead: Sequence[int] = (),
                    revive: Sequence[int] = ()) -> Dict[str, Any]:
        """Elastic in-flight repartition: re-split the pipeline over a
        CHANGED device set — shrink (e.g. 4→3 stages) when devices die,
        widen back when they rejoin — without draining in-flight work.

        Steps: (1) stop the background fill cleanly (remembering cadence);
        (2) apply the membership change and ``reassign`` contiguous stage
        spans over the new alive set; (3) load until a viable chain exists
        again; (4) rebuild the shard_map mesh for the new stage count
        (compiles keyed per stage count, never per crash event); (5) re-lay
        live decode state onto the new partition via ``reconstruct_cache``
        — only layers whose KV actually died are recomputed, surviving
        layers are reused verbatim, so the continued token stream is
        bit-identical and zero tokens are re-prefilled; (6) hand the fill
        back off to a fresh thread over the new plan if one was running.

        Returns a stats dict (also appended as a ``repartition`` event).
        """
        dead = [int(i) for i in dead]
        revive = [int(i) for i in revive]
        was_filling = self.fill_running
        if was_filling:
            self._fill_stop.set()
            self.stop_fill(join=True)
        with self._load_lock:
            for i in dead:
                self.devices[i].alive = False
            for i in revive:
                d = self.devices[i]
                if d.alive:
                    continue
                d.alive = True
                d.loaded = set()
                d.kv_segments = set()
            alive = [d.idx for d in self.devices if d.alive]
            if not alive:
                raise EngineError("all devices dead")
            self.plan = reassign(self.plan, self.loaded_map(), alive)
        while self.chain() is None:
            if not self.load_round():
                raise EngineError("cannot complete chain after repartition")
        n_stages = self._repartition_pipeline()
        stats: Dict[str, Any] = {
            "dead": dead, "revive": revive, "n_alive": len(alive),
            "n_stages": n_stages, "lost_layers": 0,
        }
        ch = self.chain()
        if self._cache is not None and self._tokens_seen is not None:
            surviving_kv: Set[int] = set()
            with self._load_lock:
                for d in self.devices:
                    if d.alive:
                        surviving_kv |= d.kv_segments
            has_state = self._segment_layer_mask(surviving_kv)
            stats["lost_layers"] = int(sum(1 for h in has_state if not h))
            if not all(has_state):
                # the reconstruct prefill is the expensive part — keep it
                # OUTSIDE the lock so the refill thread isn't stalled
                self._cache, rstats = reconstruct_cache(
                    self.cfg, self._merged_params,
                    {"tokens": self._tokens_seen}, self._cache, has_state,
                    max_len=self.max_len)
                stats["reconstruct"] = rstats
            # KV ownership follows the NEW chain after the re-lay
            with self._load_lock:
                for d in self.devices:
                    d.kv_segments = set()
                for dev, seg in ch:
                    self.devices[dev].kv_segments.add(seg)
        if was_filling and not self.fully_loaded:
            self.start_fill(self._fill_interval_s, self._fill_budget)
        self._record_event("repartition", stats)
        return stats

    def recover(self) -> Dict[str, Any]:
        """Pipeline-parallel recovery: layer reassignment + (if mid-decode)
        KV/state reconstruction.  Returns a stats dict."""
        stats: Dict[str, Any] = {}
        with self._load_lock:
            alive = [d.idx for d in self.devices if d.alive]
            if not alive:
                raise EngineError("all devices dead")
            ch = self.chain()
            if ch is None:
                # layer reassignment: survivors re-plan loading of missing
                # spans.  Under the lock: a fill round racing the plan swap
                # would load segments of the plan being replaced.
                self.plan = reassign(self.plan, self.loaded_map(), alive)
                stats["replanned"] = True
        if stats.get("replanned"):
            while not self.ready:
                if not self.load_round():
                    raise EngineError("cannot complete chain")
            ch = self.chain()
        stats["chain"] = ch

        # KV reconstruction for in-flight decode state (if any)
        if self._cache is not None and self._tokens_seen is not None:
            surviving_kv: Set[int] = set()
            with self._load_lock:
                for d in self.devices:
                    if d.alive:
                        surviving_kv |= d.kv_segments
            has_state = self._segment_layer_mask(surviving_kv)
            # reconstruct prefill runs OUTSIDE the lock (expensive; the
            # refill thread may keep loading while state is recomputed)
            self._cache, rstats = reconstruct_cache(
                self.cfg, self._merged_params,
                {"tokens": self._tokens_seen}, self._cache, has_state,
                max_len=self.max_len)
            stats["reconstruct"] = rstats
            with self._load_lock:
                for dev, seg in ch:
                    self.devices[dev].kv_segments.add(seg)
        self._record_event("recover", stats)
        return stats


def generate(engine: PipeBoostEngine, batch: Dict, n_tokens: int,
             crash_at: Optional[int] = None,
             crash_devices: Sequence[int] = ()) -> jnp.ndarray:
    """Greedy generation helper (tests/examples): returns (B, n_tokens)."""
    logits = engine.prefill(batch)
    outs = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    outs.append(tok)
    for i in range(1, n_tokens):
        if crash_at is not None and i == crash_at:
            engine.crash(crash_devices)
            engine.recover()
        logits = engine.decode(tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(tok)
    return jnp.stack(outs, axis=1)
