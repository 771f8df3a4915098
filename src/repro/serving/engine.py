"""Serving engine: request lifecycle + continuous batching (Orca-style,
which the paper adopts) over slot-indexed KV caches, with epoch-based
LoRA adapter scheduling and PipeBoost cold-start/recovery integration.

Slots: the engine owns one batched cache of ``n_slots``; a new request's
prefill is computed and written into a free slot while other slots keep
decoding — requests join/leave the batch at token granularity (continuous
batching).  Per-slot positions ride in ``cache["pos"]`` (B,).

Hot-path design (the zero-copy decode loop)
-------------------------------------------
* **Donated fused decode+sample**: one jitted step runs
  ``decode_step`` + the sampler with ``donate_argnums`` on the cache, so
  every token updates the KV buffers in place instead of copying the
  whole slot-stacked cache.  Exactly one small (B,) device->host transfer
  happens per step (the sampled tokens); the token array itself stays on
  device between steps.
* **Bucketed prefill**: prompts are right-padded to power-of-two length
  buckets (``bucket_sizes``) so XLA compiles once per bucket, not once
  per prompt length.  Padding is masked in-kernel: causal attention means
  trailing pads never contaminate real positions, the last-token logits
  are gathered at the true prompt end (``forward(..., last_index=...)``),
  and ``cache["pos"]`` records the true length so decode attention masks
  the pad K/V.  Same-bucket requests prefill together in one batched
  call, and the slot write happens in-jit on the donated cache (a
  select/scatter over stacked leaves) instead of a per-leaf Python loop.
* **One jit for the engine's lifetime**: params are a traced argument, so
  an adapter epoch switch swaps ``params`` without retracing; free slots
  are masked in-jit (their ``pos`` is frozen and their token is passed
  through) so inactive lanes can't hit sampler edge cases.

``compile_stats()`` / ``hotpath_stats()`` surface compile counts and
decode throughput for benchmarks, the cluster metrics, and the CI
compile-count regression guard.

See ``docs/ARCHITECTURE.md`` § "Serving: continuous batching".
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ArchConfig
from repro.core.adapter_scheduler import EpochSchedulerPolicy
from repro.models import transformer
from repro.serving.snapshot import KVSnapshot, export_slot, export_slots

BUCKET_MIN = 16


def quantized_greedy(logits):
    """Quantize-then-argmax greedy sampler: sub-1e-3 fp differences between
    batched and solo kernels land in the same bin, so the pick only flips in
    the (vanishingly rare) case where near-tied logits straddle a bin edge.
    The cluster layer uses this for exact replay after crash re-routing."""
    return jnp.argmax(jnp.round(logits.astype(jnp.float32) * 1e3), axis=-1)


def bucket_sizes(max_len: int, bmin: int = BUCKET_MIN) -> List[int]:
    """The prefill length buckets for ``max_len``: powers of two from
    ``bmin`` up, with ``max_len`` itself as the final bucket."""
    out = []
    b = bmin
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


@dataclass
class ServeRequest:
    rid: int
    tokens: np.ndarray                   # prompt (S,)
    max_new_tokens: int
    adapter: Optional[str] = None
    arrival: Optional[float] = None      # stamped at submit if unset
    model: Optional[str] = None          # fleet pool name (multi-model)
    deadline: Optional[float] = None     # absolute TTFT deadline (clock s);
                                         # None = no SLO attached
    generated: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    eos_id: Optional[int] = None
    # decode state exported at drain time (crash migration); carried so a
    # survivor can resume without re-prefill — excluded from equality
    snapshot: Optional[KVSnapshot] = field(default=None, repr=False,
                                           compare=False)


class ContinuousBatcher:
    """Slot-based continuous batching over the stacked-cache models."""

    def __init__(self, cfg: ArchConfig, params, n_slots: int, max_len: int,
                 sampler: Optional[Callable] = None):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        # prefill backend dispatch (overlapped cold start): while the host
        # engine is mid-load in pipeline strategy, admission prefills lower
        # through the injected pipeline fn (shard_map belt on multi-device
        # backends); after the strategy switch — or when nothing was
        # injected — through the engine's own fused single lowering
        self.prefill_backend: Callable[[], str] = lambda: "single"
        self._pipe_prefill: Optional[Callable] = None
        self._pipe_fits: Callable[[int, int], bool] = lambda P, S: True
        self.cache = transformer.init_cache(cfg, n_slots, max_len,
                                            jnp.dtype(cfg.dtype))
        self.cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
        self.active: Dict[int, ServeRequest] = {}     # slot -> request
        self.free: List[int] = list(range(n_slots))
        # Bucketed (padded) prefill is exact only when every layer is
        # batch-row-independent AND per-token causal (attention with a
        # full-length cache, and dropless MoE, which routes per token and
        # routes pad tokens nowhere): SSM/recurrent states integrate pad
        # tokens, and a ring buffer would evict real K/V.
        self._can_bucket = (
            set(cfg.layer_kinds()) <= {"attn", "moe"}
            and transformer.attn_cache_capacity(cfg, max_len) == max_len)
        # device-resident step I/O (rebuilt only when slot membership
        # changes; in steady state nothing crosses the host boundary except
        # the sampled tokens)
        self._dev_tokens = jnp.zeros((n_slots,), jnp.int32)
        self._dev_active = jnp.zeros((n_slots,), bool)
        self._io_dirty = True
        # hot-path counters
        self.n_decode_steps = 0
        self.decode_time_s = 0.0
        self.n_prefill_calls = 0
        self.n_prefill_reqs = 0
        self.n_prefill_pipeline = 0      # requests prefilled via the
                                         # pipeline (cold-start) lowering
        # migration counters (snapshot imports; tokens whose prefill was
        # skipped because their state arrived with them)
        self.n_migrated_in = 0
        self.migrated_tokens_in = 0
        self.n_batched_imports = 0       # import_snapshots scatter calls
        self.n_relay_scatters = 0        # relay_inflight scatter calls
                                         # (repartition re-lay)
        # cross-request prefix reuse (serving/prefix_cache.py): when a
        # store is attached, admission probes it per request, imports hits
        # through the shared donated scatter, and prefills only the
        # uncached suffix; completed/drained prompts deposit their rows
        self.prefix_cache = None
        self._prefix_evict_base = 0      # evictions before attach (delta)
        self.n_prefill_tokens = 0        # real (unpadded) tokens prefilled
        self.n_prefill_padded_tokens = 0  # rows x bucket of each prefill call
        self.prefix_hits = 0             # admissions served from the cache
        self.prefix_hit_tokens = 0       # prompt tokens NOT re-prefilled
        self._sampler = sampler or (lambda lg: jnp.argmax(lg, axis=-1))
        self._build_jits()

    # ------------------------------------------------------------------
    # jitted hot-path functions (built once; params stay a traced argument
    # so adapter switches never retrace)
    # ------------------------------------------------------------------
    @property
    def sampler(self) -> Callable:
        return self._sampler

    @sampler.setter
    def sampler(self, fn: Callable) -> None:
        # the sampler is fused into the jitted step, so swapping it needs a
        # fresh trace (done here, never on adapter switches)
        self._sampler = fn
        self._build_jits()

    def _build_jits(self) -> None:
        cfg, n_slots, max_len = self.cfg, self.n_slots, self.max_len

        # With MoE layers both fused steps also return the layers' counts
        # (``stats``), and route free slots and pad tokens to no expert.
        moe = "moe" in cfg.layer_kinds()

        def fused_decode(p, toks, active_mask, cache):
            old_pos = cache["pos"]
            if moe:
                logits, cache, stats = transformer.decode_step(
                    cfg, p, {"tokens": toks}, cache, token_mask=active_mask,
                    return_stats=True)
            else:
                logits, cache = transformer.decode_step(
                    cfg, p, {"tokens": toks}, cache)
            # freeze free slots: their position must not advance (a wrapped
            # ring-buffer pos would corrupt a later admission) and their
            # garbage logits must not reach EOS bookkeeping
            cache["pos"] = jnp.where(active_mask, cache["pos"], old_pos)
            nxt = self._sampler(logits).astype(jnp.int32)
            nxt = jnp.where(active_mask, nxt, toks)
            return (nxt, cache, stats) if moe else (nxt, cache)

        self._decode_fused = jax.jit(fused_decode, donate_argnums=(3,))

        def write_rows(cache, rows, slots, valid, pos):
            """Scatter per-request row stacks into the donated cache.

            ``rows``: kind -> leaf -> (L, P, ...) stacked rows (a prefill's
            fresh cache, a batch of migrated snapshots, or the pipeline
            prefill's state); slot j takes row src[j] iff some valid row
            targets it — one select per leaf, no per-row dispatch.
            """
            sel = (slots[None, :] == jnp.arange(n_slots)[:, None]) \
                & valid[None, :]                       # (n_slots, P)
            written = sel.any(axis=1)                  # (n_slots,)
            src = jnp.argmax(sel.astype(jnp.int32), axis=1)
            for key in ("attn", "ssm", "rec"):
                if key in rows:
                    for leaf in rows[key]:
                        old = cache[key][leaf]
                        new = jnp.take(rows[key][leaf], src, axis=1)
                        w = written.reshape((1, -1) + (1,) * (old.ndim - 2))
                        cache[key][leaf] = jnp.where(w, new, old)
            cache["pos"] = jnp.where(written, jnp.take(pos, src),
                                     cache["pos"])
            return cache

        def fused_prefill(p, toks, last_idx, slots, valid, cache):
            """Prefill padded prompts and write them into ``slots`` in-jit.

            toks (P, bucket) int32 right-padded; last_idx (P,) true last
            token index; slots (P,) target slot per row; valid (P,) row
            mask (pad rows are ignored).  The cache is donated: the write
            is a per-slot select over the stacked leaves, not a Python
            ``.at[].set`` loop with one dispatch per leaf.
            """
            kw = {}
            if moe:
                kw = dict(return_stats=True, token_mask=(
                    jnp.arange(toks.shape[1])[None, :] <= last_idx[:, None])
                    & valid[:, None])
            logits, c1, *stats = transformer.forward(
                cfg, p, {"tokens": toks}, mode="prefill", max_len=max_len,
                last_index=last_idx, **kw)
            rows = {k: c1[k] for k in ("attn", "ssm", "rec") if k in c1}
            cache = write_rows(cache, rows, slots, valid, last_idx + 1)
            first = self._sampler(logits).astype(jnp.int32)
            return (first, cache, *stats)

        self._prefill_fused = jax.jit(fused_prefill, donate_argnums=(5,))

        def fused_scatter(cache, rows, slots, pos, valid):
            """Standalone donated row scatter (one compile for its
            lifetime): batched snapshot import — N migrated requests land
            in ONE call — and the pipeline-prefill slot write both ride
            this.  Row count is pinned to ``n_slots`` (pad rows masked by
            ``valid``) so every caller shares the compilation."""
            return write_rows(cache, rows, slots, valid, pos)

        self._scatter_fused = jax.jit(fused_scatter, donate_argnums=(0,))

        def fused_import(cache, rows, slot, pos):
            """Scatter one request's per-layer state rows into ``slot``.

            ``rows``: kind -> leaf -> (L, ...) arrays (a KVSnapshot's rows
            or a reconstructed slot).  One donated in-place scatter for the
            whole model — no host round-trip per layer, no cache copy.
            ``slot``/``pos`` are traced scalars so every import shares one
            compilation.
            """
            for kind in ("attn", "ssm", "rec"):
                if kind in rows:
                    for leaf in rows[kind]:
                        cache[kind][leaf] = \
                            cache[kind][leaf].at[:, slot].set(rows[kind][leaf])
            cache["pos"] = cache["pos"].at[slot].set(pos)
            return cache

        self._import_fused = jax.jit(fused_import, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # prefill / admission
    # ------------------------------------------------------------------
    def set_pipeline_prefill(self, fn: Callable,
                             fits: Optional[Callable[[int, int], bool]]
                             = None) -> None:
        """Inject the pipeline prefill lowering for cold-start dispatch.

        ``fn(params, {"tokens": (P, S), "last_index": (P,)})`` must return
        ``(last-index logits (P, V), state {kind: {leaf: (L, P, ...)}})``
        — the contract of ``distributed.pipeline.build_pipeline_prefill``
        with ``return_cache=True`` (see ``PipeBoostEngine.
        serving_pipeline_prefill``).  ``fits(P, S)`` pre-checks mesh
        divisibility; unfit shapes fall back to the single lowering.
        Admission uses it only while ``prefill_backend()`` says
        "pipeline" (i.e. mid-load, before the strategy switch).
        """
        self._pipe_prefill = fn
        if fits is not None:
            self._pipe_fits = fits

    def _choose_prefill_backend(self, P: int, bucket: int) -> str:
        if (self._pipe_prefill is not None and self._can_bucket
                and self.prefill_backend() == "pipeline"
                and self._pipe_fits(P, bucket)):
            return "pipeline"
        return "single"

    def _total_len(self, req: ServeRequest) -> int:
        return len(req.tokens) + len(req.generated)

    def bucket_for(self, req: ServeRequest) -> int:
        """Padded prefill length for ``req`` (exact length when the model
        can't be padded safely — see ``_can_bucket``)."""
        L = self._total_len(req)
        if not self._can_bucket:
            return L
        # derive from bucket_sizes so the ladder the engine pads with and
        # the ladder the compile-count guards bound against can't drift
        for b in bucket_sizes(self.max_len):
            if b >= L:
                return b
        return L        # out-of-contract (L > max_len): exact length

    def admit(self, req: ServeRequest) -> bool:
        """Prefill ``req`` into a free slot; False if the batch is full.

        Re-submission: a request that already carries ``generated`` tokens
        (drained from a crashed server) is prefilled over prompt + generated,
        so greedy decoding continues exactly where it left off.
        """
        if not self.free:
            return False
        self.admit_batch([req])
        return True

    def attach_prefix_cache(self, cache) -> None:
        """Attach (or detach with ``None``) a ``PrefixCache``.

        Eviction accounting is delta-based from this moment, so a store
        that moves between servers via the cluster's ``StateTier`` never
        double-counts its history into two servers' hot-path stats.
        Prefix reuse rides the bucketed-attention cache contract
        (``_can_bucket``): SSM/recurrent state integrates every token and
        a ring buffer evicts real K/V, so those models skip probing.
        """
        self.prefix_cache = cache
        self._prefix_evict_base = 0 if cache is None else cache.evictions

    def admit_batch(self, reqs: Sequence[ServeRequest]) -> None:
        """Prefill several requests in one batched, bucketed call.

        Caller guarantees ``len(reqs) <= len(self.free)``.  Requests are
        padded to the largest bucket in the group (the scheduler groups by
        bucket, so normally they share one).  Models that can't pad safely
        are prefilled one by one at exact length.

        With a prefix cache attached, each fresh request first probes it:
        hits import their cached prompt-prefix rows and replay only the
        uncached suffix (``_admit_prefix_hits``); misses — and re-submits
        carrying a generated prefix — take the normal prefill path.
        """
        assert len(reqs) <= len(self.free), (len(reqs), len(self.free))
        with tracing.span("pb.admit") as sp:
            hits: List[Tuple[ServeRequest, Any]] = []
            misses: List[ServeRequest] = []
            for r in reqs:
                h = None
                if (self.prefix_cache is not None and self._can_bucket
                        and not r.generated):
                    h = self.prefix_cache.probe(
                        self.cfg.name, r.adapter,
                        np.asarray(r.tokens, np.int64))
                if h is None:
                    misses.append(r)
                else:
                    hits.append((r, h))
            if sp:
                sp.end(rids=[r.rid for r in reqs],
                       prompt_lens=[len(r.tokens) for r in reqs],
                       prefix_hits=len(hits))
            if hits:
                self._admit_prefix_hits(hits)
            if not misses:
                return
            if not self._can_bucket:
                for r in misses:
                    self._admit_rows([r])
            else:
                self._admit_rows(misses)

    def _admit_prefix_hits(self, hits: List[Tuple[ServeRequest, Any]]
                           ) -> None:
        """Admit prefix-cache hits: import cached rows, walk the suffix.

        The cached rows land in ONE donated ``fused_scatter`` — the same
        compilation batched migration and the pipeline prefill share, so
        cache imports add zero compiles — with each hit's slot position
        set to its usable prefix length ``k``.  The uncached suffix then
        replays through the already-compiled fused decode step: walk step
        ``i`` feeds suffix token ``i`` of every hit still walking, while
        finished hits and unrelated live slots are frozen by the active
        mask (the existing free-slot mechanism: their pos is restored and
        the garbage write at their uncommitted index is overwritten by
        their next real step).  Each hit therefore emits exactly ONE
        sampled token at admission — the observable shape of a cold
        prefill.  Sampled tokens accumulate on device; a single host read
        at the end picks each hit's first generated token (the sample
        after its last prompt token).  Bit-identity with cold prefill
        rides on the same quantized-sampler argument as snapshot resume:
        rows are exact host copies, and causal attention makes prefix KV
        a function of prefix tokens only.
        """
        P = self.n_slots
        slots_np = np.zeros((P,), np.int32)
        pos_np = np.zeros((P,), np.int32)
        valid_np = np.zeros((P,), bool)
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        assigned: List[Tuple[int, ServeRequest, int, np.ndarray]] = []
        for j, (req, (entry, k)) in enumerate(hits):
            slot = self.free.pop()
            req.slot = slot
            slots_np[j] = slot
            pos_np[j] = k
            valid_np[j] = True
            for kind, leaves in entry.rows.items():
                dst = rows.setdefault(kind, {})
                for leaf, a in leaves.items():
                    if leaf not in dst:
                        dst[leaf] = np.zeros((a.shape[0], P) + a.shape[1:],
                                             a.dtype)
                    dst[leaf][:, j] = a
            assigned.append((slot, req, k,
                             np.asarray(req.tokens, np.int64)[k:]))
        self.cache = self._scatter_fused(
            self.cache, rows, jnp.asarray(slots_np), jnp.asarray(pos_np),
            jnp.asarray(valid_np))
        for _, (entry, _k) in hits:
            self.prefix_cache.release(entry)
        W = max(len(sfx) for _, _, _, sfx in assigned)
        toks = np.zeros((W, P), np.int32)
        act = np.zeros((W, P), bool)
        for slot, _req, _k, sfx in assigned:
            w = len(sfx)
            toks[:w, slot] = sfx
            act[:w, slot] = True
        outs = []
        for i in range(W):
            nxt, self.cache, *_ = self._decode_fused(
                self.params, jnp.asarray(toks[i]), jnp.asarray(act[i]),
                self.cache)
            outs.append(nxt)
        # pbcheck: disable=R2 (designed sync: ONE host read for the whole suffix walk; admission needs the hits' first tokens)
        walked = np.asarray(jnp.stack(outs))
        for slot, req, k, sfx in assigned:
            self.prefix_hits += 1
            self.prefix_hit_tokens += k
            self.n_prefill_tokens += len(sfx)
            tok = int(walked[len(sfx) - 1, slot])
            req.generated.append(tok)
            at_eos = req.eos_id is not None and tok == req.eos_id
            if len(req.generated) >= req.max_new_tokens or at_eos:
                req.done = True
                self.free.append(slot)
                req.slot = -1
            else:
                self.active[slot] = req
        self._io_dirty = True

    def _admit_rows(self, reqs: List[ServeRequest]) -> None:
        bucket = max(self.bucket_for(r) for r in reqs)
        # Row count is pinned to n_slots on the bucketed path so prefill
        # compile counts depend ONLY on the length bucket (the compile-cache
        # contract the CI guard enforces).  Pad rows cost extra FLOPs when
        # admitting fewer requests than slots, but the cost is bounded by
        # n_slots x bucket and the batch dim is underutilized at these
        # sizes anyway; variable row counts would multiply the compile
        # bound by a row-bucket factor.
        P = self.n_slots if self._can_bucket else len(reqs)
        toks = np.zeros((P, bucket), np.int32)
        last_idx = np.zeros((P,), np.int32)
        slots = np.zeros((P,), np.int32)
        valid = np.zeros((P,), bool)
        assigned: List[Tuple[int, int, ServeRequest]] = []
        for i, req in enumerate(reqs):
            t = np.asarray(req.tokens, np.int64)
            if req.generated:
                t = np.concatenate([t, np.asarray(req.generated, np.int64)])
            L = len(t)
            self.n_prefill_tokens += L
            toks[i, :L] = t
            last_idx[i] = L - 1
            slot = self.free.pop()
            req.slot = slot
            slots[i] = slot
            valid[i] = True
            assigned.append((i, slot, req))
        self.n_prefill_padded_tokens += P * bucket
        backend = self._choose_prefill_backend(P, bucket)
        stats: Dict[str, Any] = {}        # the MoE layers' counts
        with tracing.span("pb.prefill") as sp:
            if backend == "pipeline":
                # TTFT-critical cold-start path: the prompt runs the
                # shard_map pipeline belt over the partially-loaded stage
                # chain; the slot write reuses the shared donated scatter
                logits, state = self._pipe_prefill(
                    self.params, {"tokens": jnp.asarray(toks),
                                  "last_index": jnp.asarray(last_idx)})
                self.cache = self._scatter_fused(
                    self.cache, state, jnp.asarray(slots),
                    jnp.asarray(last_idx + 1), jnp.asarray(valid))
                first = self._sampler(logits).astype(jnp.int32)
                self.n_prefill_pipeline += len(reqs)
            else:
                first, self.cache, *moe = self._prefill_fused(
                    self.params, jnp.asarray(toks), jnp.asarray(last_idx),
                    jnp.asarray(slots), jnp.asarray(valid), self.cache)
                stats = moe[0] if moe else {}
                if sp:
                    for v in stats.values():
                        v.copy_to_host_async()
            with tracing.span("pb.prefill.wait"):
                # pbcheck: disable=R2 (designed sync: admission reads first tokens to catch immediate EOS before slot commit)
                first_host = np.asarray(first)
            if sp:
                sp.end(backend=backend, rows=P, bucket=bucket,
                       real_tokens=int(last_idx.sum()) + len(reqs),
                       rids=[r.rid for r in reqs],
                       # pbcheck: disable=R2 (traced calls only: copied to the host beside the first tokens)
                       **{k: int(v) for k, v in stats.items()})
        self.n_prefill_calls += 1
        self.n_prefill_reqs += len(reqs)
        for i, slot, req in assigned:
            tok = int(first_host[i])
            req.generated.append(tok)
            at_eos = req.eos_id is not None and tok == req.eos_id
            if len(req.generated) >= req.max_new_tokens or at_eos:
                req.done = True       # satisfied at admission (re-submit tail)
                self.free.append(slot)
                req.slot = -1
            else:
                self.active[slot] = req
        self._io_dirty = True

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def step(self) -> List[ServeRequest]:
        """One decode step for all active slots; returns finished requests."""
        if not self.active:
            return []        # no sampler/decode work when nothing is active
        t0 = time.perf_counter()
        with tracing.span("pb.decode", t0=t0) as sp:
            if sp:
                sp.meta.update(n_active=len(self.active), cache_lens=[
                    len(r.tokens) + len(r.generated) - 1
                    for r in self.active.values()])
            if self._io_dirty:
                toks = np.zeros((self.n_slots,), np.int32)
                act = np.zeros((self.n_slots,), bool)
                for slot, req in self.active.items():
                    toks[slot] = req.generated[-1]
                    act[slot] = True
                # committed-ness is part of the jit cache key: after a
                # pipeline hand-off the cache is committed, and so is every
                # step's output, so the inputs built here must be too or
                # the next step compiles the decode a second time
                pos = self.cache["pos"]
                if pos.committed:
                    toks, act = jax.device_put((toks, act), pos.sharding)
                self._dev_tokens = jnp.asarray(toks)
                self._dev_active = jnp.asarray(act)
                self._io_dirty = False
            nxt, self.cache, *moe = self._decode_fused(
                self.params, self._dev_tokens, self._dev_active, self.cache)
            stats = moe[0] if moe else {}
            self._dev_tokens = nxt
            kv_meta = bool(sp) and "attn" in self.cache
            if kv_meta:
                # the slots' lengths and the step's MoE counts come to the
                # host with the tokens
                self.cache["pos"].copy_to_host_async()
                for v in stats.values():
                    v.copy_to_host_async()
            with tracing.span("pb.decode.wait"):
                # pbcheck: disable=R2 (designed sync: THE one host transfer per decode step; EOS checks need the token ids)
                nxt_host = np.asarray(nxt)
            if kv_meta:
                sp.meta.update(self._kv_block_meta())
                if stats:
                    # pbcheck: disable=R2 (traced steps only: copied to the host beside the step's tokens)
                    sp.meta.update({k: int(v) for k, v in stats.items()})
                    sp.meta["moe_experts_held"] = self.cfg.n_experts * sum(
                        k == "moe" for k in self.cfg.layer_kinds())
            self.n_decode_steps += 1
            finished = []
            done_slots: List[Tuple[int, ServeRequest]] = []
            for slot, req in list(self.active.items()):
                tok = int(nxt_host[slot])
                req.generated.append(tok)
                at_eos = req.eos_id is not None and tok == req.eos_id
                if len(req.generated) >= req.max_new_tokens or at_eos:
                    req.done = True
                    finished.append(req)
                    done_slots.append((slot, req))
                    del self.active[slot]
                    self.free.append(slot)
            if finished:
                self._io_dirty = True        # active mask changed
                if self.prefix_cache is not None and self._can_bucket:
                    # deposit finished prompts before their slots are
                    # reused (nothing else touches the cache in this step)
                    self._deposit_prefixes(done_slots)
            t1 = time.perf_counter()
            if sp:
                sp.end(t1, rids=[r.rid for r in finished])
        self.decode_time_s += t1 - t0
        return finished

    def _kv_block_meta(self) -> Dict[str, int]:
        """The decode kernel's K/V blocks per layer in the step just run,
        by its own rule: ``kv_blocks`` those it fetched (every slot up to
        its valid length, free slots at the length their last request
        left), ``kv_blocks_all`` those of the whole cache.  With sliding
        layers also, summed over them, ``kv_blocks_window`` (from each
        slot's first row in the window) and ``kv_blocks_nowindow`` (what
        the same layers would fetch without it).  Called after the step,
        before its finished requests leave ``active``."""
        from repro.kernels import decode_attention as dec
        k = self.cache["attn"]["k"]                   # (L, slots, C, Hkv, d)
        C, Hkv, d = k.shape[2:]
        bk = dec.block_k_for(C, Hkv, d, k.dtype.itemsize)
        # pbcheck: disable=R2 (traced steps only: copied to the host beside the step's tokens, so nothing more is waited for)
        pos = np.array(self.cache["pos"])
        pos[list(self.active)] -= 1          # the kernel ran before the step
        out = {"kv_blocks": dec.kv_blocks(pos, C, bk),
               "kv_blocks_all": self.n_slots * -(-C // bk)}
        wins = [self.cfg.layer_window(i) for i in range(self.cfg.n_layers)]
        if any(wins):
            out["kv_blocks_window"] = sum(dec.kv_blocks(
                pos, C, bk, lo=np.maximum(pos - w + 1, 0)) for w in wins if w)
            out["kv_blocks_nowindow"] = out["kv_blocks"] * sum(
                1 for w in wins if w)
        return out

    def _deposit_prefixes(self, pairs: Sequence[Tuple[int, ServeRequest]]
                          ) -> None:
        """Insert finished requests' prompt-prefix KV into the attached
        prefix cache.  Prompts the store already covers are skipped
        BEFORE exporting, so the device->host row transfer only happens
        for genuinely new prefixes; the batched ``export_slots`` keeps it
        to one transfer per kind leaf for the rest."""
        todo: List[Tuple[int, ServeRequest, np.ndarray]] = []
        for slot, req in pairs:
            toks = np.asarray(req.tokens, np.int64)
            if toks.shape[0] < 2:
                continue                 # nothing reusable below 2 tokens
            if self.prefix_cache.covers(self.cfg.name, req.adapter, toks):
                continue
            todo.append((slot, req, toks))
        if not todo:
            return
        snaps = export_slots(self.cache, [s for s, _, _ in todo],
                             arch=self.cfg.name, max_len=self.max_len)
        for (_slot, req, toks), snap in zip(todo, snaps):
            self.prefix_cache.insert(self.cfg.name, req.adapter, toks,
                                     min(toks.shape[0], snap.pos),
                                     rows=snap.rows)

    def drain(self, export_state: bool = True) -> List[ServeRequest]:
        """Pull every in-flight request out of the batch (server crash /
        re-route path): slots are freed, requests keep their generated
        prefix so ``admit`` elsewhere resumes them exactly.

        With ``export_state`` each request also carries a ``KVSnapshot``
        of its slot (per-layer KV/recurrent rows + pos), so a survivor can
        ``import_snapshot`` it into a free slot and continue decoding with
        ZERO re-prefilled tokens instead of recomputing prompt+prefix.
        """
        items = sorted(self.active.items())
        if export_state and items:
            # batched export: one host transfer per kind leaf total
            snaps = export_slots(self.cache, [s for s, _ in items],
                                 arch=self.cfg.name, max_len=self.max_len)
            for (_, req), snap in zip(items, snaps):
                req.snapshot = snap
                # the rows are already on host: deposit the prompt prefix
                # for free (drain insertion — the other half of the
                # completion-time deposit)
                if self.prefix_cache is not None and self._can_bucket:
                    toks = np.asarray(req.tokens, np.int64)
                    if toks.shape[0] >= 2 and not self.prefix_cache.covers(
                            self.cfg.name, req.adapter, toks):
                        self.prefix_cache.insert(
                            self.cfg.name, req.adapter, toks,
                            min(toks.shape[0], snap.pos), rows=snap.rows)
        drained = []
        for slot, req in items:
            req.slot = -1
            self.free.append(slot)
            drained.append(req)
        self.active.clear()
        self._io_dirty = True
        return drained

    def export_snapshot(self, slot: int) -> KVSnapshot:
        """Snapshot ``slot``'s state to host memory (see serving.snapshot)."""
        return export_slot(self.cache, slot, arch=self.cfg.name,
                           max_len=self.max_len)

    def import_snapshot(self, req: ServeRequest, snap: KVSnapshot) -> bool:
        """Resume ``req`` from a migrated snapshot in a free slot.

        The state rows are scattered into the donated cache in one jitted
        call; the request starts decoding from its last sampled token on
        the next ``step`` — no prefill happens.  False if the batch is
        full or the snapshot's shapes don't match this batcher.
        """
        if not self.free:
            return False
        if not snap.compatible_with(self.cache, self.cfg.name, self.max_len):
            return False
        slot = self.free.pop()
        # numpy rows go straight into the jitted call (the transfer happens
        # as part of the one dispatch — no per-leaf host round-trip)
        self.cache = self._import_fused(
            self.cache, snap.rows, jnp.asarray(slot, jnp.int32),
            jnp.asarray(snap.pos, jnp.int32))
        req.slot = slot
        self.active[slot] = req
        self._io_dirty = True
        self.n_migrated_in += 1
        self.migrated_tokens_in += snap.pos
        return True

    def import_snapshots(self, pairs: Sequence[Tuple[ServeRequest,
                                                     KVSnapshot]]
                         ) -> List[ServeRequest]:
        """Batched migration import: N displaced requests' snapshots land
        in ONE donated scatter (one dispatch, one compile shared with the
        other row-scatter users) instead of N sequential
        ``import_snapshot`` calls — the survivor-absorbs-several-victims
        path after a whole-server crash.

        Imports as many pairs as there are free slots / compatible
        snapshots (in order) and returns the requests actually admitted;
        the caller re-routes the rest.
        """
        usable: List[Tuple[ServeRequest, KVSnapshot]] = []
        for req, snap in pairs:
            if len(usable) >= len(self.free):
                break
            if snap is not None and snap.compatible_with(
                    self.cache, self.cfg.name, self.max_len):
                usable.append((req, snap))
        if not usable:
            return []
        P = self.n_slots
        slots = np.zeros((P,), np.int32)
        pos = np.zeros((P,), np.int32)
        valid = np.zeros((P,), bool)
        # stack each leaf's per-request rows (L, ...) -> (L, P, ...); pad
        # rows stay zero and are masked out by ``valid``
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        for kind, leaves in usable[0][1].rows.items():
            rows[kind] = {}
            for leaf, a in leaves.items():
                buf = np.zeros((a.shape[0], P) + a.shape[1:], a.dtype)
                for j, (_, s) in enumerate(usable):
                    buf[:, j] = s.rows[kind][leaf]
                rows[kind][leaf] = buf
        out: List[ServeRequest] = []
        for j, (req, snap) in enumerate(usable):
            slot = self.free.pop()
            slots[j] = slot
            pos[j] = snap.pos
            valid[j] = True
            req.slot = slot
            self.active[slot] = req
            self.n_migrated_in += 1
            self.migrated_tokens_in += snap.pos
            out.append(req)
        self.cache = self._scatter_fused(
            self.cache, rows, jnp.asarray(slots), jnp.asarray(pos),
            jnp.asarray(valid))
        self.n_batched_imports += 1
        self._io_dirty = True
        return out

    def warm_import(self) -> None:
        """Pre-compile the snapshot-import jits (recovery-path warm-up).

        Writes slot 0's own rows back to itself — a semantic no-op — so
        the first real migration pays steady-state import cost, not an
        XLA compile, inside the post-crash TTFT window.  The batched
        scatter is warmed with an all-invalid write for the same reason.
        """
        rows = {kind: {leaf: arr[:, 0]
                       for leaf, arr in self.cache[kind].items()}
                for kind in ("attn", "ssm", "rec") if kind in self.cache}
        self.cache = self._import_fused(
            self.cache, rows, jnp.asarray(0, jnp.int32),
            self.cache["pos"][0])
        zeros = {kind: {leaf: jnp.zeros_like(arr)
                        for leaf, arr in self.cache[kind].items()}
                 for kind in ("attn", "ssm", "rec") if kind in self.cache}
        P = self.n_slots
        self.cache = self._scatter_fused(
            self.cache, zeros, jnp.zeros((P,), jnp.int32),
            jnp.zeros((P,), jnp.int32), jnp.zeros((P,), bool))

    def reconstruct_inflight(self, has_state: Sequence[bool]
                             ) -> Dict[str, float]:
        """Partial-crash recovery (paper §4.4.2) for the live batch: rebuild
        only the layers whose state died, per active slot, via
        ``core.kv_reconstruct.reconstruct_cache`` — attention layers with
        surviving KV get the Q-only recompute, missing layers a full
        per-layer prefill, layers above the deepest missing one are
        untouched.  Requests stay in their slots; decode resumes exactly.
        Returns the summed per-layer work stats."""
        from repro.core.kv_reconstruct import reconstruct_cache
        totals: Dict[str, float] = {}
        if not self.active or all(has_state):
            return totals
        for slot, req in sorted(self.active.items()):
            # tokens processed so far: prompt + generated prefix minus the
            # last sampled token (it is the NEXT decode step's input)
            seq = np.asarray(req.tokens, np.int64)
            tail = req.generated[:-1]
            if tail:
                seq = np.concatenate([seq, np.asarray(tail, np.int64)])
            view = {"pos": self.cache["pos"][slot:slot + 1]}
            for kind in ("attn", "ssm", "rec"):
                if kind in self.cache:
                    view[kind] = {leaf: arr[:, slot:slot + 1]
                                  for leaf, arr in self.cache[kind].items()}
            rebuilt, stats = reconstruct_cache(
                self.cfg, self.params, {"tokens": jnp.asarray(seq)[None]},
                view, has_state, max_len=self.max_len)
            rows = {kind: {leaf: arr[:, 0]
                           for leaf, arr in rebuilt[kind].items()}
                    for kind in ("attn", "ssm", "rec") if kind in rebuilt}
            self.cache = self._import_fused(
                self.cache, rows, jnp.asarray(slot, jnp.int32),
                jnp.asarray(len(seq), jnp.int32))
            for k, v in stats.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            totals["reconstructed_reqs"] = \
                totals.get("reconstructed_reqs", 0.0) + 1.0
        return totals

    def relay_inflight(self, has_state: Sequence[bool]) -> Dict[str, float]:
        """Repartition re-lay of the live batch onto a changed partition:
        rebuild the layers whose KV died for EVERY active slot, then land
        all rebuilt rows in ONE donated scatter (the same ``fused_scatter``
        batched migration uses — no new compile) instead of one import per
        slot.  Slots with equal merged-sequence length share one batched
        ``reconstruct_cache`` call (exact — no padding), so the recompute
        cost scales with the number of distinct lengths, not requests.
        Requests keep their slots and their sampled prefix; decode resumes
        bit-identically with ZERO re-prefilled tokens.  Surviving layers
        are reused verbatim (Q-only recompute where possible), like
        ``reconstruct_inflight``, whose per-layer work stats this returns
        summed over requests, under ``relayed_reqs``."""
        from repro.core.kv_reconstruct import reconstruct_cache
        totals: Dict[str, float] = {}
        if not self.active or all(has_state):
            return totals
        P = self.n_slots
        slots = np.zeros((P,), np.int32)
        pos = np.zeros((P,), np.int32)
        valid = np.zeros((P,), bool)
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        groups: Dict[int, List] = {}
        for j, (slot, req) in enumerate(sorted(self.active.items())):
            seq = np.asarray(req.tokens, np.int64)
            tail = req.generated[:-1]
            if tail:
                seq = np.concatenate([seq, np.asarray(tail, np.int64)])
            groups.setdefault(len(seq), []).append((j, slot, seq))
        for _, members in sorted(groups.items()):
            g_slots = np.asarray([s for _, s, _ in members], np.int32)
            tokens = jnp.asarray(np.stack([q for _, _, q in members]))
            view = {"pos": self.cache["pos"][g_slots]}
            for kind in ("attn", "ssm", "rec"):
                if kind in self.cache:
                    view[kind] = {leaf: arr[:, g_slots]
                                  for leaf, arr in self.cache[kind].items()}
            rebuilt, stats = reconstruct_cache(
                self.cfg, self.params, {"tokens": tokens}, view, has_state,
                max_len=self.max_len)
            for kind in ("attn", "ssm", "rec"):
                if kind not in rebuilt:
                    continue
                dst = rows.setdefault(kind, {})
                for leaf, arr in rebuilt[kind].items():
                    a = np.asarray(arr)
                    if leaf not in dst:
                        dst[leaf] = np.zeros(
                            (a.shape[0], P) + a.shape[2:], a.dtype)
                    for gi, (j, _, _) in enumerate(members):
                        dst[leaf][:, j] = a[:, gi]
            for gi, (j, slot, seq) in enumerate(members):
                slots[j] = slot
                pos[j] = len(seq)
                valid[j] = True
            # per-layer/token work counts are batch-invariant in
            # reconstruct_cache: scale by group size to keep the
            # sum-over-requests semantics of the per-slot path
            for k, v in stats.items():
                totals[k] = totals.get(k, 0.0) + float(v) * len(members)
            totals["relayed_reqs"] = totals.get("relayed_reqs", 0.0) \
                + float(len(members))
        self.cache = self._scatter_fused(
            self.cache, rows, jnp.asarray(slots), jnp.asarray(pos),
            jnp.asarray(valid))
        self.n_relay_scatters += 1
        self._io_dirty = True
        return totals

    @property
    def n_active(self) -> int:
        return len(self.active)

    # ------------------------------------------------------------------
    # instrumentation
    # ------------------------------------------------------------------
    def compile_stats(self) -> Dict[str, int]:
        """XLA compile counts of the two hot-path functions.  The decode
        count must stay 1 for the engine's lifetime (adapter switches swap
        params, never retrace); the prefill count is bounded by the number
        of length buckets actually seen."""
        def _n(fn):
            try:
                return int(fn._cache_size())
            except Exception:       # private API moved — report -1, don't die
                return -1
        return {"decode_compiles": _n(self._decode_fused),
                "prefill_compiles": _n(self._prefill_fused)}

    def hotpath_stats(self) -> Dict[str, float]:
        s: Dict[str, float] = {
            "n_decode_steps": float(self.n_decode_steps),
            "decode_time_s": self.decode_time_s,
            "n_prefill_calls": float(self.n_prefill_calls),
            "n_prefill_reqs": float(self.n_prefill_reqs),
            "n_prefill_pipeline": float(self.n_prefill_pipeline),
            "n_batched_imports": float(self.n_batched_imports),
            "n_relay_scatters": float(self.n_relay_scatters),
            "n_prefill_tokens": float(self.n_prefill_tokens),
            "n_prefill_padded_tokens": float(self.n_prefill_padded_tokens),
            "prefix_hits": float(self.prefix_hits),
            "prefix_hit_tokens": float(self.prefix_hit_tokens),
            "prefix_evictions": (
                0.0 if self.prefix_cache is None
                else float(self.prefix_cache.evictions
                           - self._prefix_evict_base)),
        }
        s.update({k: float(v) for k, v in self.compile_stats().items()})
        return s


class ServingEngine:
    """Request dispatcher + continuous batcher + adapter epochs.

    ``set_params`` supports the PipeBoost adapter switch (merged weights
    swapped between epochs) and the post-recovery parameter refresh.
    """

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 max_len: int = 256,
                 policy: Optional[EpochSchedulerPolicy] = None,
                 adapter_params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.batcher = ContinuousBatcher(cfg, params, n_slots, max_len)
        self.policy = policy or EpochSchedulerPolicy()
        self.policy_state = self.policy.make_state()
        self.adapter_params = adapter_params or {}
        self.base_params = params
        self.active_adapter: Optional[str] = None
        self.clock = 0.0
        self.completed: List[ServeRequest] = []
        self.n_adapter_switches = 0

    def submit(self, req: ServeRequest):
        # stamp fresh requests that carry no arrival of their own; requests
        # with a trace arrival or a generated prefix (re-submits) keep theirs
        if req.arrival is None:
            req.arrival = self.clock
        self.policy.enqueue(self.policy_state, _PolicyItem(req))

    def _switch_adapter(self, name: Optional[str]):
        if name == self.active_adapter:
            return
        # params are a traced argument of the batcher's jitted hot path, so
        # an epoch switch is a pointer swap — no retrace, no recompile
        self.batcher.params = self.base_params if name is None \
            else self.adapter_params[name]
        self.active_adapter = name
        self.n_adapter_switches += 1

    def _admit_pending(self) -> List[ServeRequest]:
        """Admit queued requests per the adapter policy into free slots.

        Epoch barrier: merged-LoRA means a switch swaps the weights for
        EVERY active slot, so a different adapter is only admitted once the
        batch has drained (the paper's epoch semantics, Fig. 5).  Same-bucket
        requests within a policy batch prefill together in one padded call.
        Returns requests already satisfied at admission (re-submitted tails).
        Traced, the ``pb.schedule`` span carries the admitted ``rids`` and
        their queue ``waits`` on the engine's clock (``self.clock``).
        """
        satisfied: List[ServeRequest] = []
        admitted: List[ServeRequest] = []
        with tracing.span("pb.schedule") as sp:
            while self.batcher.free:
                nxt = self.policy.peek_adapter(self.policy_state)
                if nxt is None:
                    break
                nxt_name = None if nxt == "__base__" else nxt
                if self.batcher.active and nxt_name != self.active_adapter:
                    break  # drain before switching (epoch barrier)
                adapter, batch = self.policy.next_batch(self.policy_state)
                if adapter is None:
                    break
                self._switch_adapter(None if adapter == "__base__"
                                     else adapter)
                n_free = len(self.batcher.free)
                if len(batch) > n_free:
                    # policy batch can exceed free slots under staggered
                    # occupancy — hand the tail back for the next tick
                    self.policy.requeue_front(self.policy_state,
                                              batch[n_free:])
                    batch = batch[:n_free]
                groups: Dict[int, List[_PolicyItem]] = {}
                for item in batch:
                    groups.setdefault(self.batcher.bucket_for(item.req),
                                      []).append(item)
                for _, items in sorted(groups.items()):
                    self.batcher.admit_batch([it.req for it in items])
                    if sp:
                        admitted.extend(it.req for it in items)
                    for it in items:
                        if it.req.first_token_at is None:
                            it.req.first_token_at = self.clock
                        if it.req.done:
                            it.req.finished_at = self.clock
                            self.completed.append(it.req)
                            satisfied.append(it.req)
            if admitted:
                sp.end(rids=[r.rid for r in admitted],
                       waits=[self.clock - r.arrival for r in admitted])
        return satisfied

    def step(self, now: Optional[float] = None) -> List[ServeRequest]:
        """One scheduling + decode tick; returns requests finished this tick.

        With ``now`` the caller owns the clock (the cluster router drives
        many servers off one shared clock); without it the engine advances
        its own logical step clock by 1 per decode.
        """
        if now is not None:
            self.clock = now
        finished = self._admit_pending()
        if not self.batcher.active:
            return finished
        done = self.batcher.step()
        if now is None:
            self.clock += 1.0  # logical step clock
        for r in done:
            r.finished_at = self.clock
            self.completed.append(r)
        return finished + done

    def admit_with_state(self, req: ServeRequest) -> bool:
        """Admit a migrated request by importing its ``KVSnapshot`` into a
        free slot — the state-preserving alternative to ``submit`` for
        requests drained off a crashed server.  Zero prompt tokens are
        re-prefilled; decode continues from the request's last sampled
        token.

        Falls back (returns False, snapshot kept) when: no free slot, the
        snapshot's shapes don't match, the request needs an adapter this
        engine doesn't have, or the batch is mid-epoch on a *different*
        adapter (merged-LoRA weights apply to every slot, so importing
        across the epoch barrier would decode with the wrong weights).
        """
        snap = req.snapshot
        if snap is None or not self.batcher.free:
            return False
        name = req.adapter
        if name is not None and name not in self.adapter_params:
            return False
        if self.batcher.active:
            if name != self.active_adapter:
                return False
        else:
            self._switch_adapter(name)
        if not self.batcher.import_snapshot(req, snap):
            return False
        if req.arrival is None:
            req.arrival = self.clock
        req.snapshot = None
        return True

    def admit_with_state_batch(self, reqs: Sequence[ServeRequest]
                               ) -> List[ServeRequest]:
        """Batched ``admit_with_state``: displaced requests sharing an
        adapter import their snapshots in ONE donated scatter (one
        dispatch) instead of one call each — how a survivor absorbs
        several victims of a whole-server crash.  Applies the same guards
        (free slots, shape compatibility, adapter availability, epoch
        barrier) and returns the requests actually admitted; the caller
        falls back to re-prefill for the rest.
        """
        accepted: List[ServeRequest] = []
        groups: Dict[Optional[str], List[ServeRequest]] = {}
        for r in reqs:
            if r.snapshot is not None:
                groups.setdefault(r.adapter, []).append(r)
        for name, group in groups.items():
            if name is not None and name not in self.adapter_params:
                continue
            if self.batcher.active:
                if name != self.active_adapter:
                    continue            # epoch barrier (see admit_with_state)
            else:
                self._switch_adapter(name)
            done = self.batcher.import_snapshots(
                [(r, r.snapshot) for r in group])
            for r in done:
                if r.arrival is None:
                    r.arrival = self.clock
                r.snapshot = None
                accepted.append(r)
        return accepted

    def drain_inflight(self, export_state: bool = True) -> List[ServeRequest]:
        """Remove every in-flight AND queued request (crash re-route path);
        in-flight requests keep their generated prefix — and, with
        ``export_state``, their KV snapshot — for exact resumption on
        another server."""
        out = self.batcher.drain(export_state=export_state)
        while True:
            adapter, batch = self.policy.next_batch(self.policy_state)
            if adapter is None:
                break
            out.extend(item.req for item in batch)
        return out

    def attach_prefix_cache(self, cache) -> None:
        """Attach a cross-request ``PrefixCache`` to the batcher (see
        ContinuousBatcher.attach_prefix_cache)."""
        self.batcher.attach_prefix_cache(cache)

    def reconstruct_inflight(self, has_state) -> Dict[str, float]:
        """Partial-crash in-place rebuild of the live batch's lost layers
        (see ContinuousBatcher.reconstruct_inflight)."""
        return self.batcher.reconstruct_inflight(has_state)

    def relay_inflight(self, has_state) -> Dict[str, float]:
        """Repartition re-lay: rebuild lost layers for the whole live
        batch and land them in one donated scatter (see
        ContinuousBatcher.relay_inflight)."""
        return self.batcher.relay_inflight(has_state)

    # ---- scheduling surface (consumed by cluster/scheduler.py policies) --
    def resident_adapters(self) -> set:
        """Adapters admittable RIGHT NOW without an epoch-switch stall.

        Merged-LoRA semantics: while the batch is busy, only the active
        adapter's weights are merged in — admitting anything else must
        wait for the epoch to drain.  An idle batch can switch to any
        loaded adapter with a pointer swap (params are a traced argument),
        so everything this engine holds is resident.  ``None`` names the
        base model.
        """
        if self.batcher.active:
            return {self.active_adapter}
        return set(self.adapter_params) | {None, self.active_adapter}

    def predicted_step_cost_s(self, default: float = 0.05) -> float:
        """Measured mean wall-clock cost of one decode step (the
        SLO-aware dispatch's unit of predicted work); ``default`` until
        this engine has decoded anything."""
        b = self.batcher
        if b.n_decode_steps > 0 and b.decode_time_s > 0:
            return b.decode_time_s / b.n_decode_steps
        return default

    def queued_requests(self) -> List[ServeRequest]:
        """Requests enqueued but not yet admitted (no first token yet)."""
        out: List[ServeRequest] = []
        for q in self.policy_state.get("queues", {}).values():
            out.extend(it.req for it in q)
        out.extend(it.req for it in self.policy_state.get("fifo", ()))
        return out

    @property
    def n_pending(self) -> int:
        """Queued (not yet admitted) + in-flight requests."""
        return len(self.queued_requests()) + self.batcher.n_active

    def hotpath_stats(self) -> Dict[str, float]:
        return self.batcher.hotpath_stats()

    def run(self, max_steps: int = 10_000) -> List[ServeRequest]:
        """Drain all queues: admit per the adapter policy, decode until done."""
        for _ in range(max_steps):
            self.step()
            if not self.batcher.active \
                    and self.policy.peek_adapter(self.policy_state) is None:
                break
        return self.completed


class _PolicyItem:
    """Adapter-scheduler item wrapping a ServeRequest."""

    def __init__(self, req: ServeRequest):
        self.req = req
        self.adapter = req.adapter or "__base__"
        self.arrival = req.arrival
        self.service = 0.0
