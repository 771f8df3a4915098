"""KV-cache / recurrent-state reconstruction after a crash (paper §4.4.2).

Given the merged token sequence processed so far (prompt + generated) and a
per-layer "has state" mask, rebuild the missing per-layer caches:

  * attention layers WITH KV: recompute only Q over the full sequence and
    attend against the surviving cache (K/V projections skipped) — exact,
    because cached K/V equal what a recompute would produce;
  * attention layers WITH KV but a *wrapped* ring buffer (windowed cache,
    sequence longer than the window): positions older than the ring were
    evicted, so Q-only reuse can't reproduce their outputs — the layer's
    activations are recomputed in full while the surviving ring is kept;
  * attention layers WITHOUT KV: full prefill for that layer, cache stored;
  * SSM / RG-LRU layers WITHOUT state: full re-scan (there is no per-position
    memo to reuse — see DESIGN.md §5 mamba2 note); layers WITH state above
    the deepest missing layer are left untouched (their state is still valid).

Reconstruction stops at the deepest missing layer: everything above it kept
its state, so the decode queue can resume immediately after
(paper Fig. 7b: decode requests detour through the prefill queue and return).

See ``docs/ARCHITECTURE.md`` § "Core: the PipeBoost engine".
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import mamba2, transformer
from repro.models.transformer import (_apply_norm, _project_qkv, _rope,
                                      attn_cache_capacity, rec_layer_fwd)


def _layer_params(params, kind: str, idx: int):
    return jax.tree.map(lambda a: a[idx], params["blocks"][kind])


def _kind_indices(cfg) -> List[Tuple[str, int, int]]:
    """[(kind, index_within_kind, index_within_attnlike_cache), ...] in
    global layer order.  attn and moe share the 'attn' cache stack."""
    out = []
    per_kind: Dict[str, int] = {}
    attnlike = 0
    for kind in cfg.layer_kinds():
        i = per_kind.get(kind, 0)
        per_kind[kind] = i + 1
        if kind in ("attn", "moe"):
            out.append((kind, i, attnlike))
            attnlike += 1
        else:
            out.append((kind, i, -1))
    return out


def reconstruct_cache(cfg: ArchConfig, params, batch: Dict,
                      cache: Dict, has_state: Sequence[bool],
                      max_len: Optional[int] = None) -> Tuple[Dict, Dict[str, int]]:
    """Rebuild missing per-layer state. ``has_state[i]`` is per *global*
    layer.  Returns (new_cache, stats) where stats counts the work done.

    ``batch`` carries the merged sequence ({"tokens": (B, S)} or embeds).
    The returned cache is exactly equal (up to fp) to a fresh prefill cache.
    """
    # the Q-only path below rebuilds attention without per-layer windows,
    # RoPE flags, the output gate or the branch norms
    assert not (cfg.layer_windows or cfg.layer_rope or cfg.attn_out_gate
                or cfg.sandwich_norm), f"{cfg.name}: no rebuild path"
    x, positions = transformer.embed_tokens(cfg, params, batch)
    B, S = x.shape[:2]
    max_len = max_len or S
    cap = attn_cache_capacity(cfg, max_len)
    kinds = _kind_indices(cfg)
    assert len(has_state) == len(kinds)
    deepest_missing = max((i for i, h in enumerate(has_state) if not h),
                          default=-1)
    stats = {"layers_recomputed": 0, "kv_reused": 0, "full_prefill": 0,
             "window_recompute": 0, "layers_skipped": 0,
             # token-granular work counts (surface in cluster metrics):
             # q_only_tokens  — positions whose K/V were reused (Q recomputed)
             # prefill_tokens — positions run through a full layer forward
             #                  (missing layers AND wrapped-ring recomputes)
             "q_only_tokens": 0, "prefill_tokens": 0}

    new_cache = {k: (dict(v) if isinstance(v, dict) else v)
                 for k, v in cache.items()}
    new_cache["pos"] = jnp.full((B,), S, jnp.int32)

    for gi, (kind, ki, ai) in enumerate(kinds):
        if gi > deepest_missing:
            stats["layers_skipped"] += len(kinds) - gi
            break
        p_l = _layer_params(params, kind, ki)
        if kind in ("attn", "moe"):
            if has_state[gi] and cfg.attn_window > 0 and S > cap:
                # Wrapped ring: positions older than S - cap were evicted,
                # so Q-only reuse cannot reproduce their outputs (a query's
                # window would attend keys that no longer exist).  The
                # surviving ring stays as-is (it IS still exact for
                # decode); the layer's *activations* are recomputed in
                # full so deeper rebuilds see correct inputs.
                x, _, _ = transformer.attn_layer_fwd(cfg, p_l, x, positions)
                stats["window_recompute"] += 1
                stats["prefill_tokens"] += S
            elif has_state[gi]:
                # Q-only recompute against the surviving cache (exact reuse)
                h = _apply_norm(cfg, p_l["ln1"], x)
                q, _, _ = _project_qkv(cfg, p_l, h)
                q = _rope(cfg, q, positions)
                kc = cache["attn"]["k"][ai]
                vc = cache["attn"]["v"][ai]
                if cfg.attn_window > 0:
                    o = _windowed_ring_attention(cfg, q, kc, vc, S)
                else:
                    p = attn_lib.attention_partial(
                        q, kc[:, :S], vc[:, :S], causal=True, window=0)
                    o = attn_lib.finalize_partial(p, q.dtype)
                o = o.reshape(B, S, -1) @ p_l["wo"]
                x, _, _ = transformer._ffn_branch(cfg, p_l, x + o)
                stats["kv_reused"] += 1
                stats["q_only_tokens"] += S
            else:
                x, kv, _ = transformer.attn_layer_fwd(cfg, p_l, x, positions,
                                                      kv_write=cap)
                new_cache["attn"]["k"] = new_cache["attn"]["k"].at[ai].set(kv[0])
                new_cache["attn"]["v"] = new_cache["attn"]["v"].at[ai].set(kv[1])
                stats["full_prefill"] += 1
                stats["prefill_tokens"] += S
        elif kind == "ssm":
            x, (conv_s, state) = mamba2.ssm_block_fwd(cfg, p_l, x)
            if not has_state[gi]:
                new_cache["ssm"]["conv"] = new_cache["ssm"]["conv"].at[ki].set(conv_s)
                new_cache["ssm"]["state"] = new_cache["ssm"]["state"].at[ki].set(state)
                stats["full_prefill"] += 1
                stats["prefill_tokens"] += S
        elif kind == "rec":
            x, st = rec_layer_fwd(cfg, p_l, x, want_state=True)
            if not has_state[gi]:
                new_cache["rec"]["conv"] = new_cache["rec"]["conv"].at[ki].set(st[0])
                new_cache["rec"]["h"] = new_cache["rec"]["h"].at[ki].set(st[1])
                stats["full_prefill"] += 1
                stats["prefill_tokens"] += S
        stats["layers_recomputed"] += 1
    return new_cache, stats


def _windowed_ring_attention(cfg, q, kc, vc, S):
    """Attention of full-sequence Q against a ring-buffered local cache.

    The ring holds the last ``cap`` (roped) keys in rotated order.  Query at
    global position t may attend to keys with position in (t-window, t].
    We reconstruct each ring slot's global position from S and the slot
    index, then mask per-query.
    """
    B, _, Hq, hd = q.shape
    cap = kc.shape[1]
    S = q.shape[1]
    ring_positions = _ring_slot_positions(S, cap)
    qf = (q.astype(jnp.float32) * hd ** -0.5)
    Hkv = kc.shape[2]
    G = Hq // Hkv
    qf = qf.reshape(B, S, Hkv, G, hd)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kc.astype(jnp.float32))
    q_pos = jnp.arange(S)
    ok = (ring_positions[None, :] <= q_pos[:, None]) & \
         (ring_positions[None, :] > q_pos[:, None] - cfg.attn_window) & \
         (ring_positions[None, :] >= 0)
    s = jnp.where(ok[None, :, None, None, :], s, attn_lib.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok[None, :, None, None, :], p, 0.0)
    o = jnp.einsum("bqkgc,bckd->bqkgd", p, vc.astype(jnp.float32))
    return o.reshape(B, S, Hq, hd).astype(q.dtype)


def _ring_slot_positions(S: int, cap: int) -> jnp.ndarray:
    """Global position held by each ring slot after S writes (-1 if empty)."""
    slots = jnp.arange(cap)
    if S >= cap:
        # slot j holds the largest p < S with p % cap == j
        return S - 1 - jnp.mod(jnp.asarray(S - 1) - slots, cap)
    return jnp.where(slots < S, slots, -1)
