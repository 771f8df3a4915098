"""Model assembly: stacked-layer decoder/encoder covering all assigned
architecture families (dense GQA, MoE, Mamba2 SSD, RG-LRU hybrid, encoder).

Parameters are stored *stacked by layer kind* (leading axis = layer index
within that kind) so the whole stack runs under one ``lax.scan`` — compile
time and HLO size stay flat in depth, and a stacked leading axis reshapes
cleanly into pipeline stages (core/pipeline.py) and planner segments
(core/planner.py).

Three entry points (pure functions of (cfg, params, batch)):
  * ``forward(..., mode="train")``   -> (logits (B,S,V), aux)
  * ``forward(..., mode="prefill")`` -> (last-token logits (B,V), cache)
  * ``decode_step(...)``             -> (logits (B,V), cache)

See ``docs/ARCHITECTURE.md`` § "Models and kernels".
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.context import constrain
from repro.models import attention as attn_lib
from repro.models import mamba2, moe, rglru
from repro.models.layers import (_ACTS, apply_mrope, apply_rope, dense_init,
                                 embed_init, init_mlp, layer_norm, mlp,
                                 rms_norm)

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn_layer(key, cfg: ArchConfig, dtype) -> Params:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    p: Params = {
        "ln1": _norm_init(cfg, D, dtype),
        "wq": dense_init(ks[0], (D, Hq * hd), dtype),
        "wk": dense_init(ks[1], (D, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (D, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (Hq * hd, D), dtype),
        "ln2": _norm_init(cfg, D, dtype),
        "mlp": _init_mlp_for(cfg, ks[4], dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if cfg.attn_out_gate:
        p["wg"] = dense_init(ks[5], (D, Hq * hd), dtype)
    if cfg.sandwich_norm:       # norms of the attention and MLP outputs
        p["post"] = {"ln1": _norm_init(cfg, D, dtype),
                     "ln2": _norm_init(cfg, D, dtype)}
    return p


def _norm_init(cfg, D, dtype):
    if cfg.family == "audio":
        return {"scale": jnp.ones((D,), dtype), "bias": jnp.zeros((D,), dtype)}
    return jnp.ones((D,), dtype)


def _apply_norm(cfg, w, x):
    if cfg.family == "audio":
        return layer_norm(w, x, cfg.norm_eps)
    return rms_norm(w, x, cfg.norm_eps)


def _init_mlp_for(cfg, key, dtype) -> Params:
    if cfg.gated_mlp:
        return init_mlp(key, cfg.d_model, cfg.d_ff, dtype)
    k1, k2 = jax.random.split(key)
    return {"w_up": dense_init(k1, (cfg.d_model, cfg.d_ff), dtype),
            "w_down": dense_init(k2, (cfg.d_ff, cfg.d_model), dtype)}


def _apply_mlp(cfg, p, x):
    if cfg.gated_mlp:
        h = constrain(_ACTS[cfg.act](x @ p["w_gate"]) * (x @ p["w_up"]), "ffh")
        return h @ p["w_down"]
    h = constrain(_ACTS[cfg.act](x @ p["w_up"]), "ffh")
    return h @ p["w_down"]


def _init_moe_layer(key, cfg, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = _init_attn_layer(k1, cfg, dtype)
    p["mlp"] = moe.init_moe_mlp(k2, cfg, dtype)
    return p


def _init_rec_layer(key, cfg, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": _norm_init(cfg, cfg.d_model, dtype),
        "rec": rglru.init_rec_block(k1, cfg, dtype),
        "ln2": _norm_init(cfg, cfg.d_model, dtype),
        "mlp": _init_mlp_for(cfg, k2, dtype),
    }


_LAYER_INIT = {
    "attn": _init_attn_layer,
    "moe": _init_moe_layer,
    "ssm": lambda k, c, d: mamba2.init_ssm_block(k, c, d),
    "rec": _init_rec_layer,
}


def kind_counts(cfg: ArchConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for k in cfg.layer_kinds():
        counts[k] = counts.get(k, 0) + 1
    return counts


def init_params(cfg: ArchConfig, key, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    V, D = cfg.padded_vocab, cfg.d_model
    k_embed, k_head, k_blocks = jax.random.split(key, 3)
    params: Params = {"embed": embed_init(k_embed, (V, D), dtype)}
    blocks: Params = {}
    for kind, n in kind_counts(cfg).items():
        keys = jax.random.split(jax.random.fold_in(
            k_blocks, zlib.crc32(kind.encode()) % 2**31), n)
        blocks[kind] = jax.vmap(
            lambda kk: _LAYER_INIT[kind](kk, cfg, dtype))(keys)
    params["blocks"] = blocks
    params["final_norm"] = _norm_init(cfg, D, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (D, V), dtype)
    return params


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def attn_cache_capacity(cfg: ArchConfig, max_len: int) -> int:
    """Ring-buffer capacity: the window for local attention, else max_len."""
    if cfg.attn_window > 0:
        return min(max_len, cfg.attn_window)
    return max_len


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> Cache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    counts = kind_counts(cfg)
    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    hd = cfg.resolved_head_dim
    n_attnlike = counts.get("attn", 0) + counts.get("moe", 0)
    if n_attnlike:
        C = attn_cache_capacity(cfg, max_len)
        cache["attn"] = {
            "k": jnp.zeros((n_attnlike, batch, C, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((n_attnlike, batch, C, cfg.n_kv_heads, hd), dtype),
        }
    if "ssm" in counts:
        L = counts["ssm"]
        ch = cfg.d_inner + 2 * cfg.ssm_state
        cache["ssm"] = {
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, ch), dtype),
            "state": jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32),
        }
    if "rec" in counts:
        L = counts["rec"]
        W = cfg.lru_width or cfg.d_model
        cache["rec"] = {
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, W), dtype),
            "h": jnp.zeros((L, batch, W), jnp.float32),
        }
    return cache


# ---------------------------------------------------------------------------
# Per-layer forwards
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, h):
    B, S, _ = h.shape
    hd = cfg.resolved_head_dim
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _rope(cfg, x, positions):
    """positions: (B, S) int or (B, S, 3) for mrope."""
    if cfg.mrope:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def _rope_if(cfg, x, positions, use):
    """RoPE, or none on a layer whose (scanned) flag ``use`` is off."""
    r = _rope(cfg, x, positions)
    return r if use is None else jnp.where(use, r, x)


def _attn_out(cfg, p, o, h, x):
    """The attention branch's output added to the residual ``x``: the
    output gate (on the normed input ``h``), wo, and the post norm."""
    o = o.reshape(*x.shape[:2], -1)
    if cfg.attn_out_gate:
        o = o * jax.nn.sigmoid((h @ p["wg"]).astype(jnp.float32)
                               ).astype(o.dtype)
    o = o @ p["wo"]
    if cfg.sandwich_norm:
        o = _apply_norm(cfg, p["post"]["ln1"], o)
    return x + o


def _ffn_branch(cfg, p, x, token_mask=None):
    """(x + the MLP or MoE branch, aux loss, MoE counts or {})."""
    h2 = _apply_norm(cfg, p["ln2"], x)
    if "router" in p["mlp"]:
        y, aux, counts = moe.moe_mlp(cfg, p["mlp"], h2, _ACTS[cfg.act],
                                     token_mask)
    else:
        y, aux, counts = _apply_mlp(cfg, p["mlp"], h2), \
            jnp.zeros((), jnp.float32), {}
    if cfg.sandwich_norm:
        y = _apply_norm(cfg, p["post"]["ln2"], y)
    return x + y, aux, counts


def attn_layer_fwd(cfg, p, x, positions, *, kv_write: Optional[int] = None,
                   window=None, rope=None, token_mask=None):
    """Full-sequence attention layer.  Returns (x, (k, v), stats): roped
    k/v for the cache when prefilling (kv_write = capacity) else None, and
    ``stats`` the aux loss (``aux``) and, for a MoE layer, its counts.

    ``window`` / ``rope`` (traced per-layer values in a scan of mixed
    layers) override ``cfg.attn_window`` and RoPE-everywhere; tokens
    outside ``token_mask`` (B, S) are routed to no expert."""
    h = _apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(cfg, p, h)
    q = constrain(_rope_if(cfg, q, positions, rope), "heads")
    k = constrain(_rope_if(cfg, k, positions, rope), "heads")
    v = constrain(v, "heads")
    o = attn_lib.attention(q, k, v, causal=cfg.causal,
                           window=cfg.attn_window if window is None
                           else window)
    x = constrain(_attn_out(cfg, p, o, h, x), "act")
    x, aux, counts = _ffn_branch(cfg, p, x, token_mask)
    x = constrain(x, "act")
    kv = None
    if kv_write is not None:
        S = k.shape[1]
        if kv_write >= S:
            pad = kv_write - S
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            # Ring buffer smaller than the prompt: keep the tail, placed so
            # slot j holds position p with p % cap == j (decode writes at
            # pos % cap, so the oldest entry is always the one overwritten).
            shift = (S - kv_write) % kv_write
            kc = jnp.roll(k[:, S - kv_write:], shift, axis=1)
            vc = jnp.roll(v[:, S - kv_write:], shift, axis=1)
        kv = (kc, vc)
    return x, kv, dict(counts, aux=aux)


def attn_layer_step(cfg, p, x, position, k_cache, v_cache, cache_len, *,
                    zero_copy: bool = False, window=None, rope=None,
                    token_mask=None):
    """Single-token step. x: (B, 1, D); caches (B, C, kv, hd);
    cache_len: (B,) per-slot valid lengths (continuous batching).
    Returns (x, k, v, MoE counts or {}).

    ``zero_copy=False`` (legacy copy path): the current token's K/V are
    written into the cache here and the updated cache-sized arrays are
    returned — the classic copy-per-layer loop.

    ``zero_copy=True``: the cache is only *read*; the current token is
    merged into the softmax as an online partial
    (``decode_attention_merged``) and only its (B, kv, hd) K/V row is
    returned.  The caller performs one scatter of all layers' rows into
    the donated cache after the layer scan — decode stops rewriting
    cache-sized buffers every layer.  Ring-buffered (windowed) caches ride
    the same path: eviction becomes a per-slot mask on the read (the slot
    the new row will land in holds the evicted, out-of-window entry), and
    the post-scan scatter at ``pos % C`` performs the overwrite.

    ``window`` (a layer's sliding window over a full-length cache, traced
    in a scan; 0 = full) gives each slot its first row in the window,
    ``cache_len - window + 1``, so older rows are neither attended nor
    fetched; ``rope`` and ``token_mask`` (B,) as in ``attn_layer_fwd``.
    """
    h = _apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(cfg, p, h)
    pos2d = position if position.ndim >= 2 else position[:, None]
    q = _rope_if(cfg, q, pos2d if not cfg.mrope else position, rope)
    k = _rope_if(cfg, k, pos2d if not cfg.mrope else position, rope)
    B, C = k_cache.shape[:2]
    if zero_copy:
        valid_old = jnp.minimum(cache_len, C)
        slot_mask = None
        if cfg.attn_window > 0:
            # ring invariant: slot j holds the latest position p < pos with
            # p % C == j.  Once the ring is full the slot the new token
            # overwrites (pos % C) holds position pos - C — exactly one
            # step outside the window — so it must not be attended.
            j = jnp.arange(C)[None, :]
            p_len = cache_len[:, None]
            slot_mask = (j < p_len) & ((p_len < C) | (j != jnp.mod(p_len, C)))
        lo = None
        if window is not None:
            lo = jnp.where(window > 0,
                           jnp.maximum(cache_len - window + 1, 0), 0)
        o = attn_lib.decode_attention_merged(q, k_cache, v_cache, valid_old,
                                             k, v, kv_slot_mask=slot_mask,
                                             kv_lo=lo)
        kv_out = (k[:, 0], v[:, 0])
    else:
        slot = jnp.mod(cache_len, C)      # == cache_len when C >= max_len
        bidx = jnp.arange(B)
        k_cache = k_cache.at[bidx, slot].set(k[:, 0])
        v_cache = v_cache.at[bidx, slot].set(v[:, 0])
        valid = jnp.minimum(cache_len + 1, C)
        o = attn_lib.decode_attention(q, k_cache, v_cache, valid)
        kv_out = (k_cache, v_cache)
    x = _attn_out(cfg, p, o, h, x)
    x, _, counts = _ffn_branch(
        cfg, p, x, None if token_mask is None else token_mask[:, None])
    return x, kv_out[0], kv_out[1], counts


def rec_layer_fwd(cfg, p, x, *, conv_state=None, h0=None, want_state=False):
    h = _apply_norm(cfg, p["ln1"], x)
    y, (conv_s, h_last) = rglru.rec_block_fwd(cfg, p["rec"], h,
                                              conv_state=conv_state, h0=h0)
    x = constrain(x + y, "act")
    h2 = _apply_norm(cfg, p["ln2"], x)
    x = constrain(x + _apply_mlp(cfg, p["mlp"], h2), "act")
    return x, (conv_s, h_last) if want_state else None


def rec_layer_step(cfg, p, x, conv_state, h):
    hin = _apply_norm(cfg, p["ln1"], x)
    y, (conv_s, h_new) = rglru.rec_block_step(cfg, p["rec"], hin[:, 0, :],
                                              conv_state, h)
    x = x + y[:, None, :]
    h2 = _apply_norm(cfg, p["ln2"], x)
    x = x + _apply_mlp(cfg, p["mlp"], h2)
    return x, conv_s, h_new


# ---------------------------------------------------------------------------
# Full-model forward
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def embed_tokens(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x (B,S,D), positions)."""
    if "embeds" in batch:
        x = batch["embeds"]
    else:
        x = _embed(cfg, params, batch["tokens"])
    if "positions" in batch:
        positions = batch["positions"]
    else:
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    return constrain(x, "act"), positions


def unembed(cfg, params, x) -> jnp.ndarray:
    import os
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if os.environ.get("REPRO_BF16_LOGITS"):
        # halve CE-section wire/HBM traffic; logsumexp still runs f32
        lg = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
        return constrain(lg, "logits")
    return constrain(jnp.einsum("bsd,dv->bsv", x, head,
                                preferred_element_type=jnp.float32), "logits")


def _per_layer(cfg, g0: int, count: int):
    """Scanned per-layer (window, rope) of layers g0 .. g0+count-1, or None
    where the stack has one window and RoPE everywhere."""
    if not (cfg.layer_windows or cfg.layer_rope):
        return None
    idx = range(g0, g0 + count)
    return (jnp.asarray([cfg.layer_window(i) for i in idx], jnp.int32),
            jnp.asarray([cfg.layer_uses_rope(i) for i in idx]))


def _add_moe_counts(total: Dict, counts: Dict) -> None:
    """Add a run's per-layer MoE counts, summed over its layers, to
    ``total`` (as ``moe_pairs``, ``moe_experts_hit``)."""
    for k, v in counts.items():
        if k != "aux":
            total[f"moe_{k}"] = total.get(f"moe_{k}", 0) + jnp.sum(v)


def forward(cfg: ArchConfig, params: Params, batch: Dict, *,
            mode: str = "train", max_len: Optional[int] = None,
            remat: bool = False, unroll: int = 1,
            last_index=None, token_mask=None,
            return_stats: bool = False) -> Tuple[jnp.ndarray, Any]:
    """Full-sequence forward.

    mode="train":   returns (logits (B,S,V) f32, aux_loss scalar)
    mode="prefill": returns (last logits (B,V) f32, cache)

    ``last_index`` (B,) int32, prefill only: per-row index of the true last
    prompt token for right-padded (bucketed) prompts.  Logits are gathered
    there and ``cache["pos"]`` is set to ``last_index + 1`` so decode
    attention masks the pad K/V.  Only valid for models whose per-token
    state is causal and batch-row-independent (attention and dropless MoE
    with a full-length cache); SSM/recurrent running states would
    integrate the pad tokens — callers gate on that (see serving.engine).

    ``token_mask`` (B, S) bool: tokens MoE layers route at all (padding is
    routed nowhere).  ``return_stats`` adds a third output, the MoE layers'
    counts summed over layers (``moe_pairs``, ``moe_experts_hit``; empty
    without MoE layers).
    """
    assert mode in ("train", "prefill")
    x, positions = embed_tokens(cfg, params, batch)
    B, S = x.shape[:2]
    kinds = cfg.layer_kinds()
    want_cache = mode == "prefill"
    max_len = max_len or S
    cap = attn_cache_capacity(cfg, max_len) if want_cache else None

    aux_total = jnp.zeros((), jnp.float32)
    stats: Dict[str, jnp.ndarray] = {}
    kv_stack = {"k": [], "v": []}
    ssm_states: Dict[str, list] = {"conv": [], "state": []}
    rec_states: Dict[str, list] = {"conv": [], "h": []}

    def attn_body(x, xs):
        p_l, win, rope = xs if len(xs) == 3 else (xs[0], None, None)
        x, kv, st = attn_layer_fwd(cfg, p_l, x, positions,
                                   kv_write=cap if want_cache else None,
                                   window=win, rope=rope,
                                   token_mask=token_mask)
        outs = (kv if kv is not None else (), st)
        return x, outs

    def ssm_body(x, p_l):
        x, (conv_s, state) = mamba2.ssm_block_fwd(cfg, p_l, x)
        return x, ((conv_s, state) if want_cache else ())

    def rec_body(x, p_l):
        x, st = rec_layer_fwd(cfg, p_l, x, want_state=True)
        return x, (st if want_cache else ())

    bodies = {"attn": attn_body, "moe": attn_body, "ssm": ssm_body,
              "rec": rec_body}

    # Group maximal runs of the same kind and scan each run over its stacked
    # params (hybrid patterns become several short scans over slices);
    # per-layer windows and RoPE flags ride the scan beside the params.
    runs = _kind_runs(kinds)
    kind_cursor: Dict[str, int] = {}
    g0 = 0
    for kind, count in runs:
        start = kind_cursor.get(kind, 0)
        kind_cursor[kind] = start + count
        stacked = jax.tree.map(lambda a: a[start:start + count],
                               params["blocks"][kind])
        per = _per_layer(cfg, g0, count) if kind in ("attn", "moe") \
            else None
        g0 += count
        body = bodies[kind]
        if remat:
            body = jax.checkpoint(body)
        xs = (stacked,) + (per or ()) if kind in ("attn", "moe") else stacked
        x, outs = jax.lax.scan(body, x, xs, unroll=unroll)
        if kind in ("attn", "moe"):
            if want_cache:
                kv, st = outs
                kv_stack["k"].append(kv[0])
                kv_stack["v"].append(kv[1])
            else:
                _, st = outs
            aux_total = aux_total + jnp.sum(st["aux"])
            _add_moe_counts(stats, st)
        elif kind == "ssm" and want_cache:
            conv_s, state = outs
            ssm_states["conv"].append(conv_s)
            ssm_states["state"].append(state)
        elif kind == "rec" and want_cache:
            conv_s, h_last = outs
            rec_states["conv"].append(conv_s)
            rec_states["h"].append(h_last)

    x = _apply_norm(cfg, params["final_norm"], x)

    if mode == "train":
        return unembed(cfg, params, x), aux_total

    if last_index is not None:
        li = jnp.asarray(last_index, jnp.int32)
        x_last = x[jnp.arange(B), li]                 # (B, D)
        logits = unembed(cfg, params, x_last[:, None, :])[:, 0, :]
        cache: Cache = {"pos": li + 1}
    else:
        logits = unembed(cfg, params, x[:, -1:, :])[:, 0, :]
        cache = {"pos": jnp.full((B,), S, jnp.int32)}
    if kv_stack["k"]:
        cache["attn"] = {"k": jnp.concatenate(kv_stack["k"], axis=0),
                         "v": jnp.concatenate(kv_stack["v"], axis=0)}
    if ssm_states["conv"]:
        cache["ssm"] = {"conv": jnp.concatenate(ssm_states["conv"], axis=0),
                        "state": jnp.concatenate(ssm_states["state"], axis=0)}
    if rec_states["conv"]:
        cache["rec"] = {"conv": jnp.concatenate(rec_states["conv"], axis=0),
                        "h": jnp.concatenate(rec_states["h"], axis=0)}
    if return_stats:
        return logits, cache, stats
    return logits, cache


def _kind_runs(kinds):
    runs = []
    for k in kinds:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return [(k, n) for k, n in runs]


def decode_step(cfg: ArchConfig, params: Params, batch: Dict,
                cache: Cache, *, unroll: int = 1, token_mask=None,
                return_stats: bool = False) -> Tuple[jnp.ndarray, Cache]:
    """One autoregressive step.

    batch: {"tokens": (B,) int32} or {"embeds": (B, 1, D)}
           (+ "positions": (B, 1) or (B, 1, 3) for mrope).
    Returns (logits (B, V) f32, new cache); ``token_mask`` and
    ``return_stats`` as in ``forward`` (the mask is (B,)).
    """
    assert cfg.has_decode, f"{cfg.name} is encoder-only"
    pos = cache["pos"]  # (B,) per-slot positions
    if "embeds" in batch:
        x = batch["embeds"]
        B = x.shape[0]
    else:
        toks = batch["tokens"].reshape(-1)
        x = _embed(cfg, params, toks[:, None])
        B = toks.shape[0]
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = pos[:, None]

    kinds = cfg.layer_kinds()
    runs = _kind_runs(kinds)
    kind_cursor: Dict[str, int] = {}
    new_cache: Cache = {"pos": pos + 1}
    stats: Dict[str, jnp.ndarray] = {}
    # collect per-kind outputs across runs, then reassemble stacks
    collected: Dict[str, list] = {k: [] for k in ("ssm_conv", "ssm_state",
                                                  "rec_conv", "rec_h")}
    # attn/moe share the "attn" cache stack; track separate cursor
    attnlike_cursor = 0
    # A single attention run scans its cache slices and one scatter after
    # the scan writes every layer's new row.  Several runs (dense layers
    # before MoE ones) carry the whole stacked cache through their scans
    # and write each layer's row in place as they go: a slice of the
    # cache fed to a scan, or the cache read by several scans and written
    # after them, would be copied whole.
    kv_carry = sum(1 for k, _ in runs if k in ("attn", "moe")) > 1
    if kv_carry:
        kc_all, vc_all = cache["attn"]["k"], cache["attn"]["v"]
        slot_all = jnp.mod(pos, kc_all.shape[2])
    g0 = 0

    for kind, count in runs:
        start = kind_cursor.get(kind, 0)
        kind_cursor[kind] = start + count
        stacked = jax.tree.map(lambda a: a[start:start + count],
                               params["blocks"][kind])
        if kind in ("attn", "moe"):
            a0 = attnlike_cursor
            attnlike_cursor += count
            per = _per_layer(cfg, g0, count)
            # Zero-copy hot path: the scan only READS the cache and emits
            # each layer's new (B, kv, hd) row; the rows land in the
            # donated cache in place (an O(L*B)-row update instead of an
            # O(cache-size) rewrite per layer).  Ring-buffer (windowed)
            # caches use the same path: the merged partial masks out the
            # slot being evicted (attn_layer_step builds the per-slot
            # mask) and the row write at pos % C is the eviction write.
            if kv_carry:
                xs = (stacked, jnp.arange(a0, a0 + count))
            else:
                xs = (stacked, cache["attn"]["k"][a0:a0 + count],
                      cache["attn"]["v"][a0:a0 + count])
            if per is not None:
                xs += per                       # (window, rope) a layer

            def body(carry, xs):
                win = rope = None
                if per is not None:
                    xs, (win, rope) = xs[:-2], xs[-2:]
                if kv_carry:
                    (p_l, li), (x, kc, vc) = xs, carry
                    k_l, v_l = kc[li], vc[li]
                else:
                    (p_l, k_l, v_l), x = xs, carry
                x, k_l, v_l, counts = attn_layer_step(
                    cfg, p_l, x, positions, k_l, v_l, pos, zero_copy=True,
                    window=win, rope=rope, token_mask=token_mask)
                if not kv_carry:
                    return x, (k_l, v_l, counts)
                bidx = jnp.arange(B)
                kc = kc.at[li, bidx, slot_all].set(k_l)
                vc = vc.at[li, bidx, slot_all].set(v_l)
                return (x, kc, vc), counts

            if kv_carry:
                (x, kc_all, vc_all), counts = jax.lax.scan(
                    body, (x, kc_all, vc_all), xs, unroll=unroll)
                new_cache["attn"] = {"k": kc_all, "v": vc_all}
            else:
                x, (kn, vn, counts) = jax.lax.scan(body, x, xs,
                                                   unroll=unroll)
                kc, vc = cache["attn"]["k"], cache["attn"]["v"]
                slot = jnp.mod(pos, kc.shape[2])
                bidx = jnp.arange(B)
                # (L, B, kv, hd) rows of every attention layer, one scatter
                new_cache["attn"] = {"k": kc.at[:, bidx, slot].set(kn),
                                     "v": vc.at[:, bidx, slot].set(vn)}
            _add_moe_counts(stats, counts)
        elif kind == "ssm":
            cv = cache["ssm"]["conv"][start:start + count]
            st = cache["ssm"]["state"][start:start + count]

            def body(x, per):
                p_l, cv_l, st_l = per
                y, (cv_l, st_l) = mamba2.ssm_block_step(cfg, p_l, x[:, 0, :],
                                                        cv_l, st_l)
                return y[:, None, :], (cv_l, st_l)

            x, (cv, st) = jax.lax.scan(body, x, (stacked, cv, st), unroll=unroll)
            collected["ssm_conv"].append(cv)
            collected["ssm_state"].append(st)
        elif kind == "rec":
            cv = cache["rec"]["conv"][start:start + count]
            hh = cache["rec"]["h"][start:start + count]

            def body(x, per):
                p_l, cv_l, h_l = per
                x, cv_l, h_l = rec_layer_step(cfg, p_l, x, cv_l, h_l)
                return x, (cv_l, h_l)

            x, (cv, hh) = jax.lax.scan(body, x, (stacked, cv, hh), unroll=unroll)
            collected["rec_conv"].append(cv)
            collected["rec_h"].append(hh)
        g0 += count

    x = _apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params, x)[:, 0, :]

    if collected["ssm_conv"]:
        new_cache["ssm"] = {"conv": jnp.concatenate(collected["ssm_conv"], 0),
                            "state": jnp.concatenate(collected["ssm_state"], 0)}
    if collected["rec_conv"]:
        new_cache["rec"] = {"conv": jnp.concatenate(collected["rec_conv"], 0),
                            "h": jnp.concatenate(collected["rec_h"], 0)}
    if return_stats:
        return logits, new_cache, stats
    return logits, new_cache
