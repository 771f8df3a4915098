"""Memory-efficient pure-JAX attention (the XLA-lowered path).

This is the implementation the distributed steps lower through.  It never
materializes the full (Sq, Sk) score matrix: queries are processed in blocks
and keys are scanned in blocks with online-softmax rescaling (flash-style),
so compiled HBM use stays O(S * d) even at 32k/524k sequence lengths.

The Pallas kernels in ``repro.kernels`` implement the same math as explicit
VMEM-tiled TPU kernels; ``repro.kernels.*.ref`` oracles cross-check both.

Partial-attention form (acc, m, l) is exposed so ring attention
(context-parallel prefill) and sequence-parallel decode can merge partials
across devices.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Pipeline-mode stages see the full (unsharded) sequence and opt into
# sequential q-block chunking to bound peak memory; the standard
# sequence-sharded path keeps q un-chunked (reshape would fight SPMD).
DEFAULT_BLOCK_Q = [0]


class default_block_q:
    def __init__(self, n: int):
        self.n = n

    def __enter__(self):
        self.prev = DEFAULT_BLOCK_Q[0]
        DEFAULT_BLOCK_Q[0] = self.n

    def __exit__(self, *exc):
        DEFAULT_BLOCK_Q[0] = self.prev


class AttnPartial(NamedTuple):
    acc: jnp.ndarray  # (B, Sq, Hq, hd) un-normalized weighted values (f32)
    m: jnp.ndarray    # (B, Sq, Hq) running max of logits (f32)
    l: jnp.ndarray    # (B, Sq, Hq) running sum of exp(logit - m) (f32)


def merge_partials(a: AttnPartial, b: AttnPartial) -> AttnPartial:
    """Associative merge of two online-softmax partial results."""
    m = jnp.maximum(a.m, b.m)
    ea = jnp.exp(a.m - m)
    eb = jnp.exp(b.m - m)
    acc = a.acc * ea[..., None] + b.acc * eb[..., None]
    l = a.l * ea + b.l * eb
    return AttnPartial(acc, m, l)


def finalize_partial(p: AttnPartial, dtype) -> jnp.ndarray:
    l = jnp.where(p.l == 0.0, 1.0, p.l)
    return (p.acc / l[..., None]).astype(dtype)


def _block_mask(q_pos, k_pos, *, causal: bool, window):
    """(Bq, Bk) bool mask: True = attend.  ``window`` is an int or a
    traced scalar (a layer's window in a scan); 0 is full attention."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok = ok & (dk <= dq)
    if not isinstance(window, int):
        ok = ok & ((window <= 0) | (dk > dq - window))
    elif window > 0:
        ok = ok & (dk > dq - window)
    return ok


def attention_partial(
    q: jnp.ndarray,            # (B, Sq, Hq, hd)
    k: jnp.ndarray,            # (B, Sk, Hkv, hd)
    v: jnp.ndarray,            # (B, Sk, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset=0,                # global position of q[0] (int or traced scalar)
    k_offset=0,                # global position of k[0]
    kv_valid_len=None,         # mask k positions >= this (ragged caches)
    kv_slot_mask=None,         # (B, Sk) bool per-slot mask (ring buffers:
                               # validity is per slot, not a prefix length)
    block_k: int = 1024,
    block_q: int = 0,          # opt-in (pipeline full-seq stages): 0 = off —
                               # reshaping a sequence-sharded q breaks SPMD
    scale: Optional[float] = None,
) -> AttnPartial:
    """Blocked online-softmax attention returning mergeable partials.

    GQA: Hq must be a multiple of Hkv; query heads are grouped onto kv heads.
    Long query runs are additionally chunked over ``block_q`` (sequentially,
    via lax.map) so peak memory stays O(block_q * block_k) per head.
    """
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5

    if block_q and Sq > 2 * block_q and Sq % block_q == 0:
        nq = Sq // block_q
        qb = jnp.moveaxis(q.reshape(B, nq, block_q, Hq, hd), 1, 0)

        def one(args):
            qblk, i = args
            return attention_partial(
                qblk, k, v, causal=causal, window=window,
                q_offset=q_offset + i * block_q, k_offset=k_offset,
                kv_valid_len=kv_valid_len, kv_slot_mask=kv_slot_mask,
                block_k=block_k, block_q=0, scale=scale)

        parts = jax.lax.map(one, (qb, jnp.arange(nq)))
        acc = jnp.moveaxis(parts.acc, 0, 1).reshape(B, Sq, Hq, hd)
        m = jnp.moveaxis(parts.m, 0, 1).reshape(B, Sq, Hq)
        l = jnp.moveaxis(parts.l, 0, 1).reshape(B, Sq, Hq)
        return AttnPartial(acc, m, l)

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, hd)
    q_pos = q_offset + jnp.arange(Sq)

    nk = max(1, (Sk + block_k - 1) // block_k)
    block_k = (Sk + nk - 1) // nk
    pad_k = nk * block_k - Sk

    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kb = kp.reshape(B, nk, block_k, Hkv, hd)
    vb = vp.reshape(B, nk, block_k, Hkv, hd)
    if kv_slot_mask is not None:
        smp = jnp.pad(jnp.asarray(kv_slot_mask, bool), ((0, 0), (0, pad_k)))
        smb = jnp.moveaxis(smp.reshape(B, nk, block_k), 1, 0)  # (nk, B, bk)

    def step(carry, blk):
        acc, m, l = carry
        if kv_slot_mask is not None:
            kblk, vblk, kidx, sblk = blk            # ... + (B, bk) slot mask
        else:
            kblk, vblk, kidx = blk                  # (B,bk,Hkv,hd) x2, ()
        k_pos = k_offset + kidx * block_k + jnp.arange(block_k)
        # logits: (B, Sq, Hkv, G, bk)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kblk.astype(jnp.float32))
        mask = _block_mask(q_pos, k_pos, causal=causal, window=window)
        mask = mask & (k_pos < (Sk + k_offset))[None, :]  # kill pad keys
        mask = mask[None, :, None, None, :]               # (1,Sq,1,1,bk)
        if kv_valid_len is not None:
            vl = jnp.asarray(kv_valid_len)
            if vl.ndim == 0:
                mask = mask & (k_pos < vl)[None, None, None, None, :]
            else:  # per-batch valid lengths (continuous batching)
                mask = mask & (k_pos[None, :] < vl[:, None]
                               )[:, None, None, None, :]
        if kv_slot_mask is not None:
            mask = mask & sblk[:, None, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)                 # (B,Sq,Hkv,G)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, Hkv, G, hd), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)                   # (nk, B, bk, Hkv, hd)
    vb_t = jnp.moveaxis(vb, 1, 0)
    xs = (kb_t, vb_t, jnp.arange(nk))
    if kv_slot_mask is not None:
        xs = xs + (smb,)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), xs)
    return AttnPartial(acc.reshape(B, Sq, Hq, hd),
                       m.reshape(B, Sq, Hq), l.reshape(B, Sq, Hq))


def attention(q, k, v, *, causal=True, window=0, q_offset=0, k_offset=0,
              kv_valid_len=None, block_k: int = 1024,
              block_q: Optional[int] = None,
              scale: Optional[float] = None) -> jnp.ndarray:
    """Full attention = finalize(partial). Shapes as attention_partial.

    Long query runs finalize per q-block inside the sequential map, so the
    live intermediates are one block's f32 partials — not the whole
    sequence's (peak-memory critical for the full-seq pipeline stages)."""
    B, Sq, Hq, hd = q.shape
    if block_q is None:
        block_q = DEFAULT_BLOCK_Q[0]
    if block_q and Sq > 2 * block_q and Sq % block_q == 0:
        nq = Sq // block_q
        qb = jnp.moveaxis(q.reshape(B, nq, block_q, Hq, hd), 1, 0)

        def one(args):
            qblk, i = args
            p = attention_partial(qblk, k, v, causal=causal, window=window,
                                  q_offset=q_offset + i * block_q,
                                  k_offset=k_offset,
                                  kv_valid_len=kv_valid_len,
                                  block_k=block_k, block_q=0, scale=scale)
            return finalize_partial(p, q.dtype)

        out = jax.lax.map(one, (qb, jnp.arange(nq)))
        return jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, hd)
    p = attention_partial(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, k_offset=k_offset,
                          kv_valid_len=kv_valid_len, block_k=block_k,
                          block_q=0, scale=scale)
    return finalize_partial(p, q.dtype)


def attention_reference(q, k, v, *, causal=True, window=0, q_offset=0,
                        k_offset=0, kv_valid_len=None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """O(S^2)-memory oracle used only by tests (small shapes)."""
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, hd) * scale
    s = jnp.einsum("bqkgd,bckd->bqkgc", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = k_offset + jnp.arange(Sk)
    mask = _block_mask(q_pos, k_pos, causal=causal, window=window)
    mask = mask[None, :, None, None, :]
    if kv_valid_len is not None:
        vl = jnp.asarray(kv_valid_len)
        if vl.ndim == 0:
            mask = mask & (k_pos < vl)[None, None, None, None, :]
        else:
            mask = mask & (k_pos[None, :] < vl[:, None])[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, hd).astype(q.dtype)


# Decode-attention backend: "auto" routes to the Pallas flash-decode kernel
# (kernels/decode_attention.py, split-K over the cache with per-slot lens
# prefetched as scalars) on TPU and to the XLA online-softmax path
# elsewhere; kernels/ref.py is the shared oracle for both.  The choice is
# made at trace time, so tests forcing an impl must trace inside the
# context manager (plain eager calls do).
DECODE_ATTN_IMPL = ["auto"]      # "auto" | "pallas" | "xla"


class decode_attn_impl:
    """Context manager pinning the decode-attention backend (tests/bench)."""

    def __init__(self, impl: str):
        assert impl in ("auto", "pallas", "xla"), impl
        self.impl = impl

    def __enter__(self):
        self.prev = DECODE_ATTN_IMPL[0]
        DECODE_ATTN_IMPL[0] = self.impl

    def __exit__(self, *exc):
        DECODE_ATTN_IMPL[0] = self.prev


def _use_pallas_decode() -> bool:
    impl = DECODE_ATTN_IMPL[0]
    if impl == "pallas":
        return True
    if impl == "xla":
        return False
    return jax.default_backend() == "tpu"


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, Hq, hd); k/v_cache: (B, C, Hkv, hd); cache_len: () or (B,)
    int32 — valid entries.  With ``window`` > 0 the cache is a ring buffer
    of capacity C == window (positions are irrelevant: softmax is
    permutation-invariant and RoPE was applied before caching).
    """
    if scale is None and _use_pallas_decode():
        from repro.kernels import ops
        B = q.shape[0]
        lens = jnp.broadcast_to(
            jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
        return ops.decode_attention(q, k_cache, v_cache, lens)
    p = attention_partial(q, k_cache, v_cache, causal=False, window=0,
                          kv_valid_len=cache_len, block_k=k_cache.shape[1],
                          scale=scale)
    return finalize_partial(p, q.dtype)


def decode_attention_merged(q, k_cache, v_cache, cache_len, k_new, v_new, *,
                            kv_slot_mask=None, kv_lo=None,
                            scale: Optional[float] = None) -> jnp.ndarray:
    """Zero-copy decode attention: the current token's K/V are merged as an
    online-softmax partial instead of being written into the cache first.

    q: (B, 1, Hq, hd); k/v_cache: (B, C, Hkv, hd) — *without* the current
    token; cache_len: () or (B,) valid old entries; k/v_new: (B, 1, Hkv, hd)
    the current token.  Equivalent to writing k/v_new at position
    ``cache_len`` and attending over ``cache_len + 1`` entries, but the
    cache is only read — the single-row write happens once, outside the
    layer scan, on the donated cache (see transformer.decode_step).

    ``kv_slot_mask`` (B, C) bool extends the zero-copy trick to ring-
    buffered (windowed) caches: slot validity there is not a prefix length
    (the slot the new token will overwrite holds the evicted, out-of-window
    entry and must not be attended).  The mask rides the Pallas kernel's
    split-K blocking too, so the windowed path no longer pins to the XLA
    lowering.

    ``kv_lo`` (B,) int: each slot's first cache row inside a sliding
    window over a full-length cache; rows under it are not attended (and
    the Pallas kernel does not fetch their blocks).
    """
    if scale is None and _use_pallas_decode():
        from repro.kernels import ops
        B = q.shape[0]
        lens = jnp.broadcast_to(
            jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
        return ops.decode_attention(q, k_cache, v_cache, lens,
                                    k_new=k_new, v_new=v_new,
                                    slot_mask=kv_slot_mask, lo=kv_lo)
    if kv_lo is not None:
        in_win = jnp.arange(k_cache.shape[1])[None, :] >= kv_lo[:, None]
        kv_slot_mask = in_win if kv_slot_mask is None \
            else kv_slot_mask & in_win
    p_old = attention_partial(q, k_cache, v_cache, causal=False, window=0,
                              kv_valid_len=cache_len,
                              kv_slot_mask=kv_slot_mask,
                              block_k=k_cache.shape[1], scale=scale)
    p_new = attention_partial(q, k_new, v_new, causal=False, window=0,
                              block_k=1, scale=scale)
    return finalize_partial(merge_partials(p_old, p_new), q.dtype)
