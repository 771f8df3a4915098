"""Flash-decode attention — Pallas TPU kernel.

One new query token against a long KV cache (the serving decode loop).
Split-K over the cache: grid (B, n_k), one program per (slot, cache
block) for all heads, with the cache-block dimension innermost and
sequential; online-logsumexp partials merge in VMEM scratch.  Each
program fetches one (Hkv, block_k, d) block of K and of V and updates the
softmax state of all Hkv x G query rows (G = Hq // Hkv) with two batched
dots, so every KV head is read once per step, not once per query head.
Per-batch ``lens`` (valid cache entries — continuous batching gives every
slot its own length) is prefetched as a scalar.

Dead blocks are neither fetched nor computed: the K/V (and slot-mask)
index maps clamp the block index to the slot's last valid block, so the
pipeline sees a repeated block index and issues no copy, and the body
runs only while the block starts below ``lens``.  ``kv_blocks`` counts
what that leaves the kernel to fetch.

``block_k`` follows the shapes (``block_k_for``): the most rows, a
multiple of 128, that keep one (Hkv, block_k, d) block within
``BLOCK_BYTES`` of VMEM, or the whole cache where it is shorter.  The
cache is never padded: a ragged tail (C not a multiple of ``block_k``)
is a partial last block whose rows past C hold whatever the copy left
there, and both the scores and the V rows at or past ``lens`` (clamped
to C) are masked, since 0 x NaN is NaN.

Sliding windows over a full-length cache: pass ``lo`` (B,), each slot's
first row in the window.  It is prefetched like ``lens``; the index maps
clamp the block index from below to the block holding ``lo`` as they
clamp it from above, so blocks wholly before the window are neither
fetched nor computed, and rows under ``lo`` are masked like rows at or
past ``lens``.  Without ``lo`` the kernel is unchanged.

Zero-copy serving mode: pass ``k_new``/``v_new`` (the current token's K/V,
not yet written to the cache) and the kernel folds them into the final
split-K block's online-softmax state — the cache is only *read*, so the
serving engine can defer the single-row cache write to one donated
post-scan scatter instead of rewriting cache-sized buffers every layer.
A slot with ``lens`` 0 then attends the new token alone.

Ring-buffer (windowed) caches: pass ``slot_mask`` (B, C) — validity there
is per *slot*, not a prefix length (the slot the new token will overwrite
holds the evicted, out-of-window entry and must not be attended).  The
mask rides the same split-K blocking as K/V, so the windowed zero-copy
path no longer has to fall back to the XLA lowering.

Layouts: q (B, Hq, d); k/v (B, Hkv, C, d); lens (B,) int32; lo (B,) int32;
k/v_new (B, Hkv, 1, d); slot_mask (B, C) bool/int -> out (B, Hq, d).

Mosaic tiling: the last two dims of every block must be multiples of
(8, 128) or equal the array's own.  So the kernel sees the query and the
output as (B, Hkv, G, d) (a free reshape) and the slot mask with a unit
axis in front of its last dim ((B, 1, C)); ``block_k`` is a multiple of
128 or the whole cache length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM for one (Hkv, block_k, d) K or V block.  On a v5e at both benchmark
# cells' widths 512 KiB (256 rows) beat 1 MiB by 13% and 3% a call at the
# cells' lengths, and tied with it on full caches: a smaller block fetches
# less of a slot's partly valid last block.
BLOCK_BYTES = 1 << 19


def block_k_for(C: int, Hkv: int, d: int, itemsize: int) -> int:
    """Cache rows per grid step for a (·, Hkv, C, d) cache of ``itemsize``
    bytes: the most multiples of 128 within ``BLOCK_BYTES``, at least 128,
    or all of C where that is fewer."""
    rows = BLOCK_BYTES // (Hkv * d * itemsize) // 128 * 128
    return min(C, max(rows, 128))


def kv_blocks(lens, C: int, block_k: int, lo=None) -> int:
    """K/V blocks the kernel fetches for slots of valid lengths ``lens``
    (and first rows ``lo``): each slot's blocks from the one holding its
    first row up to its last valid one, and at least one."""
    n = -(-np.minimum(np.asarray(lens, np.int64), C) // block_k)
    if lo is not None:
        n = n - np.asarray(lo, np.int64) // block_k
    return int(np.maximum(n, 1).sum())


def _decode_kernel(lens_ref, *rest, scale: float, block_k: int, n_k: int,
                   merge_new: bool, masked: bool, bounded: bool):
    lo_ref = None
    if bounded:
        lo_ref, rest = rest[0], rest[1:]
    q_ref, k_ref, v_ref, *rest = rest
    smask_ref = None
    if masked:
        smask_ref, rest = rest[0], rest[1:]
    if merge_new:
        knew_ref, vnew_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    ki = pl.program_id(1)
    valid = lens_ref[b]
    # (Hkv, G, d) query rows; batched dots over the leading KV-head axis
    qk_dims = (((2,), (2,)), ((0,), (0,)))               # hgd,hkd->hgk
    pv_dims = (((2,), (1,)), ((0,), (0,)))               # hgk,hkd->hgd

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = ki * block_k < valid
    if bounded:
        first = lo_ref[b]
        live = live & ((ki + 1) * block_k > first)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (Hkv, G, d)
        k = k_ref[0].astype(jnp.float32)                  # (Hkv, Bk, d)
        v = v_ref[0].astype(jnp.float32)
        start = ki * block_k
        # rows past lens (and past C in a ragged tail) may hold anything:
        # 0 x NaN is NaN, so V is zeroed there as well as the scores masked
        row = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k, 1), 1)
        col = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_k), 2)
        row_ok, col_ok = row < valid, col < valid         # col_ok (1, 1, Bk)
        if bounded:
            row_ok, col_ok = row_ok & (row >= first), col_ok & (col >= first)
        v = jnp.where(row_ok, v, 0.0)
        if masked:
            # per-slot validity (ring buffers): ANDed with the prefix-length
            # mask, exactly like the XLA lowering's kv_slot_mask
            col_ok = col_ok & (smask_ref[...] != 0)
        s = jax.lax.dot_general(q, k, qk_dims,
                                preferred_element_type=jnp.float32)
        s = jnp.where(col_ok, s, NEG_INF)                 # (Hkv, G, Bk)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(col_ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, pv_dims, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _fin():
        m = m_ref[...]
        l = l_ref[...]
        acc = acc_ref[...]
        if merge_new:
            # fold the current (not-yet-cached) token into the softmax state
            q = q_ref[0].astype(jnp.float32) * scale      # (Hkv, G, d)
            kn = knew_ref[0].astype(jnp.float32)          # (Hkv, 1, d)
            vn = vnew_ref[0].astype(jnp.float32)
            s_new = jnp.sum(q * kn, axis=2, keepdims=True)  # (Hkv, G, 1)
            m2 = jnp.maximum(m, s_new)
            c = jnp.exp(m - m2)
            p_new = jnp.exp(s_new - m2)
            l = l * c + p_new
            acc = acc * c + p_new * vn
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lens: jnp.ndarray, *, k_new: Optional[jnp.ndarray] = None,
                     v_new: Optional[jnp.ndarray] = None,
                     slot_mask: Optional[jnp.ndarray] = None,
                     lo: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     block_k: Optional[int] = None,
                     interpret: bool = True) -> jnp.ndarray:
    """q: (B, Hq, d); k/v: (B, Hkv, C, d); lens: (B,) -> (B, Hq, d).

    With ``k_new``/``v_new`` (B, Hkv, 1, d) the current token is attended
    as if written at position ``lens`` (zero-copy serving mode).  With
    ``slot_mask`` (B, C) only slots where the mask is nonzero are attended
    (ring-buffer eviction), ANDed with the ``lens`` prefix mask.  With
    ``lo`` (B,) rows under ``lo`` are neither attended nor fetched.
    ``block_k`` overrides ``block_k_for``'s choice (tests only)."""
    B, Hq, d = q.shape
    _, Hkv, C, _ = k.shape
    G = Hq // Hkv
    merge_new = k_new is not None
    masked = slot_mask is not None
    bounded = lo is not None
    scale = scale if scale is not None else d ** -0.5
    if block_k is None:
        block_k = block_k_for(C, Hkv, d, k.dtype.itemsize)
    block_k = min(block_k, C)
    n_k = pl.cdiv(C, block_k)
    lens = jnp.minimum(lens.astype(jnp.int32), C)
    prefetch = [lens]
    if bounded:
        prefetch.append(jnp.minimum(lo.astype(jnp.int32), lens))

    def kv_block(b, ki, lens, *lo):
        # clamp to the slot's last valid block (and from below to the
        # block holding its first row): a repeated block index issues no
        # copy, so dead blocks are never fetched
        last = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        if lo:
            ki = jnp.maximum(ki, jnp.minimum(lo[0][b] // block_k, last))
        return jnp.minimum(ki, last)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, n_k=n_k, merge_new=merge_new,
                               masked=masked, bounded=bounded)
    heads = pl.BlockSpec((1, Hkv, G, d), lambda b, ki, *_: (b, 0, 0, 0))
    kv = pl.BlockSpec((1, Hkv, block_k, d),
                      lambda b, ki, *p: (b, 0, kv_block(b, ki, *p), 0))
    in_specs = [heads, kv, kv]
    inputs = [q.reshape(B, Hkv, G, d), k, v]
    if masked:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, ki, *p: (b, 0, kv_block(b, ki, *p))))
        inputs.append(jnp.asarray(slot_mask, jnp.int32)[:, None, :])
    if merge_new:
        new = pl.BlockSpec((1, Hkv, 1, d), lambda b, ki, *_: (b, 0, 0, 0))
        in_specs += [new, new]
        inputs += [k_new, v_new]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_k),
        in_specs=in_specs,
        out_specs=heads,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, d), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), q.dtype),
        interpret=interpret,
    )(*prefetch, *inputs)
    return out.reshape(B, Hq, d)
