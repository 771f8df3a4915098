"""Flash-decode attention — Pallas TPU kernel.

One new query token against a long KV cache (the ``decode_32k`` /
``long_500k`` hot loop).  Split-K over the cache: grid (B, Hq, nk) with the
cache-block dimension innermost/sequential; online-logsumexp partials merge
in VMEM scratch.  Per-batch ``lens`` (valid cache entries — continuous
batching gives every slot its own length) is prefetched as a scalar so the
mask needs no extra HBM traffic.

Zero-copy serving mode: pass ``k_new``/``v_new`` (the current token's K/V,
not yet written to the cache) and the kernel folds them into the final
split-K block's online-softmax state — the cache is only *read*, so the
serving engine can defer the single-row cache write to one donated
post-scan scatter instead of rewriting cache-sized buffers every layer.

Ring-buffer (windowed) caches: pass ``slot_mask`` (B, C) — validity there
is per *slot*, not a prefix length (the slot the new token will overwrite
holds the evicted, out-of-window entry and must not be attended).  The
mask rides the same split-K blocking as K/V, so the windowed zero-copy
path no longer has to fall back to the XLA lowering.

Layouts: q (B, Hq, d); k/v (B, Hkv, C, d); lens (B,) int32;
k/v_new (B, Hkv, 1, d); slot_mask (B, C) bool/int -> out (B, Hq, d).

Mosaic tiling: the last two dims of every block must be multiples of
(8, 128) or equal the array's own.  So the kernel sees the query, the
output and the slot mask with a unit axis in front of their last dim
((B, Hq, 1, d) and (B, 1, C)), and ``block_k`` is either a multiple of
128 or the whole (padded) cache length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                   block_k: int, n_k: int, merge_new: bool,
                   masked: bool):
    smask_ref = None
    if masked:
        smask_ref, rest = rest[0], rest[1:]
    if merge_new:
        knew_ref, vnew_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (1, d) row
    k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, Bk)
    valid = lens_ref[b]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = k_pos < valid
    if masked:
        # per-slot validity (ring buffers): ANDed with the prefix-length
        # mask, exactly like the XLA lowering's kv_slot_mask
        mask = mask & (smask_ref[0] != 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _fin():
        m = m_ref[...]
        l = l_ref[...]
        acc = acc_ref[...]
        if merge_new:
            # fold the current (not-yet-cached) token into the softmax state
            kn = knew_ref[0, 0].astype(jnp.float32)          # (1, d)
            vn = vnew_ref[0, 0].astype(jnp.float32)
            s_new = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())))
            m2 = jnp.maximum(m, s_new)
            c = jnp.exp(m - m2)
            p_new = jnp.exp(s_new - m2)
            l = l * c + p_new
            acc = acc * c + p_new * vn
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lens: jnp.ndarray, *, k_new: Optional[jnp.ndarray] = None,
                     v_new: Optional[jnp.ndarray] = None,
                     slot_mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     block_k: int = 512,
                     interpret: bool = True) -> jnp.ndarray:
    """q: (B, Hq, d); k/v: (B, Hkv, C, d); lens: (B,) -> (B, Hq, d).

    With ``k_new``/``v_new`` (B, Hkv, 1, d) the current token is attended
    as if written at position ``lens`` (zero-copy serving mode).  With
    ``slot_mask`` (B, C) only slots where the mask is nonzero are attended
    (ring-buffer eviction), ANDed with the ``lens`` prefix mask."""
    B, Hq, d = q.shape
    _, Hkv, C, _ = k.shape
    G = Hq // Hkv
    merge_new = k_new is not None
    masked = slot_mask is not None
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, C)
    pad = (-C) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_k = (C + pad) // block_k
    q4 = q[:, :, None, :]                                 # (B, Hq, 1, d)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, n_k=n_k, merge_new=merge_new,
                               masked=masked)
    in_specs = [
        pl.BlockSpec((1, 1, 1, d), lambda b, h, ki, lens: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, ki, lens: (b, h // G, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, ki, lens: (b, h // G, ki, 0)),
    ]
    inputs = [q4, k, v]
    if masked:
        sm = jnp.asarray(slot_mask, jnp.int32)
        if pad:
            sm = jnp.pad(sm, ((0, 0), (0, pad)))
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, h, ki, lens: (b, 0, ki)))
        sm = sm[:, None, :]                               # (B, 1, C)
        inputs.append(sm)
    if merge_new:
        in_specs += [
            pl.BlockSpec((1, 1, 1, d), lambda b, h, ki, lens: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda b, h, ki, lens: (b, h // G, 0, 0)),
        ]
        inputs += [k_new, v_new]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, d), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), *inputs)
    return out[:, :, 0, :]
