"""RG-LRU linear recurrence — Pallas TPU kernel (RecurrentGemma/Griffin
[arXiv:2402.19427], DESIGN.md §6).

h_t = a_t * h_{t-1} + b_t with per-channel gates.  Grid (B, nW, nT): width
is tiled over the lane dimension, time blocks run innermost/sequential with
the (1, Wb) state carried in VMEM scratch.  Within a time block the
recurrence materializes as a log-space *segmented* prefix product:

    h_{t} = exp(cumA_t) * h_in + sum_{k<=t} exp(cumA_t - cumA_k) * b_k

computed as a (Tb, Tb) masked matrix applied on the VPU — numerically safe
because cumA_t - cumA_k <= 0 within the mask (a_t in (0, 1]).

Layouts: log_a/bx (B, S, W) f32; h0 (B, W) f32 -> (y (B, S, W), h_T (B, W)).

Interpret mode only: Mosaic refuses this kernel on a TPU v5e (a block
shape is not aligned to the (8, 128) tiling), and no model calls it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(la_ref, bx_ref, h0_ref, y_ref, hT_ref, h_ref, *,
                  block_t: int, n_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[0][None, :]            # (1, Wb)

    la = la_ref[0].astype(jnp.float32)             # (Tb, Wb), <= 0
    bx = bx_ref[0].astype(jnp.float32)             # (Tb, Wb)

    cum = jnp.cumsum(la, axis=0)                   # (Tb, Wb)
    # decay[t, k] = exp(cum_t - cum_k) for k <= t else 0  — per channel this
    # is a (Tb, Tb) matrix; apply channel-blocked via einsum on the VPU.
    ti_idx = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    ki_idx = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    causal = ti_idx >= ki_idx
    # seg[t, k, w] = cum[t, w] - cum[k, w]
    seg = cum[:, None, :] - cum[None, :, :]
    dec = jnp.where(causal[:, :, None], jnp.exp(seg), 0.0)  # (Tb, Tb, Wb)
    y = jnp.einsum("tkw,kw->tw", dec, bx)
    y = y + jnp.exp(cum) * h_ref[...]              # carry-in contribution
    y_ref[0] = y.astype(y_ref.dtype)
    h_ref[...] = y[-1][None, :]

    @pl.when(ti == n_t - 1)
    def _fin():
        hT_ref[0] = h_ref[...][0].astype(hT_ref.dtype)


def rglru_scan(log_a: jnp.ndarray, bx: jnp.ndarray,
               h0: Optional[jnp.ndarray] = None, *, block_t: int = 128,
               block_w: int = 128,
               interpret: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """log_a/bx: (B, S, W); h0: (B, W) or None -> (y (B,S,W), h_T (B,W))."""
    B, S, W = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    block_t = min(block_t, S)
    block_w = min(block_w, W)
    pad_t = (-S) % block_t
    pad_w = (-W) % block_w
    if pad_t or pad_w:
        # log_a=0 (a=1) + bx=0 padding is an exact no-op on the recurrence
        log_a = jnp.pad(log_a, ((0, 0), (0, pad_t), (0, pad_w)))
        bx = jnp.pad(bx, ((0, 0), (0, pad_t), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    Sp, Wp = S + pad_t, W + pad_w
    n_t = Sp // block_t
    n_w = Wp // block_w

    kernel = functools.partial(_rglru_kernel, block_t=block_t, n_t=n_t)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, n_w, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w),
                         lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, block_t, block_w),
                         lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, block_w), lambda b, wi, ti: (b, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w),
                         lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, block_w), lambda b, wi, ti: (b, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Wp), log_a.dtype),
            jax.ShapeDtypeStruct((B, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(log_a, bx, h0)
    return y[:, :S, :W], hT[:, :W]
