"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm [arXiv:2405.21060] (DESIGN.md §6): the
GPU version leans on warp-level scans; here each grid step owns one
(batch, head, chunk) tile in VMEM — intra-chunk work is a masked (Q, Q)
quadratic form on the MXU, and the (P, N) inter-chunk state is carried in
VMEM scratch across the sequential chunk dimension (innermost grid axis).

Layouts: x (B, H, nc, Q, P); dt (B, H, nc, Q, 1); A (1, H);
         Bm/Cm (B, nc, Q, N)  [single B/C group broadcast over heads]
Outputs: y (B, H, nc, Q, P); final_state (B, H, P, N).

Interpret mode only: Mosaic refuses this kernel on a TPU v5e (``cumsum``
has no TPU lowering), and no model calls it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fs_ref,
                state_ref, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (Q, 1)
    a = a_ref[0, h].astype(jnp.float32)           # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    dA = dt * a                                   # (Q, 1)
    cum = jnp.cumsum(dA, axis=0)                  # (Q, 1)

    # intra-chunk: G[q, k] = (C_q . B_k) * exp(cum_q - cum_k) * dt_k, q >= k
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    seg = cum - cum[:, 0][None, :]                # (Q, Q) = cum_q - cum_k
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = qi >= ki
    G = jnp.where(causal, scores * jnp.exp(seg) * dt[:, 0][None, :], 0.0)
    y = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())))        # (Q, P)

    # inter-chunk: y += exp(cum_q) * C_q . state_in   (state: (P, N))
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (1,)), ((), ())))

    # state update: state_out = exp(cum_last)*state_in + sum_k w_k x_k B_k^T
    w = jnp.exp(cum[-1, 0] - cum) * dt            # (Q, 1)
    S_c = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())))  # (P, N)
    state_ref[...] = jnp.exp(cum[-1, 0]) * state_ref[...] + S_c

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _fin():
        fs_ref[0, 0] = state_ref[...].astype(fs_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, H, P); dt: (B, S, H) (post-softplus); A: (H,) negative;
    Bm/Cm: (B, S, N).  Returns (y (B, S, H, P), final_state (B, H, P, N))."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-S) % chunk
    if pad:  # dt=0 padding is an exact no-op on the recurrence
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    xk = jnp.moveaxis(x.reshape(B, nc, chunk, H, P), 3, 1)      # (B,H,nc,Q,P)
    dtk = jnp.moveaxis(dt.reshape(B, nc, chunk, H), 3, 1)[..., None]
    bk = Bm.reshape(B, nc, chunk, N)
    ck = Cm.reshape(B, nc, chunk, N)
    a2 = A[None, :].astype(jnp.float32)                          # (1, H)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, fs = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, H), lambda b, h, c: (0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xk, dtk, a2, bk, ck)
    y = jnp.moveaxis(y, 1, 3).reshape(B, Sp, H, P)[:, :S]
    return y, fs
