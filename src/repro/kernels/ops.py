"""Jit'd public wrappers around the Pallas kernels.

On the CPU kernels execute in interpret mode (Python semantics, exact
math); on TPU ``decode_attention``, ``moe_gmm`` and ``lora_merge``
compile to Mosaic, while ``ssd_scan`` and ``rglru_scan`` do not (see their
modules).
``interpret`` is resolved from the backend unless forced.  Layout
adapters translate from the model zoo's (B, S, H, d) convention to the
kernels' (B, H, S, d).

See ``docs/ARCHITECTURE.md`` § "Models and kernels".
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import lora_merge as _lm
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def _interpret(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """Model-layout flash attention: q (B,S,Hq,d), k/v (B,S,Hkv,d)."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    o = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                            q_offset=q_offset, block_q=block_q,
                            block_k=block_k, interpret=_interpret(interpret))
    return jnp.moveaxis(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, lens, *, k_new=None, v_new=None,
                     slot_mask=None, lo=None, block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Model-layout flash decode: q (B,1,Hq,d), caches (B,C,Hkv,d),
    lens (B,) -> (B,1,Hq,d).  Optional k/v_new (B,1,Hkv,d): the current
    token's K/V, merged in-kernel instead of read from the cache
    (zero-copy serving mode).  Optional slot_mask (B,C): per-slot cache
    validity for ring-buffered (windowed) caches.  Optional lo (B,): each
    slot's first row in a sliding window.  ``block_k`` overrides
    the kernel's choice from the shapes (tests only)."""
    qt = q[:, 0]                                     # (B,Hq,d)
    kt = jnp.moveaxis(k_cache, 1, 2)                 # (B,Hkv,C,d)
    vt = jnp.moveaxis(v_cache, 1, 2)
    kn = None if k_new is None else jnp.moveaxis(k_new, 1, 2)
    vn = None if v_new is None else jnp.moveaxis(v_new, 1, 2)
    o = _dec.decode_attention(qt, kt, vt, lens, k_new=kn, v_new=vn,
                              slot_mask=slot_mask, lo=lo, block_k=block_k,
                              interpret=_interpret(interpret))
    return o[:, None]


@functools.partial(jax.jit, static_argnames=("act", "tm", "tf", "interpret"))
def moe_gmm(x, w_gate, w_up, w_down, group_sizes, layer, *,
            act=jax.nn.silu, tm: Optional[int] = None,
            tf: Optional[int] = None, interpret: Optional[bool] = None):
    """Grouped expert FFN over rows sorted by expert, the experts read in
    place from stacked weights at ``layer``: x (M, D), w_gate / w_up
    (L, G, D, F), w_down (L, G, F, D), group_sizes (G,), layer int32
    -> (M, D).  Rows at or past sum(group_sizes) are unspecified."""
    return _gmm.moe_gmm(x, w_gate, w_up, w_down, group_sizes, layer,
                        act=act, tm=tm, tf=tf,
                        interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """Mamba2 SSD: x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N)."""
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_t", "block_w",
                                             "interpret"))
def rglru_scan(log_a, bx, h0=None, *, block_t: int = 128,
               block_w: int = 128, interpret: Optional[bool] = None):
    """RG-LRU recurrence: log_a/bx (B,S,W) f32."""
    return _rg.rglru_scan(log_a, bx, h0, block_t=block_t, block_w=block_w,
                          interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "block_i", "block_j",
                                             "interpret"))
def lora_merge(W, A, B, scale: float, *, block_i: int = 256,
               block_j: int = 256, interpret: Optional[bool] = None):
    """Fused W + scale*(A@B) over stacked layers: W (L,Din,Dout)."""
    return _lm.lora_merge(W, A, B, scale, block_i=block_i, block_j=block_j,
                          interpret=_interpret(interpret))
