"""Grouped expert FFN — Pallas TPU kernel (``moe_gmm``).

The routed part of a dropless MoE layer.  Rows of ``x`` are (token, expert)
pairs sorted by expert; group ``g`` holds ``group_sizes[g]`` consecutive
rows, and every row of it goes through expert ``g``'s SwiGLU FFN:

    out[r] = (act(x[r] @ w_gate[g]) * (x[r] @ w_up[g])) @ w_down[g]

Adapted from the megablox grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): its group metadata
gives every grid step one (m tile, group) visit, so a tile that two groups
share is visited once per group and each visit stores only its group's
rows.  Here one kernel does all three matmuls of a visit.  The grid is
(visits, F tiles); a visit streams expert ``g``'s gate and up columns and
down rows one F tile at a time, accumulates the (tm, D) output in f32
VMEM, and stores its group's rows at the last F tile.  The hidden
activation never leaves VMEM.

Only tiles that some group covers are visited: an expert with no rows is
neither fetched nor computed, and rows at or past ``sum(group_sizes)``
(pairs routed to experts held elsewhere, sorted to the end) are never
written — the caller masks them.  The number of visits is a traced
scalar, so the grid is dynamic.

Layouts: x (M, D); w_gate, w_up (G, D, F); w_down (G, F, D); group_sizes
(G,) int32 -> (M, D) in ``x.dtype``.  ``tm`` rows per m tile (M is padded
to a multiple), ``tf`` hidden columns per F tile (F a multiple of it).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "moe_gmm"
VMEM_LIMIT = 64 * 1024 * 1024


def tiles_for(M: int, F: int) -> Tuple[int, int]:
    """(tm, tf): 128 rows a tile for a decode step's few pairs, 256 for a
    prefill's many; hidden columns 512 at a time (or all of a small F)."""
    tm = 128 if M <= 1024 else 256
    tf = 512 if F % 512 == 0 else F
    return tm, tf


def group_metadata(group_sizes: jnp.ndarray, m: int, tm: int):
    """((group_offsets, group_ids, m_tile_ids), visits) for ``m`` rows in
    tiles of ``tm``: visit ``i`` computes group ``group_ids[i]`` on m tile
    ``m_tile_ids[i]``; ``visits`` counts the visits the groups need (empty
    groups need none).  Megablox's ``make_group_metadata`` for one shard
    that holds every group."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    # tiles each group touches: its start rounded down, its end up
    spans = ((ends + tm - 1) // tm * tm - starts // tm * tm).astype(jnp.int32)
    group_tiles = jnp.where(group_sizes == 0, 0, spans // tm)
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), group_tiles,
                           total_repeat_length=tiles_m + G - 1)
    # a tile is visited once by the group owning its first row, and once
    # more by every group that starts inside it
    partial = jnp.where(((starts % tm) == 0) | (group_sizes == 0), tiles_m,
                        starts // tm)
    visits_per_tile = jnp.histogram(partial, bins=tiles_m,
                                    range=(0, tiles_m - 1))[0] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                            visits_per_tile.astype(jnp.int32),
                            total_repeat_length=tiles_m + G - 1)
    return (offsets, group_ids, m_tile_ids), group_tiles.sum()


def _kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, x_ref, wg_ref,
            wu_ref, wd_ref, out_ref, acc_ref, *, tm: int, n_f: int,
            act: Callable):
    i = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(wd_ref.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _store():
        grp = group_ids_ref[i]
        row = (jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
               + m_tile_ids_ref[i] * tm)
        mine = (row >= offsets_ref[grp]) & (row < offsets_ref[grp + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def moe_gmm(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
            w_down: jnp.ndarray, group_sizes: jnp.ndarray, *,
            act: Callable = jax.nn.silu, tm: Optional[int] = None,
            tf: Optional[int] = None, interpret: bool = True) -> jnp.ndarray:
    """Rows of each group through its expert's gated FFN; see the module
    docstring.  Rows at or past ``sum(group_sizes)`` are left unwritten."""
    M, D = x.shape
    G, _, F = w_gate.shape
    d_tm, d_tf = tiles_for(M, F)
    tm, tf = tm or d_tm, tf or d_tf
    assert F % tf == 0, (F, tf)
    Mp = -(-M // tm) * tm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    n_f = F // tf
    meta, visits = group_metadata(group_sizes.astype(jnp.int32), Mp, tm)

    def rows(i, f, offsets, group_ids, m_tile_ids):
        return m_tile_ids[i], 0

    def cols(i, f, offsets, group_ids, m_tile_ids):
        return group_ids[i], 0, f

    def down(i, f, offsets, group_ids, m_tile_ids):
        return group_ids[i], f, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(visits, n_f),
        in_specs=[pl.BlockSpec((tm, D), rows),
                  pl.BlockSpec((None, D, tf), cols),
                  pl.BlockSpec((None, D, tf), cols),
                  pl.BlockSpec((None, tf, D), down)],
        out_specs=pl.BlockSpec((tm, D), rows),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, n_f=n_f, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(*meta, x, w_gate, w_up, w_down)
    return out[:M]
