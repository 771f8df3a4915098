"""Weights made from ``--seed`` alone, leaf by leaf.

Every leaf of the served parameter tree is named by its path
(``blocks/attn/wq``) and made from ``fold_in(seed key, crc32(path))``; a
leaf stacked by layer makes layer ``l`` from ``fold_in(that key, l)``.  So
the plain reference can remake any single layer without the whole tree,
and the values do not depend on the process (no ``hash()``) or on how the
leaves are grouped into jitted calls: random bits become integers, the
integers become float32 exactly, and one multiply and one rounding to the
served dtype follow.

Values are uniform with the spread of the usual initialisers: fan-in
scaled projections, 0.02 for the embedding, norm scales about 1, biases
of a few tenths.  Rows (or columns) of the embedding and the output head
past the published vocabulary are zero, as a checkpoint padded to the
program's vocabulary would be.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_LEAVES = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
BIAS_LEAVES = ("bq", "bk", "bv")
VOCAB_LEAVES = ("embed", "lm_head")
VOCAB_BLOCK = 256
STACKED_ROOT = "blocks"


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, also past 32 bits or negative."""
    s = int(seed) % 2**64
    hi, lo = divmod(s, 2**32)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(hi))
    return jax.random.fold_in(key, np.uint32(lo))


def _half_width(path: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    """(centre, half width) of the uniform distribution of one leaf."""
    name = path.rsplit("/", 1)[-1]
    if name in NORM_LEAVES:
        return 1.0, 0.25
    if name in BIAS_LEAVES:
        return 0.0, 0.5
    if name == "embed":
        return 0.0, 0.02 * math.sqrt(3.0)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return 0.0, math.sqrt(3.0 / fan_in)


def _uniform(key, shape, centre: float, half: float, dtype) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint32)
    ints = (bits >> 8).astype(jnp.int32) - (1 << 23)      # [-2^23, 2^23)
    x = ints.astype(jnp.float32) * np.float32(half / (1 << 23))
    if centre:
        x = x + np.float32(centre)
    return x.astype(dtype)


def make_leaf(key, path: str, shape: Tuple[int, ...], dtype,
              vocab_size: int) -> jax.Array:
    """One unstacked leaf (or one layer of a stacked one).

    The embedding (rows) and the output head (columns) are made in blocks
    of ``VOCAB_BLOCK`` along the vocabulary, so their first rows do not
    depend on how far the program pads the vocabulary."""
    centre, half = _half_width(path, shape)
    key = jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))
    name = path.rsplit("/", 1)[-1]
    if name not in VOCAB_LEAVES:
        return _uniform(key, shape, centre, half, dtype)
    axis = 0 if name == "embed" else len(shape) - 1
    n = shape[axis]
    nb = -(-n // VOCAB_BLOCK)
    blk = list(shape)
    blk[axis] = VOCAB_BLOCK
    x = jax.vmap(lambda b: _uniform(jax.random.fold_in(key, b), tuple(blk),
                                    centre, half, dtype),
                 out_axes=axis)(jnp.arange(nb, dtype=jnp.uint32))
    x = jax.lax.collapse(x, axis, axis + 2)
    x = jax.lax.slice_in_dim(x, 0, n, axis=axis)
    if n > vocab_size:
        keep = jnp.arange(n) < vocab_size
        x = jnp.where(keep.reshape([-1 if a == axis else 1
                                    for a in range(len(shape))]), x, 0)
    return x.astype(dtype)


def layer_leaf(key, path: str, layer, shape: Tuple[int, ...], dtype,
               vocab_size: int) -> jax.Array:
    """Layer ``layer`` of the stacked leaf at ``path`` (shape without L)."""
    if isinstance(layer, int):
        layer = np.uint32(layer)
    return make_leaf(jax.random.fold_in(key, layer), path, shape, dtype,
                     vocab_size)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_tree(key, shapes: Any, vocab_size: int) -> Any:
    """The whole tree of ``shapes`` (``ShapeDtypeStruct`` leaves)."""
    def one(path, s):
        p = _path_str(path)
        if p.startswith(STACKED_ROOT + "/"):
            n = s.shape[0]
            return jax.vmap(lambda l: layer_leaf(key, p, l, s.shape[1:],
                                                 s.dtype, vocab_size)
                            )(jnp.arange(n, dtype=jnp.uint32))
        return make_leaf(key, p, s.shape, s.dtype, vocab_size)
    return jax.tree_util.tree_map_with_path(one, shapes)


def make_params(seed: int, shapes: Any, vocab_size: int) -> Any:
    """All weights on the default device, in one jitted call."""
    return jax.jit(lambda k: make_tree(k, shapes, vocab_size))(seed_key(seed))


def leaf_paths(shapes: Any) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """path -> (shape, dtype) of every leaf; for tests and the reference."""
    out = {}
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        out[_path_str(path)] = (tuple(s.shape), s.dtype)
    return out
