"""Production mesh builders.

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any jax init).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with ``Auto`` axes.  The repo places arrays with
    ``NamedSharding`` and ``shard_map`` and leaves the rest to the
    partitioner; ``make_mesh``'s default ``Explicit`` axes would demand
    sharding-in-types everywhere (``jax.set_mesh``, ``out_sharding=``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_pipeline_mesh(n_stages: int, *, total: Optional[int] = None):
    """Mesh for the PipeBoost pipeline lowering: ('data', 'stage').

    n_stages is arch-dependent (largest divisor of n_layers that divides the
    chip budget); the remaining chips become data-parallel pipeline replicas.
    """
    total = total or 256
    assert total % n_stages == 0, (total, n_stages)
    return auto_mesh((total // n_stages, n_stages), ("data", "stage"))


def make_host_mesh(axes: Tuple[str, ...] = ("data", "model")):
    """Degenerate mesh over however many local devices exist (CPU tests)."""
    n = len(jax.devices())
    shape = [1] * len(axes)
    shape[0] = n
    return auto_mesh(shape, axes)


def pipeline_stages_for(n_layers: int, max_stages: int = 16) -> int:
    """Largest s <= max_stages with s | n_layers and s | 256."""
    for s in range(max_stages, 0, -1):
        if n_layers % s == 0 and 256 % s == 0:
            return s
    return 1


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes of a mesh (everything except 'model'/'stage')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
