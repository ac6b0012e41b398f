"""Meshes. Defined as functions (never module-level constants) so importing
this module never touches jax device state.

Every mesh the program, its tests and its benchmarks build goes through
:func:`make_mesh`, which gives each axis ``AxisType.Auto``: the model code
steers layouts with ``with_sharding_constraint`` and lets GSPMD propagate
the rest, which ``Explicit`` axes (the ``jax.make_mesh`` default) reject.

Single pod: 16×16 = 256 chips (TPU v5e pod), axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries data parallelism across the inter-pod (DCN/ICI) links; batch
shards over ("pod", "data") via the 'data' alias in repro.distributed.ctx.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with ``Auto`` axes over ``devices`` (default: all
    of ``jax.devices()``)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for in-process distributed tests (host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def parse_mesh_arg(spec: str):
    """``"DxM"`` CLI string -> debug mesh (shared by the serving example
    and the benchmarks, so the mesh-flag syntax lives in one place)."""
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() and int(p) > 0 for p in parts):
        raise ValueError(
            f"mesh spec must be 'DxM' with positive ints (e.g. 2x4), "
            f"got {spec!r}"
        )
    return make_debug_mesh(int(parts[0]), int(parts[1]))
