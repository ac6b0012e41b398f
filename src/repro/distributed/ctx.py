"""Mesh context: model code calls ``shard(x, *axes)`` for activation
sharding constraints; with no active mesh (smoke tests, single device) the
call is the identity, so the same model code runs everywhere.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def current_cp_axis() -> Optional[str]:
    """Mesh axis the *sequence* dim is sharded over for context-parallel
    training, or None.  Set by ``ExecutionContext.scope()`` so registry
    backends (whose ``fn(u, h, skip, gate)`` signature carries no context)
    can resolve which axis their collectives run over."""
    return getattr(_state, "cp_axis", None)


@contextlib.contextmanager
def use_cp_axis(axis: Optional[str]):
    prev = current_cp_axis()
    _state.cp_axis = axis
    try:
        yield axis
    finally:
        _state.cp_axis = prev


def _expand_alias(name: str, mesh: Mesh):
    """'data' is an alias for all data-parallel axes — on the multi-pod mesh
    that's ('pod', 'data') so batch shards over pods too."""
    if name == "data" and "pod" in mesh.shape:
        return ("pod", "data")
    return (name,)


def _filter_spec(mesh: Mesh, shape, axes: Sequence) -> P:
    """Drop constraint entries that don't divide the dim (keeps model code
    mesh-shape agnostic: 40 heads over a 16-way axis degrades to replicated
    instead of failing)."""
    out = []
    used = set()
    for dim, ax in zip(shape, axes):
        if ax is None:
            out.append(None)
            continue
        names = (ax,) if isinstance(ax, str) else tuple(ax)
        names = sum((_expand_alias(n, mesh) for n in names), ())
        names = tuple(n for n in names if n in mesh.shape and n not in used)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if names and size > 0 and dim % size == 0:
            out.append(names if len(names) > 1 else names[0])
            used.update(names)
        else:
            out.append(None)
    return P(*out)


def shard(x: jax.Array, *axes) -> jax.Array:
    """Sharding constraint by mesh-axis names (None = replicated dim).

    ``shard(x, "data", None, "model")``; a tuple entry shards one dim over
    several axes: ``shard(cache, None, ("data", "model"), None)``.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard: {len(axes)} axes for ndim {x.ndim}")
    spec = _filter_spec(mesh, x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map`` with the repo's one spelling of its replication
    check (``check`` -> ``check_vma``)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )
