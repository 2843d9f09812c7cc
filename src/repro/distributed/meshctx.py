"""Ambient mesh context shared between the launch layer and model code.

The launch layer (dryrun/train/serve) sets the mesh once; model layers
that need explicit collectives (expert-parallel MoE via shard_map) or
sharding constraints read it here.  Smoke tests run with no mesh set and
every distributed hook degrades to a no-op.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


@contextmanager
def use_mesh(mesh: Mesh):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _MESH = prev


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity.
    Axis names absent from the mesh are dropped (e.g. "pod" on the
    single-pod mesh); axes that do not evenly divide the corresponding
    dim are dropped (e.g. batch=1 long-context decode keeps the data
    axes unsharded)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    fixed = []
    used = set()
    for i, entry in enumerate(spec):
        if entry is None or i >= x.ndim:
            fixed.append(None)
            continue
        names = tuple(a for a in
                      (entry if isinstance(entry, tuple) else (entry,))
                      if a in mesh.shape and a not in used)
        # largest prefix of the axis tuple that divides the dim (e.g.
        # batch=32 over ("data","model")=256 falls back to ("data",)=16)
        chosen = None
        while names:
            entry2 = names if len(names) > 1 else names[0]
            if x.shape[i] % _axis_size(mesh, entry2) == 0:
                chosen = entry2
                break
            names = names[:-1]
        if chosen is None:
            fixed.append(None)
        else:
            fixed.append(chosen)
            used.update(names)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))
