"""Symbolic mesh helpers for the PT-COMM auditor.

The auditor never touches real devices: programs are traced under
``jax.sharding.AbstractMesh`` (a mesh of *names and sizes*, no device
array), which jax's shard_map accepts at trace time — ``make_jaxpr``
through it yields the exact collective equations with per-shard avals,
no XLA compile. These helpers build such meshes from the plain
``{axis: size}`` dicts the tools layer records (the MULTICHIP_r01–r05
shapes), and read sizes back off whatever mesh object a ``shard_map``
equation carries (Mesh or AbstractMesh both expose ``.shape``).
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional

__all__ = ["abstract_mesh", "mesh_axis_sizes", "mesh_spec"]


def abstract_mesh(axes: Mapping[str, int]):
    """An ``AbstractMesh`` over ``{axis_name: size}`` — tracing-only, no
    devices. Size-1 axes are legal but add nothing; pass them through so
    the caller's spec names stay valid."""
    from jax.sharding import AbstractMesh

    if not axes:
        raise ValueError("abstract_mesh needs at least one axis")
    return AbstractMesh(tuple(int(v) for v in axes.values()),
                        tuple(str(k) for k in axes))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """``{axis: size}`` off a Mesh/AbstractMesh (both expose ``.shape`` as
    an ordered mapping); tolerates anything else by returning {}."""
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        return {}


def mesh_spec(axes: Mapping[str, int], *entries: Optional[str]):
    """A ``PartitionSpec`` whose entries are masked against the mesh:
    an axis name absent from ``axes`` becomes ``None`` (replicated), so
    one spec expression serves every recorded mesh shape. Entries may be
    ``None``, an axis name, or a tuple of axis names (partial tuples
    keep only the present axes)."""
    from jax.sharding import PartitionSpec

    out = []
    for e in entries:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in axes)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(e if e in axes else None)
    return PartitionSpec(*out)
