"""Sharding rules: logical-axis annotations -> PartitionSpecs on the
production mesh (pod, data, model).

Conventions (MaxText-style 2D weight sharding = FSDP x TP):
  * batch        -> ("pod", "data")       (DP across pods and the data axis)
  * d_model rows -> "data"                (FSDP: ZeRO-3-like weight sharding)
  * heads / d_ff / vocab cols -> "model"  (TP)
  * experts      -> "model" when divisible (EP), else 2D TP fallback
  * long-context KV -> "data" when batch < data axis (SP)

Model code annotates activations with :func:`shard` using *logical* names;
unknown/absent mesh axes degrade to no-op so the same model runs unsharded
on CPU tests.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Sequence[str], None]
# A logical axis resolved against a concrete mesh.
Resolved = Union[str, tuple[str, ...], None]

# Logical name -> preferred mesh axes (first match present in mesh wins; for
# "batch" every present axis is used jointly).
LOGICAL_AXES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "model": ("model",),
    "expert": ("model",),
    "seq": ("data",),      # sequence parallelism for long-context
    "none": (),
}


def current_mesh() -> Optional[Mesh]:
    """Mesh from the legacy `with mesh:` context (usable under jit tracing)."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        m: Mesh = getattr(jax.interpreters.pxla,
                          "thread_resources").env.physical_mesh
    return None if m.empty else m


def resolve_axis(mesh: Mesh, logical: Axis) -> Resolved:
    """Logical axis name -> mesh axis (or tuple) present in this mesh."""
    if logical is None:
        return None
    if isinstance(logical, (tuple, list)):
        found = tuple(a for a in logical if a in mesh.axis_names)
        return found if found else None
    prefs = LOGICAL_AXES.get(logical, (logical,))
    if logical == "batch":
        found = tuple(a for a in prefs if a in mesh.axis_names)
        return found if found else None
    for a in prefs:
        if a in mesh.axis_names:
            return a
    return None


def make_spec(mesh: Mesh, *logical_axes: Axis) -> P:
    return P(*[resolve_axis(mesh, a) for a in logical_axes])


def named_sharding(mesh: Mesh, *logical_axes: Axis) -> NamedSharding:
    return NamedSharding(mesh, make_spec(mesh, *logical_axes))


def shard(x: jax.Array, *logical_axes: Axis,
          divisible_only: bool = True) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without a mesh.

    If a dimension does not divide the resolved mesh axes the annotation is
    dropped for that dim (keeps tiny smoke-test models runnable)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    resolved: list[Resolved] = []
    for dim, logical in zip(x.shape, logical_axes):
        axis = resolve_axis(mesh, logical)
        if axis is not None and divisible_only:
            n = 1
            for a in (axis if isinstance(axis, tuple) else (axis,)):
                n *= int(mesh.shape[a])
            if dim % n != 0:
                axis = None
        resolved.append(axis)
    out: jax.Array = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))
    return out


def mesh_divides(mesh: Optional[Mesh], dim: int, logical: Axis) -> bool:
    if mesh is None:
        return False
    axis = resolve_axis(mesh, logical)
    if axis is None:
        return False
    n = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        n *= int(mesh.shape[a])
    return dim % n == 0
