"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model) — DP across pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / PP experiments (e.g. (4,), ('stage',)).

    Axes are ``Auto``: the model annotates activations with
    ``with_sharding_constraint`` (distributed/sharding.shard), which an
    ``Explicit`` axis — ``jax.make_mesh``'s default — refuses."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serve_mesh(n: int):
    """1D ("model",) mesh over the first ``n`` devices for tensor-parallel
    serving (``ServeEngine(mesh=...)`` / ``repro.launch.serve --mesh N``).

    Unlike :func:`make_mesh` this slices ``jax.devices()`` explicitly, so a
    host with more devices than requested still builds an n-way mesh (the
    CI/dev pattern: 4 fake CPU devices, meshes of 1/2/4)."""
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"--mesh {n} needs {n} devices but only {len(devs)} visible; "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before importing jax")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devs[:n]).reshape((n,)), ("model",))
