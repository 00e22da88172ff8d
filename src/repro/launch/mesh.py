"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) over ('data','model') — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) over ('pod','data','model') — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use tiny ones, e.g. (2,2) on 4 host devices).

    Axes are ``Auto``: the compiler propagates shardings, as the code here
    assumes (JAX 0.9's ``jax.make_mesh`` defaults to ``Explicit`` axes,
    whose sharding-in-types rejects e.g. an embedding gather across
    differently sharded operands)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes_of(mesh) -> Tuple[str, ...]:
    """All non-'model' axes carry the batch (pod composes with data)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def describe(mesh) -> str:
    return f"mesh{tuple(mesh.devices.shape)} axes={mesh.axis_names} chips={mesh.devices.size}"
