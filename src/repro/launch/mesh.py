"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state.

Single pod : (16, 16)    -> ("data", "model")      = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) -> ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax


def _auto(axes):
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, small-scale runs, elastic re-meshing)."""
    return jax.make_mesh(tuple(shape), tuple(axes), _auto(axes))


def make_host_mesh():
    """Whatever devices exist, as a 1-D 'data' mesh (CPU smoke runs)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
