"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. `make_production_mesh` needs 256 devices (512 multi-pod); smoke
tests and benchmarks see the real single device and use `make_test_mesh`.

Every axis is ``AxisType.Auto``: the sharding rules in runtime.sharding
hand specs to jit and let the compiler propagate the rest, which explicit
axes (jax.make_mesh's default) refuse for the KV-cache scatters.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh() -> Mesh:
    """1-device mesh with the production axis names (CPU tests)."""
    return _auto_mesh((1, 1), ("data", "model"))
