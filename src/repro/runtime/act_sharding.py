"""Activation sharding constraints with logical axis names.

Model code calls `constrain(x, "dp", None, "tp", None)`; the logical axes
resolve against the mesh active at trace time ("dp" -> (pod, data),
"tp" -> model) with divisibility checks, and become
with_sharding_constraint calls. Outside a mesh context this is a no-op, so
single-device smoke tests are unaffected.

Pinning activations matters: GSPMD propagates shardings from weights, but
mixed-divisibility cases (e.g. 8 KV heads on a 16-way model axis) let
replicated operands "win" and silently blow up per-device activation
memory. These constraints are load-bearing for the production meshes'
memory budget.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _current_mesh():
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and m.axis_names:
            return m
    except Exception:
        pass
    return None


def _resolve(mesh, logical, dim):
    if logical is None:
        return None
    if logical == "dp":
        axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        axes = axes if len(axes) > 1 else (axes[0] if axes else None)
    elif logical == "tp":
        axes = "model" if "model" in mesh.axis_names else None
    else:
        axes = logical if logical in mesh.axis_names else None
    if axes is None:
        return None
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= dict(mesh.shape)[a]
    return axes if dim % size == 0 else None


def constrain(x, *logical_axes):
    mesh = _current_mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        return x
    spec = P(*[_resolve(mesh, ax, d)
               for ax, d in zip(logical_axes, x.shape)])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def constrain_any(x, *candidate_specs):
    """First candidate spec (tuple of logical axes) whose every named axis
    divides the corresponding dim is applied; otherwise no-op."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    for spec in candidate_specs:
        if len(spec) != x.ndim:
            continue
        ok = True
        for ax, d in zip(spec, x.shape):
            if ax is not None and _resolve(mesh, ax, d) is None:
                ok = False
                break
        if ok:
            return constrain(x, *spec)
    return x
