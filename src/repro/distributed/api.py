"""Mesh-aware sharding-constraint helpers.

All model code calls ``constrain(x, "axis0", "axis1", ...)`` with *logical*
axis names; the helper resolves them against the ambient mesh (set by
``jax.set_mesh`` around the jit) and silently drops axes the mesh does not
have. This lets the same model run un-meshed on one device (smoke tests),
on the (data, model) single-pod mesh, and on the (pod, data, model)
multi-pod mesh without code changes. Jits take raw PartitionSpecs for
their in/out shardings; ``jax.set_mesh`` resolves them.

Logical axis conventions:
  "dp"    -> sharded over ("pod", "data") (whichever exist)
  "model" -> sharded over "model" (tensor/expert parallel)
  None    -> replicated
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
from jax.sharding import PartitionSpec as P  # noqa: F401

AxisName = Union[None, str, tuple]

_DP = ("pod", "data")


def ambient_mesh():
    """The mesh ``jax.set_mesh`` made ambient, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axes() -> tuple[str, ...]:
    m = ambient_mesh()
    return tuple(m.axis_names) if m is not None else ()


def has_axis(name: str) -> bool:
    return name in mesh_axes()


def dp_axes() -> tuple[str, ...]:
    """Mesh axes that play the data-parallel role."""
    return tuple(a for a in _DP if has_axis(a))


def _resolve(axis: AxisName, axes: tuple[str, ...]):
    if axis is None:
        return None
    if axis == "dp":
        got = tuple(a for a in _DP if a in axes)
        return got if got else None
    if isinstance(axis, tuple):
        got = tuple(a for sub in axis for a in (_resolve(sub, axes),)
                    if a is not None)
        flat: list[str] = []
        for a in got:
            flat.extend(a if isinstance(a, tuple) else (a,))
        return tuple(flat) if flat else None
    return axis if axis in axes else None


def axis_size(name: str) -> int:
    m = ambient_mesh()
    if m is None or name not in m.axis_names:
        return 1
    return m.shape[name]


def _prod_size(resolved) -> int:
    if resolved is None:
        return 1
    if isinstance(resolved, tuple):
        out = 1
        for a in resolved:
            out *= axis_size(a)
        return out
    return axis_size(resolved)


def spec(*logical: AxisName) -> P:
    axes = mesh_axes()
    return P(*[_resolve(a, axes) for a in logical])


def shaped_spec(shape: Sequence[int], *logical: AxisName) -> P:
    """Like spec() but drops any axis whose mesh-size does not divide the
    corresponding dimension (e.g. 8 KV heads on a 16-way model axis)."""
    axes = mesh_axes()
    out = []
    for dim, a in zip(shape, logical):
        r = _resolve(a, axes)
        if r is not None and dim % _prod_size(r) != 0:
            # try progressively shorter prefixes of a tuple spec
            if isinstance(r, tuple):
                while r and dim % _prod_size(r) != 0:
                    r = r[:-1]
                r = r if r else None
            else:
                r = None
        out.append(r)
    return P(*out)


def constrain(x: jax.Array, *logical: AxisName) -> jax.Array:
    """with_sharding_constraint with logical axes; no-op without a mesh.
    Axes that do not divide the dimension are dropped (replicated)."""
    axes = mesh_axes()
    if not axes:
        return x
    s = shaped_spec(x.shape, *logical)
    return jax.lax.with_sharding_constraint(x, s)
