"""Mesh builders. Functions, not module constants — importing this module
never touches jax device state (device count is locked at first use)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model). Multi-pod: 2 pods of 256
    with a leading "pod" axis (data-parallel across the DCN/ICI boundary).
    Requires 256/512 visible devices (real TPUs or
    --xla_force_host_platform_device_count, see dryrun.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1-D data mesh (CPU tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
