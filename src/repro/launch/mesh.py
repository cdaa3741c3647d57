"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* first jax use.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes):
    """A fully-Auto mesh (explicit axis types silence the Auto/Explicit
    migration warning of `jax.make_mesh`)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e); multi-pod adds the 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    return _make_mesh(shape, axes)
