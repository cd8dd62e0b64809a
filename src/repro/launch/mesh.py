"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization; tests
and benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the serve and dry-run programs
    leave partitioning to GSPMD (jax >= 0.9 defaults to Explicit axes,
    under which their gathers raise `ShardingTypeError`)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The 256-chip (`data`, `model`) pod mesh — or, with
    `multi_pod`, the 512-chip (`pod`, `data`, `model`) twin-pod one
    the dry-run cost tables assume."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small (`data`, `model`) mesh over however many devices exist.

    On a CPU-only box, `XLA_FLAGS=--xla_force_host_platform_device_count=N`
    (set BEFORE jax initializes) fakes N host devices — how CI and the
    README's "Scaling out" quickstart exercise the sharded serve loop
    without accelerators."""
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size}, e.g. {"data": 2, "model": 2}."""
    return dict(mesh.shape)
