"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init.

Production target: TPU v5e pods, 256 chips each in a 16×16 (data, model)
mesh; ``multi_pod=True`` adds the leading ``pod`` axis (2 pods = 512 chips).
The ``pod`` axis participates in data parallelism (gradient psum crosses the
inter-pod DCI; see the E8MY gradient-compression option for that link).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
