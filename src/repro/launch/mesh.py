"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 device; only
``dryrun.py`` sets ``xla_force_host_platform_device_count`` before jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ("data","model") single pod; (2,16,16) with a leading "pod"
    axis for the 2-pod (512-chip) deployment. The pod axis is pure data
    parallelism: model axes never cross the inter-pod (DCI) boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh with Auto axes: the harness places arrays by
    ``with_sharding_constraint`` (``distributed.sharding.constrain``), which
    ``jax.make_mesh``'s default Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_devices(mesh) -> int:
    import numpy as np

    return int(np.prod(mesh.devices.shape))
