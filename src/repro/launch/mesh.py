"""Every device mesh of the repo is built here. FUNCTIONS, not module
constants, so importing this module never touches jax device state (the
dry-run must set XLA_FLAGS before any jax initialization).

Axes are ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes, under
which the sharded junctions' ``shard_map`` fails with "Unexpected XLA
sharding override". The repo places arrays with ``NamedSharding`` and
sharding constraints, which is what ``Auto`` axes mean.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh of ``shape`` over ``axes`` (Auto axis types). ``devices``
    defaults to the first ``prod(shape)`` devices JAX reports."""
    shape, axes = tuple(shape), tuple(axes)
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    With more devices available than the mesh needs (the dry-run forces 512
    host devices and then builds the single-pod 256-chip mesh), the first
    prod(shape) devices are used.
    """
    import numpy as np
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    return make_mesh(shape, axes, devices=devices[:need])
