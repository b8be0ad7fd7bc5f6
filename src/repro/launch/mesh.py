"""The serving mesh for sharded NVR detection.

A function, not a module-level constant, so importing this module never
touches jax device state (a CPU smoke run must set XLA_FLAGS before the
first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_serving_mesh(n_shards: int | None = None):
    """Serve-mode mesh for sharded NVR detection: ``n_shards`` entries
    on the ``data`` axis (the ``replica`` logical axis the serving
    sharding rules target), one model-parallel column each.

    Defaults to one shard per visible device.  Raises if the host has
    fewer devices than shards — on a CPU smoke host, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    the first jax import to fake an N-device mesh (what
    ``tests/test_sharded_serving.py::test_multi_device_mesh_subprocess``
    does).  Both axes are Auto: the sharding hooks
    (``sharding.context.constrain``) place activations with
    ``with_sharding_constraint``, which accepts only Auto mesh axes."""
    n = n_shards if n_shards is not None else len(jax.devices())
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"make_serving_mesh({n}) needs {n} devices but only {avail} "
            "are visible; set XLA_FLAGS=--xla_force_host_platform_"
            "device_count before the first jax import for CPU smoke "
            "meshes")
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
