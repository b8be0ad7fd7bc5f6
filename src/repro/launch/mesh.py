"""Production mesh definitions (TPU v5e-class pods).

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first
jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding hooks
    (``sharding.context.constrain``) place activations with
    ``with_sharding_constraint``, which accepts only Auto mesh axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_replica_mesh(n_replicas: int, chips_per_replica: int = 1):
    """Paper-mode mesh: n parallel detection-model replicas over the
    ``replica`` axis (the paper's n NCS2 sticks), each replica spanning
    ``chips_per_replica`` model-parallel chips."""
    return _mesh((n_replicas, chips_per_replica),
                         ("data", "model"))


def make_host_mesh():
    """Single-host CPU mesh for smoke runs."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


def make_serving_mesh(n_shards: int | None = None):
    """Serve-mode mesh for sharded NVR detection: ``n_shards`` entries
    on the ``data`` axis (the ``replica`` logical axis the serving
    sharding rules target), one model-parallel column each.

    Defaults to one shard per visible device.  Raises if the host has
    fewer devices than shards — on a CPU smoke host, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    the first jax import to fake an N-device mesh (what
    ``benchmarks/sharded_bench.py`` does)."""
    n = n_shards if n_shards is not None else len(jax.devices())
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"make_serving_mesh({n}) needs {n} devices but only {avail} "
            "are visible; set XLA_FLAGS=--xla_force_host_platform_"
            "device_count before the first jax import for CPU smoke "
            "meshes")
    return _mesh((n, 1), ("data", "model"))
