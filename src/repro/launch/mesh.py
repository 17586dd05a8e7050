"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls these.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / reduced dry-runs), every axis ``Auto``:
    the repo shards through ``NamedSharding``/``with_sharding_constraint``
    and lets GSPMD propagate, which ``Explicit`` axes (``jax.make_mesh``'s
    default) reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods =
    512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a (data, model) mesh with model=1 —
    the CPU-container execution mesh for examples and smoke tests."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_cohort_mesh(n_devices: int | None = None):
    """1-D ``(data,)`` mesh for cohort-sharded AdaSplit training
    (``shard_clients=True``): the stacked client axis C is partitioned
    across these devices, C/ndev clients per shard.  On CI / laptops the
    devices are emulated host CPUs
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on a real
    box they are the accelerators."""
    n = n_devices or len(jax.devices())
    return make_mesh((n,), ("data",))
