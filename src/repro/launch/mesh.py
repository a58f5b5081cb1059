"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the dry-run's 512 placeholder
devices to be configured before first jax init.
"""
from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the local device — smoke tests / examples."""
    return compat.make_mesh((1, 1), ("data", "model"))


def make_serve_mesh(data: int = 1, model: int = 1):
    """Serving mesh for ``ShardedServeEngine``: batch slots shard over
    "data", tensor parallelism over "model". Works against real devices
    or a forced host platform (XLA_FLAGS=--xla_force_host_platform_
    device_count=N set before jax initializes)."""
    return compat.make_mesh((data, model), ("data", "model"))
