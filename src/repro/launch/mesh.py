"""Production meshes. Defined as functions so importing never touches jax
device state (jax locks the device count on first backend init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/smokes."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def force_host_device_count(n: int) -> None:
    """Give a CPU-only host ``n`` virtual devices. Must run before jax
    initializes its backend (importing jax is fine; touching devices is
    not). A no-op when the flag is already present — an existing smaller
    count wins, and ``make_population_mesh`` will then fail loudly rather
    than silently undershard."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def make_population_mesh(slots: int, data: int = 1):
    """Mesh for the multi-device population engine: the ``slots`` axis
    shards a bucket's slot dimension (one trial subset per device), the
    ``data`` axis is reserved for env-batch data parallelism (currently
    replicated). Testable on CPU via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    return jax.make_mesh((slots, data), ("slots", "data"),
                         axis_types=(AxisType.Auto,) * 2)
