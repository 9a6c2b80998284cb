"""Production mesh construction.

Functions (not module-level constants) so importing never touches jax
device state. Single pod: 256 chips as (data=16, model=16). Multi-pod: 2
pods × 256 = 512 chips as (pod=2, data=16, model=16) — the ``pod`` axis
maps onto the DCN dimension; policies keep only gradient/FSDP traffic on
it (DESIGN.md §5).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh_for(devices_per_pod: int, n_pods: int = 1, model_parallel: int = 16):
    """Elastic variant: arbitrary pod count/size (restart after pod loss)."""
    data = devices_per_pod // model_parallel
    if n_pods > 1:
        return auto_mesh((n_pods, data, model_parallel), ("pod", "data", "model"))
    return auto_mesh((data, model_parallel), ("data", "model"))


def fleet_mesh(n_devices: int | None = None, axis: str = "dimm"):
    """1-D mesh over the DIMM axis — the fleet-characterization data mesh.

    The fleet pipeline (``fleet.sweep``, ``controller.replay``,
    ``perfmodel.trace_score``) is embarrassingly parallel over DIMMs, so
    its mesh is a single ``("dimm",)`` axis spanning every available
    device (default) or the first ``n_devices`` of them. Works on any
    backend; on CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax call to expose N host devices (the CI multi-device job runs
    the sharded parity gates this way).
    """
    avail = jax.device_count()
    n = avail if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n}")
    if n > avail:
        raise ValueError(
            f"requested {n} devices but only {avail} are available; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the first jax call to expose host devices"
        )
    return auto_mesh((n,), (axis,))
