"""Production mesh definitions.

A *function*, not a module-level constant — importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips (v5e pod);
multi-pod: 2×16×16 = 512 chips with a leading "pod" axis (DCI-connected).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules in
    ``repro.dist.sharding`` place values with ``with_sharding_constraint``,
    which only accepts Auto axes (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    return _make_mesh(shape, axes, devices)


def make_host_mesh(n_devices: int | None = None, model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    n = len(devs)
    data = n // model_axis
    return _make_mesh((data, model_axis), ("data", "model"), devs[: data * model_axis])


def make_data_mesh(n_devices: int | None = None):
    """Pure data-parallel mesh: every local device on the ``data`` axis and a
    size-1 ``model`` axis so the dist.sharding placement rules still resolve.

    This is the mesh the Trainer activates for data-parallel ``fit``:
    parameters are replicated, only the batch dim is split, and XLA's SPMD
    partitioner inserts the mean all-reduce over per-shard gradients."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return _make_mesh((len(devs), 1), ("data", "model"), devs)
