"""Production mesh construction.

Target hardware: TPU v5e, 256 chips per pod (16×16), optionally 2 pods.
Axes: ``data`` (batch / ZeRO), ``model`` (tensor/expert/context parallel),
``pod`` (multi-pod data parallel outer axis).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

from typing import Tuple

import jax


def _auto(n: int) -> tuple:
    """Automatic axes: ``jax.make_mesh`` defaults to ``Explicit`` sharding,
    which the pjit train step and the fleet's ``shard_map`` steps were not
    written for (an explicit-axis embedding gather raises
    ``ShardingTypeError``)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    import numpy as np

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "the dry-run launcher sets xla_force_host_platform_device_count=512"
        )
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)),
                         devices=devices[:n])


def make_host_mesh() -> jax.sharding.Mesh:
    """A 1×1 mesh over the local device — smoke tests of the pjit path."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def make_fleet_mesh(n_devices: int | None = None, *,
                    model_shards: int = 1) -> jax.sharding.Mesh:
    """The fleet-serving mesh: 1-D ``("data",)``, or 2-D ``("data",
    "model")`` when ``model_shards > 1``.

    ``StreamEngine`` partitions its per-stream ring arena over the ``data``
    axis so each device owns a contiguous shard of plants and runs the
    detector step on it locally (no cross-device traffic on the hot path).
    With ``model_shards=m`` the serving core additionally column-shards
    wide Dense layers over the ``model`` axis — each of the ``m`` ranks per
    data shard computes its own slice of the layer's output columns and one
    tiled ``all_gather`` recombines them (``serving/core.py``).

    ``n_devices`` is the **data-axis** width; it defaults to every visible
    device (divided by ``model_shards`` for a 2-D mesh).  The mesh takes a
    prefix of the device list, so 1/2/4-way meshes can coexist in one
    multi-device process (the sharded-parity tests rely on this).
    """
    devices = jax.devices()
    if model_shards < 1:
        raise RuntimeError(f"model_shards must be >= 1, got {model_shards}")
    if model_shards == 1:
        n = len(devices) if n_devices is None else n_devices
        if not 1 <= n <= len(devices):
            raise RuntimeError(
                f"fleet mesh needs 1..{len(devices)} devices, asked for {n}; "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=<n> to "
                "fan out host devices")
        return jax.make_mesh((n,), ("data",), axis_types=_auto(1),
                             devices=devices[:n])
    n_data = (len(devices) // model_shards if n_devices is None
              else n_devices)
    need = n_data * model_shards
    if n_data < 1 or need > len(devices):
        raise RuntimeError(
            f"fleet mesh ({n_data}, {model_shards}) needs {need} devices "
            f"but only {len(devices)} present; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<n> to fan "
            "out host devices")
    return jax.make_mesh((n_data, model_shards), ("data", "model"),
                         axis_types=_auto(2), devices=devices[:need])


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The batch-parallel axes for this mesh ('pod' folds into data)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)

