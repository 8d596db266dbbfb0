"""Production mesh construction (TPU v5e pods).

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips across 2 pods; the "pod"
axis carries only data parallelism + FSDP (cheap DCN-friendly collectives),
"model" stays intra-pod (ICI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the repo's sharding helpers
    (``sharding.shard``'s ``with_sharding_constraint``, GSPMD in_shardings)
    are written for Auto axes, and ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run under "
            "dryrun.py (it sets --xla_force_host_platform_device_count=512)"
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def make_data_mesh(n_shards: int, axis: str = "data"):
    """1-D data-parallel mesh over the first ``n_shards`` devices — the mesh
    the shard_map FSDP step and the ZeRO-sharded fused step run on (tests and
    benchmarks pair it with ``devices.force_host_device_count``)."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"data mesh needs {n_shards} devices, found {len(devices)} — "
            "call launch.devices.force_host_device_count first"
        )
    return make_mesh((n_shards,), (axis,), devices=devices[:n_shards])
