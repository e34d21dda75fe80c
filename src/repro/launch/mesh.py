"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state. The dry-run entry
point sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before
any jax import so 512 placeholder CPU devices exist; tests and benchmarks
see the real single device.

Topology: 16x16 = 256 chips per pod (v5e pod slice); multi-pod prepends a
``pod`` axis (2 pods = 512 chips). ``pod`` is hierarchical data parallelism
(DCN-connected), ``data`` is in-pod data parallelism, ``model`` is tensor /
expert parallelism on the fastest ICI dimension.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_auto_mesh",
    "make_production_mesh",
    "make_host_mesh",
    "make_shard_mesh",
]


def make_auto_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """``jax.make_mesh`` with every axis pinned to ``AxisType.Auto`` (the
    pre-explicit-sharding semantics the sharding rules are written for)."""
    axes = tuple(axes)
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes), **kwargs
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax"
        )
    return make_auto_mesh(shape, axes, devices=devices[:n])


def make_shard_mesh(n_shards: Optional[int] = None, axis: str = "shard") -> Mesh:
    """1-D mesh over the first ``n_shards`` devices (default: all) — the
    mesh shape the sharded SpGEMM plan partitions its panel schedule over.

    This is the one sanctioned way to get an SpGEMM device mesh: plans key
    their cache entries on the mesh's axis/devices, so building meshes here
    (rather than from ad-hoc device lists) keeps pattern-equal callers on
    the same cache entry.
    """
    devices = jax.devices()
    if n_shards is None:
        n_shards = len(devices)
    if n_shards < 1 or n_shards > len(devices):
        raise ValueError(
            f"n_shards={n_shards} out of range for {len(devices)} devices"
        )
    return make_auto_mesh((n_shards,), (axis,), devices=devices[:n_shards])


def make_host_mesh(
    shape: Optional[Sequence[int]] = None,
    axes: Sequence[str] = ("data", "model"),
) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return make_auto_mesh(shape, axes)
