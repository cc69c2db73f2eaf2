"""Production mesh factory (a FUNCTION — importing never touches devices).

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the engine indexes stacked,
    sharded arrays, which Explicit axes (JAX's default) reject."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many (host) devices exist — tests only."""
    n = n_devices or len(jax.devices())
    assert n % model == 0, (n, model)
    return _mesh((n // model, model), ("data", "model"))


def make_dp_mesh(n_devices: int | None = None):
    """Pure data-parallel ``("data",)`` mesh over the first N devices.

    Used by the sharded subgraph-pool engine: one pool shard per device,
    gradients all-reduced across the axis. On CPU hosts force devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
    imports.
    """
    avail = len(jax.devices())
    n = n_devices or avail
    if n > avail:
        raise ValueError(
            f"requested data-parallel degree {n} > {avail} visible "
            "devices (set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} before importing jax to simulate)")
    return _mesh((n,), ("data",), devices=jax.devices()[:n])


def parse_mesh_spec(spec: str):
    """Parse ``--mesh`` CLI specs like ``"data:4"`` or ``"4"``.

    Returns a mesh whose axes follow the spec order; a bare integer means
    a pure ``("data",)`` mesh of that size.
    """
    parts = [p for p in spec.split(",") if p]
    if len(parts) == 1 and ":" not in parts[0]:
        return make_dp_mesh(int(parts[0]))
    names, sizes = [], []
    for p in parts:
        name, _, size = p.partition(":")
        names.append(name)
        sizes.append(int(size))
    return _mesh(tuple(sizes), tuple(names))


def dp_axes(mesh, global_batch: int):
    """Mesh axes usable for the batch dim (must divide global_batch)."""
    names = [a for a in ("pod", "data") if a in mesh.axis_names]
    size = 1
    kept = []
    for a in names:
        s = mesh.shape[a]
        if global_batch % (size * s) == 0:
            kept.append(a)
            size *= s
    return tuple(kept) if kept else None
