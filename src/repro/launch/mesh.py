"""Production meshes (DESIGN.md §7).

Single pod: a 16×16 TPU v5e slice (256 chips), axes (data, model).
Multi-pod: 2 pods = 512 chips, axes (pod, data, model) — the ``pod`` axis
carries only data/pipeline parallelism, never weight sharding (the paper's
"don't extend TP across the slow fabric" mapped to ICI-vs-DCI).

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init; the dry-run sets
``xla_force_host_platform_device_count=512`` before importing jax).
Every mesh uses Auto axis types: sharding is propagated by the compiler
outside ``jax.shard_map`` regions.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU unit tests (requires forced host device count)."""
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh_arg(spec: str) -> tuple[int, int]:
    """``--mesh DxM`` → (data, model). Accepts '2x2', '4x1', '1x2'."""
    try:
        d, m = spec.lower().split("x")
        d, m = int(d), int(m)
    except ValueError:
        raise ValueError(
            f"--mesh wants DATAxMODEL (e.g. 2x2), got {spec!r}") from None
    if d < 1 or m < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    return d, m


def make_decode_mesh(data: int, model: int):
    """(data, model) mesh for the sharded paged decode engine. Unlike
    ``make_test_mesh`` this takes the FIRST data*model devices rather
    than requiring an exact device-count match, so ``--mesh 2x2`` works
    on any host with >= 4 (virtual) devices. With exactly data*model
    devices ``jax.make_mesh`` orders them by the physical topology (a
    2x2 v5e host gets its ICI neighbours along each axis)."""
    import numpy as np
    need = data * model
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {data}x{model} needs {need} devices but jax sees "
            f"{len(devs)} — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before the first jax import")
    if len(devs) == need:
        return _auto_mesh((data, model), ("data", "model"))
    return jax.sharding.Mesh(
        np.asarray(devs[:need]).reshape(data, model), ("data", "model"))


def make_stage_mesh(stages: int):
    """CPP pipeline mesh (§5.1): one axis of prefill-group stages."""
    return _auto_mesh((stages,), ("stage",))


def batch_axes_of(mesh) -> tuple:
    """Mesh axes that carry the batch dimension (everything except
    'model' / 'stage')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
