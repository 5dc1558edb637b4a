"""Production mesh definition.

Single pod: 16×16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — 'pod' is a pure
data-parallel axis across the DCN/ICI-superpod boundary.

Serving:    (data, model) with the model axis carrying tensor parallelism —
:func:`make_serving_mesh` sizes it from the requested TP degree.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: sharding stays with the compiler and the
    explicit shard_map call paths, as every mesh in the repo expects."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale distribution tests (8 virtual devices)."""
    return _make_mesh(shape, axes)


def make_serving_mesh(tp: int = 1, *, data: int = 0):
    """(data, model) mesh for tensor-parallel serving.

    ``tp`` is the model-axis (tensor-parallel) degree; ``data=0`` spreads the
    remaining local devices over the data axis. The paged serving engine
    shards KV page storage and the projection weights over ``model`` and
    keeps scheduler state replicated (see :mod:`repro.serving`).
    """
    n = len(jax.devices())
    if n % tp:
        raise ValueError(f"tp={tp} does not divide device count {n}")
    data = data or n // tp
    return _make_mesh((data, tp), ("data", "model"))
