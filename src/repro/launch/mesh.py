"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS`` before the first jax device query.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one 256-chip v5e-class pod) or 2x16x16 (two pods).

    Axes: ``data`` carries batch + FSDP; ``model`` carries TP/CP/EP/vocab;
    ``pod`` (multi-pod only) carries pure data parallelism so the only
    inter-pod collective is the per-step gradient all-reduce.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for multi-device unit tests (needs host-device override)."""
    return _make_mesh(shape, axes)
