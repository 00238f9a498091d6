"""Production meshes. Functions, not module constants — importing this file
never touches jax device state.

Every mesh is built with Auto axes: the step functions place shardings
with ``with_sharding_constraint`` and let GSPMD propagate them.
Explicit-axis meshes (what ``jax.make_mesh`` builds when not told
otherwise) reject the batch sharding on the axis that the microbatch
``lax.scan`` walks.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for CI-grade tests (requires forced host device count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_dp_mesh(dp: int):
    """Pure data-parallel ``(dp, 1)`` mesh over the first ``dp`` devices —
    the elastic-training mesh that remote-forked workers join."""
    return _auto_mesh((dp, 1), ("data", "model"), devices=jax.devices()[:dp])
