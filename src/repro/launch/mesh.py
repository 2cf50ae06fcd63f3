"""Production meshes.

Single pod: 16×16 = 256 chips ("data", "model").
Multi-pod:  2×16×16 = 512 chips ("pod", "data", "model") — the "pod" axis
extends data parallelism across pods (gradient all-reduce crosses the pod
boundary; everything else stays intra-pod).

Defined as a FUNCTION so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "auto_mesh"]


def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``.

    The model code places arrays through sharding constraints and lets the
    partitioner propagate the rest; ``jax.make_mesh`` now defaults to
    ``Explicit`` axes, under which those constraints and an unannotated
    gather from a sharded embedding table are refused.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(shape: Optional[Tuple[int, ...]] = None,
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return auto_mesh(shape, axes)
