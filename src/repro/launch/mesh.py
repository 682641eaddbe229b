"""Mesh construction: one helper every mesh of the repo goes through.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state; only calling it does (after the caller has set
XLA_FLAGS if it wants placeholder devices — see launch.dryrun).

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to
``Explicit`` axes, on which ``with_sharding_constraint`` turns into an
assert, while :func:`repro.distributed.sharding.constrain` relies on it
being a hint that XLA's SPMD partitioner propagates.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 v5e pod (256 chips), or 2 such pods (512 chips).

    Axes: ``data`` (batch / fsdp), ``model`` (TP/EP), plus ``pod`` (DP over
    DCN) in the multi-pod configuration.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist right now, as a 1-D data mesh (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
