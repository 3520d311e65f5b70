"""Mesh construction: every mesh in the repo is built here.

JAX 0.9's ``jax.make_mesh`` defaults to ``AxisType.Explicit``, under which
a plain gather of a sharded array (``embed[tokens]``, ``x[:n]``) must name
its output sharding.  The code relies on sharding propagation instead
(``ShardingRules`` places arrays, jit propagates), so :func:`make_mesh`
builds every axis as ``AxisType.Auto``.

Functions only (never module-level meshes), so importing this module
touches no device state.  Production axes:
  pod   — outer data parallelism across pods (2 pods = 512 chips)
  data  — inner data parallelism / ZeRO sharding (16)
  model — tensor/expert parallelism (16)
Larger topologies (e.g. (8,16,16) = 2048 chips) only change ``shape``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (CI / smoke tests)."""
    data = max(1, n_devices // model)
    return make_mesh((data, model), ("data", "model"))
