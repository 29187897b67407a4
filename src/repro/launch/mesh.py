"""Production mesh construction.

Single pod: 256 chips as (16, 16) = ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16) = ("pod", "data", "model");
the "pod" axis carries data parallelism for synchronous training and is the
NodIO *island* axis for pool-based evolution (launch/evolve.py).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "islands") -> Mesh:
    """1-D mesh over however many (possibly fake) devices exist — used by
    the sharded evolution runner and small-mesh tests."""
    devs = jax.devices()[: (n or len(jax.devices()))]
    return make_mesh((len(devs),), (axis,), devices=devs)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes carrying data parallelism (batch sharding)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes: Tuple[str, ...] | str) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
