"""Production mesh construction.

Functions (not module-level constants) so importing never touches jax
device state. Single pod: 256 chips as (data=16, model=16); multi-pod:
2 pods × 256 chips as (pod=2, data=16, model=16).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """``jax.sharding.AbstractMesh`` of ``shape`` over ``axes``.

    AbstractMesh carries only shape/axis metadata, so constructing it never
    touches device state.
    """
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))
