"""Mesh construction.

``make_mesh`` is a FUNCTION (not a module-level constant) so importing
this module never touches jax device state.  Every axis is
``AxisType.Auto``: ``dist.sharding.shard`` annotates with
``with_sharding_constraint``, which the Explicit axes that
``jax.make_mesh`` builds by default on JAX 0.9 reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(shape, axes):
    """A mesh of ``shape`` over ``axes`` (tests use small ones, e.g.
    (1, 1); sharded serving a (4, 1) ("data", "model") one)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
