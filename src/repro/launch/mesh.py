"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS --xla_force_host_platform_device_count=512 before any jax import
and then calls it.

Single pod:  (16, 16)      axes ("data", "model")   — 256 chips (v5e pod)
Multi pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips.
The "pod" axis carries pure data parallelism; gradient reduction across it
is the slow-link collective the multi-pod dry-run proves out.

Every axis is ``AxisType.Auto``: ``dist.sharding.shard`` annotates with
``with_sharding_constraint``, which the Explicit axes that
``jax.make_mesh`` builds by default on JAX 0.9 reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
