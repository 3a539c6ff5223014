"""Random weights for a whole model from one key, in two draws.

A model's leaves are listed as ``(shape, dist, scale, offset)``: ``dist``
``"normal"`` gives ``offset + scale * N(0, 1)`` and ``"uniform"`` gives
``offset + scale * U(0, 1)``.  All normal leaves are cut from one
``jax.random.normal`` vector and all uniform ones from one
``jax.random.uniform`` vector, so the jitted program that makes them is
small and quick to compile and load, whatever the number of leaves.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax

Leaf = Tuple[Sequence[int], str, float, float]


def draw(key: jax.Array, leaves: List[Leaf]) -> List[jax.Array]:
    """The arrays of ``leaves``, in order."""
    sizes = [math.prod(shape) for shape, *_ in leaves]
    kn, ku = jax.random.split(key)
    pools = {
        "normal": jax.random.normal(kn, (sum(
            n for n, l in zip(sizes, leaves) if l[1] == "normal"),)),
        "uniform": jax.random.uniform(ku, (sum(
            n for n, l in zip(sizes, leaves) if l[1] == "uniform"),)),
    }
    at = {"normal": 0, "uniform": 0}
    out = []
    for n, (shape, dist, scale, offset) in zip(sizes, leaves):
        flat = pools[dist][at[dist]:at[dist] + n]
        at[dist] += n
        out.append(offset + scale * flat.reshape(shape))
    return out

