"""Logical-axis sharding annotations (DESIGN.md §5).

Model code names the MEANING of each tensor dimension; the launcher names
the HARDWARE.  ``axis_rules`` installs a (rules, mesh) binding for the
current thread; inside it, ``shard`` lowers logical names to
``jax.lax.with_sharding_constraint`` with a :class:`NamedSharding`.
Outside any binding ``shard`` is the identity, which is what lets the
tier-1 test suite exercise the exact production model code on one CPU
device.

Rules values may be a physical axis name (``"model"``), a tuple of axis
names (``("pod", "data")`` — the multi-pod batch axis), or ``None``
(replicate).  A rule whose axis size does not divide the dimension is
dropped to ``None`` instead of failing, so reduced smoke configs never
trip divisibility errors — but the drop is no longer silent: the first
time a given rule is dropped a :class:`ShardingRuleDropped` warning
fires (once per rule, process-wide), so a production misconfig that
quietly replicates a tensor it was meant to shard is visible in the
serving logs.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["DEFAULT_RULES", "axis_rules", "shard", "named_sharding",
           "current_rules",
           "resolve_spec", "mesh_axis_sizes", "axis_size",
           "ShardingRuleDropped"]

Axis = Union[None, str, Tuple[str, ...]]

#: Logical -> physical defaults for meshes with axes ("data", "model")
#: (``launch.mesh.make_mesh``); ``axis_rules`` overrides them.
DEFAULT_RULES: Dict[str, Axis] = {
    "batch": "data",        # pure data parallelism
    "seq": None,            # full sequences per shard
    "seq_res": None,        # residual-stream seq axis (Megatron SP opt-in)
    "embed": None,          # d_model stays replicated (activations)
    "heads": "model",       # tensor parallel attention
    "kv_heads": "model",
    "ffn": "model",         # tensor parallel MLP hidden
    "vocab": "model",       # sharded logits / lm_head
    "experts": "model",     # expert parallelism (MoE)
}

_STATE = threading.local()


class ShardingRuleDropped(UserWarning):
    """A logical-axis rule was dropped at lowering time because the mesh
    axis size does not divide the tensor dimension — the dim replicates
    instead of sharding.  Benign in reduced smoke configs; in production
    it means a tensor you meant to shard is fully replicated."""


#: (logical name, physical axis, axis size, dim) drops already warned
#: about — once per rule GEOMETRY, not per call, so a hot serving loop
#: logs one line, not millions, while a later drop of the same rule at a
#: DIFFERENT size/dim (e.g. smoke warm-up then misconfigured production
#: mesh in one process) still surfaces.
_DROP_WARNED: set = set()


def current_rules() -> Optional[Tuple[Dict[str, Axis], Mesh]]:
    """The active (rules, mesh) binding, or None outside axis_rules."""
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def axis_rules(rules: Dict[str, Axis], mesh: Mesh):
    """Bind logical axis names to physical mesh axes for this thread."""
    prev = current_rules()
    _STATE.ctx = (dict(rules), mesh)
    try:
        yield
    finally:
        _STATE.ctx = prev


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """{axis name: size} for a mesh (what divisibility is checked against)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def axis_size(sizes: Dict[str, int], ax: Axis) -> int:
    """Devices along ``ax``: one mesh axis, or the product of a tuple."""
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(ax, 1)


def resolve_spec(rules: Dict[str, Axis], sizes: Dict[str, int],
                 shape: Tuple[int, ...],
                 logical_axes: Tuple[Optional[str], ...]) -> Tuple[Axis, ...]:
    """Lower logical axis names to a physical PartitionSpec tuple.

    Unknown names and ``None`` replicate silently (that is the contract:
    the name has no binding).  A KNOWN rule whose axis size does not
    divide the dimension is dropped to replicated with a once-per-rule
    :class:`ShardingRuleDropped` warning — reduced smoke configs keep
    running, production misconfigs become visible.  Factored out of
    :func:`shard` (which feeds it the active mesh) so the divisibility
    policy is unit-testable without multi-device meshes.
    """
    phys = []
    for dim, name in zip(shape, logical_axes):
        ax = rules.get(name) if isinstance(name, str) else None
        if ax is not None:
            n = axis_size(sizes, ax)
            if dim % n != 0:
                phys_ax = ax if isinstance(ax, str) else tuple(ax)
                key = (name, phys_ax, n, dim)
                if key not in _DROP_WARNED:
                    _DROP_WARNED.add(key)
                    warnings.warn(
                        f"sharding rule {name!r} -> {phys_ax!r} dropped: "
                        f"mesh axis size {n} does not divide dim {dim}; "
                        f"the dimension replicates instead",
                        ShardingRuleDropped, stacklevel=3)
                ax = None
        phys.append(tuple(ax) if isinstance(ax, list) else ax)
    return tuple(phys)


def named_sharding(shape: Tuple[int, ...], *logical_axes: Optional[str]
                   ) -> Optional[NamedSharding]:
    """The :class:`NamedSharding` that :func:`shard` gives an array of
    ``shape``: None outside an :func:`axis_rules` context, or where the
    axis names do not match the rank."""
    ctx = current_rules()
    if ctx is None or len(shape) != len(logical_axes):
        return None
    rules, mesh = ctx
    phys = resolve_spec(rules, mesh_axis_sizes(mesh), shape, logical_axes)
    return NamedSharding(mesh, P(*phys))


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate ``x`` with one logical axis name (or None) per dimension.

    Identity outside an :func:`axis_rules` context.  Unknown names
    replicate; indivisible dimensions replicate with a once-per-rule
    :class:`ShardingRuleDropped` warning.  A rank that does not match the
    names is left as it is: never fail model code.
    """
    s = named_sharding(x.shape, *logical_axes)
    return x if s is None else jax.lax.with_sharding_constraint(x, s)
