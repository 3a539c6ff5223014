"""Distributed-execution utilities: logical-axis sharding annotations,
parameter/cache PartitionSpec trees, and BFP gradient compression.

The model code annotates activations with LOGICAL axis names
(``sharding.shard(x, "batch", "seq", "heads", None)``); the launchers bind
logical names to physical mesh axes with ``sharding.axis_rules``.  Outside
an ``axis_rules`` context every annotation is the identity, so the same
model code runs unmodified on a single CPU host (tests) and on the
meshes the launchers build (``launch.mesh.make_mesh``).

``compress`` carries the BFP gradient wire: ``quantize_leaf`` is the
jit-safe in-graph model, ``pack_leaf``/``wire_report`` the actual
bit-packed bytes (``core.packed.PackedBFP``, DESIGN.md §10) — pinned
bit-exact against each other, padding counted honestly.
"""
