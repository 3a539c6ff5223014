"""Offline weight pre-quantization — the paper's deployment mode.

The accelerator stores weights in HBM as int8 mantissas + a small
power-of-two scale sidecar (block exponents), so every weight read moves
~4x fewer bytes than f32 (2x fewer than bf16) and FSDP weight all-gathers
shrink by the same factor — the paper's off-chip-traffic argument
(§1, §3.1) applied to TPU HBM and ICI.

Wire format (consumed FIRST-CLASS by every repro.engine backend):

    {"m": int mantissa [.., K, N],  "s": f32 scale [.., K//bk, N]}

``s`` holds the quantizer's power-of-two steps ``2^(e - (L_W - 2))``, so
the emulated integer datapath and the Pallas prequant kernel reproduce
BIT-EXACTLY what in-line ``quantize_weights`` would have produced for
Scheme.TILED with the same ``block_k`` (or per-column / eq. 4 blocks when
``block_k`` is None) — but the quantization runs ONCE, not per forward.

``quantize_param_tree`` converts LM-style trees (>=2-D GEMM leaves,
possibly stacked [L, K, N]); ``quantize_cnn_param_tree`` walks CNN trees,
lowering HWIO conv kernels to their GEMM view (``core.conv_utils``
HWIO-major K-order, the fused conv kernel's K-tiling).  Both accept
a single :class:`BFPPolicy` or a per-layer ``repro.engine.PolicyMap``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.core.policy import BFPPolicy

__all__ = ["quantize_param_tree", "quantize_cnn_param_tree", "prequant_leaf",
           "prequant_conv_leaf", "dequantize_prequant", "is_prequant",
           "prequant_act", "dequantize_act", "act_block",
           "lm_rule_path", "lm_eligible", "cnn_rule_path",
           "detect_tree_kind"]


def is_prequant(w: Any) -> bool:
    return isinstance(w, dict) and "m" in w and "s" in w


def detect_tree_kind(params: Any) -> str:
    """"lm" or "cnn" — THE param-tree convention detector.

    Single source of truth shared by ``engine.bind`` and
    ``core.packed.pack_param_tree`` (checkpoint ``format="bfp_packed"``),
    so the walk that packs a checkpoint can never classify a tree
    differently from the walk that binds it.
    """
    if isinstance(params, dict) and (
            {"embed", "layers", "dec", "periods"} & set(params)):
        return "lm"
    return "cnn"


def _resolve(policy: Any, path: Optional[str]) -> Optional[BFPPolicy]:
    # Lazy import: engine.policy_map depends on core.policy; importing it
    # at module scope here would cycle through repro.engine.__init__.
    from repro.engine.policy_map import resolve_policy
    return resolve_policy(policy, path)


def prequant_leaf(w: jax.Array, policy: BFPPolicy) -> Any:
    """[.., K, N] float -> {"m": int8 [.., K, N], "s": f32 [.., K/bk, N]}."""
    if w.ndim < 2:
        return w
    k = w.shape[-2]
    if k % (policy.block_k or k):
        return w  # odd contraction dim: leave in float
    return _prequant(w, policy)


@functools.partial(jax.jit, static_argnums=1)
def _prequant(w: jax.Array, policy: BFPPolicy) -> Any:
    """:func:`prequant_leaf` of an eligible leaf, as one compiled program
    per shape and policy: run op by op, a leaf compiled ~20 programs, so
    a bind on a cold compile cache compiled ~540 for ResNet-50 or
    GoogLeNet."""
    lead = w.shape[:-2]
    k, n = w.shape[-2:]
    bk = policy.block_k or k
    w2 = w.reshape(-1, k, n)

    def one(mat):
        blk = bfp.bfp_quantize_matrix(mat, policy.l_w, "i", bfp.Scheme.TILED,
                                      bk, policy.rounding)
        return blk.mantissa, bfp.pow2(blk.exponent - (policy.l_w - 2))

    m, s = jax.vmap(one)(w2)
    return {"m": m.reshape(*lead, k, n),
            "s": s.reshape(*lead, k // bk, n)}


def prequant_conv_leaf(w_hwio: jax.Array, policy: BFPPolicy) -> Any:
    """HWIO conv kernel -> prequant dict with the mantissa kept in HWIO.

    Quantization happens in the conv GEMM view ``[kh*kw*C, out]`` — the
    repo-wide HWIO-major K-order (core.conv_utils), which is also exactly
    the K-tiling the fused implicit-im2col Pallas kernel streams — so the
    sidecar blocks ARE the conv kernel's K-tiles and prequant execution is
    bit-exact vs inline quantization on both the fused and im2col routes.
    The mantissa is reshaped back to HWIO so the layer can still read
    (kh, kw, in_ch, out_ch) off the array shape; ``s`` stays in the GEMM
    view [K//bk, N].
    """
    if w_hwio.ndim != 4:
        return w_hwio
    kh, kw, c, n = w_hwio.shape
    if (kh * kw * c) % (policy.block_k or kh * kw * c):
        return w_hwio  # block_k does not divide kh*kw*C
    return _prequant_conv(w_hwio, policy)


@functools.partial(jax.jit, static_argnums=1)
def _prequant_conv(w_hwio: jax.Array, policy: BFPPolicy) -> Any:
    """:func:`_prequant` of the GEMM view, the reshapes in the program."""
    kh, kw, c, n = w_hwio.shape
    d = _prequant(w_hwio.reshape(kh * kw * c, n), policy)
    return {"m": d["m"].reshape(kh, kw, c, n), "s": d["s"]}


def dequantize_prequant(w: Any, dtype=jnp.float32) -> jax.Array:
    """Materialize a prequant dict back to a dense float weight.

    Supports leading batch dims ([.., K, N] mantissa with [.., K//bk, N]
    scales).  4-D HWIO conv mantissas must be lowered to the GEMM view by
    the caller first (conv2d does).
    """
    m, s = w["m"], w["s"]
    bk = m.shape[-2] // s.shape[-2]
    s_full = jnp.repeat(s, bk, axis=-2)
    return (m.astype(dtype) * s_full.astype(dtype))


def prequant_act(x: jax.Array, policy: BFPPolicy) -> Any:
    """Activations [.., K] -> {"m": int8 [.., K], "s": f32 [.., K//bk]}.

    The ACTIVATION wire format: blocks run along the LAST axis, one per
    (row, K-chunk of ``policy.block_k``) — for NHWC conv activations the
    last axis is C, so blocks are per (pixel, channel-chunk), exactly the
    blocks the fused conv kernel forms inline when ``block_k | C``.

    This is the reference two-step requantizer the kernels' fused
    epilogue must match BIT-exactly (ISSUE 6 acceptance): it runs the
    same block-format math (``bfp_quantize_matrix``) the in-kernel
    quantizer is pinned against.  Quantization idempotence (PR 4
    property suite) then makes dequantize-then-requantize consumers
    (emulated/float backends) agree bit-exactly too.

    Requires ``policy.l_i <= 8`` (int8 mantissa wire) and
    ``block_k | K`` — raises ValueError otherwise, mirroring the
    emulated path's block contract.
    """
    k = x.shape[-1]
    bk = policy.block_k or k
    if k % bk:
        raise ValueError(f"activation prequant needs block_k | K, got "
                         f"block_k={bk}, K={k}")
    if policy.l_i > 8:
        raise ValueError(f"activation prequant streams int8 mantissas; "
                         f"L_I={policy.l_i} > 8")
    lead = x.shape[:-1]
    blk = bfp.bfp_quantize_matrix(x.reshape(-1, k), policy.l_i, "w",
                                  bfp.Scheme.TILED, bk, policy.rounding)
    return {"m": blk.mantissa.reshape(*lead, k),
            "s": bfp.pow2(blk.exponent - (policy.l_i - 2)).reshape(
                *lead, k // bk)}


def dequantize_act(x: Any, dtype=jnp.float32) -> jax.Array:
    """Materialize an activation-prequant dict back to dense float.

    Inverse layout of :func:`prequant_act`: blocks along the LAST axis
    ([.., K] mantissa with [.., K//bk] steps) — vs the weight format's
    [-2] axis (:func:`dequantize_prequant`).
    """
    m, s = x["m"], x["s"]
    bk = m.shape[-1] // s.shape[-1]
    return m.astype(dtype) * jnp.repeat(s, bk, axis=-1).astype(dtype)


def act_block(x: Any) -> int:
    """Block size of an activation-prequant dict (K // sidecar columns)."""
    return x["m"].shape[-1] // x["s"].shape[-1]


def _path_keys(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


#: Leaf names that hold GEMM weights in LM trees: linear_init's "w" and
#: the MoE batched expert matrices.  Everything else (norm gains, biases,
#: recurrence parameters, embeddings — the gather path) stays float.
_GEMM_LEAF_NAMES = ("w", "w1", "w2", "w3")

#: Leading stack-container keys that runtime layer paths do not carry
#: (layers run under lax.scan; linear() sees "attn/wq", not
#: "layers/attn/wq").  "enc" is NOT stripped — encoder paths keep it.
_LM_STACK_PREFIXES = ("layers", "dec", "periods", "rem")


def lm_rule_path(keys) -> str:
    """Pytree path -> the runtime layer path PolicyMap rules see.

    Strips the trailing "/w" leaf name and leading stack-container/index
    segments so "layers/attn/wq/w" resolves as "attn/wq" — the same
    string models.lm.common.linear passes to the engine.  MoE expert
    leaves keep their matrix name ("moe/w1" vs runtime "moe"), so write
    substring rules ("^moe", not "^moe$") to cover both.
    """
    ks = list(keys)
    if ks and ks[-1] == "w":
        ks = ks[:-1]
    while ks and (ks[0] in _LM_STACK_PREFIXES or ks[0].isdigit()):
        ks = ks[1:]
    return "/".join(ks)


def lm_eligible(keys) -> bool:
    if not keys or keys[-1] not in _GEMM_LEAF_NAMES:
        return False
    if len(keys) >= 2 and keys[-2] == "router":
        return False  # MoE router always runs in float (moe_apply contract)
    return "/".join(keys) != "embed/e"


def _conv_bn_nested(params, rule_keys) -> bool:
    # The trailing "conv" segment is stripped ONLY for conv+bn blocks
    # (resnet's {"conv", "bn"} dicts), where the runtime layer path
    # omits it.  A plain conv layer that happens to be KEYED "conv"
    # (googlenet's aux heads: runtime path "loss1/conv") keeps it —
    # checked structurally via the sibling "bn" entry.
    node = params
    for kk in rule_keys[:-1]:
        node = node[int(kk)] if isinstance(node, (list, tuple)) \
            else node[kk]
    return isinstance(node.get(rule_keys[-1]), dict) and "bn" in node


def cnn_rule_path(params, keys) -> Optional[str]:
    """Runtime layer path for the CNN weight leaf at tree path ``keys``.

    Returns None when the leaf is not a GEMM/conv weight (only leaves
    literally named ``w`` count).  This is the single source of truth
    shared by :func:`quantize_cnn_param_tree` and ``engine.bind``'s site
    discovery, so a PolicyMap pins — and a Plan binds — exactly the
    layers the model apply functions execute ("stem", "blocks/3/c1",
    "conv1_1", "loss1/conv", "fc").
    """
    if not keys or keys[-1] != "w":
        return None
    rule_keys = keys[:-1]
    if rule_keys and rule_keys[-1] == "conv" and \
            _conv_bn_nested(params, rule_keys):
        rule_keys = rule_keys[:-1]
    return "/".join(rule_keys)


def quantize_param_tree(params: Any, policy: Any) -> Any:
    """Walk an LM param tree; convert GEMM weights to the BFP wire format.

    ``policy`` may be None (no-op), a BFPPolicy (uniform), or a
    repro.engine.PolicyMap (per-layer; a rule resolving to None keeps
    that leaf in float).  PolicyMap rules are matched against the SAME
    layer paths the runtime GEMMs use ("attn/wq", "ffn/w1", "lm_head"),
    so a per-layer assignment quantizes exactly the weights it executes.
    Stacked-layer leaves ([L, K, N], or [L, E, K, N] MoE experts)
    quantize each trailing [K, N] matrix independently.
    """
    if policy is None:
        return params

    def one(path, leaf):
        keys = _path_keys(path)
        if not lm_eligible(keys):
            return leaf
        pol = _resolve(policy, lm_rule_path(keys))
        if pol is None:
            return leaf
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and \
                jnp.issubdtype(leaf.dtype, jnp.floating):
            return prequant_leaf(leaf, pol)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def quantize_cnn_param_tree(params: Any, policy: Any) -> Any:
    """Walk a CNN param tree (models.cnn conventions) into the wire format.

    Only leaves literally named ``w`` are touched: 4-D HWIO conv kernels
    go through :func:`prequant_conv_leaf`, 2-D dense weights through
    :func:`prequant_leaf`.  Biases / batch-norm / metadata stay as-is.
    The policy is resolved against the leaf's tree path with the
    trailing ``/w`` (and the ``/conv`` nesting of conv+bn blocks)
    stripped, which is exactly the layer path the model apply functions
    pass to the engine ("stem", "blocks/3/c1", "conv1_1", "fc") — a
    PolicyMap quantizes precisely the layers it will execute in BFP.
    """
    if policy is None:
        return params

    def one(path, leaf):
        keys = _path_keys(path)
        if not keys or keys[-1] != "w" or not hasattr(leaf, "ndim"):
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        pol = _resolve(policy, cnn_rule_path(params, keys))
        if pol is None:
            return leaf
        if leaf.ndim == 4:
            return prequant_conv_leaf(leaf, pol)
        if leaf.ndim == 2:
            return prequant_leaf(leaf, pol)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)
