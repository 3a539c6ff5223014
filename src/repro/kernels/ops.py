"""Public jit'd wrappers around the Pallas BFP kernels.

Handles shape padding to tile multiples, interpret dispatch
(:func:`default_interpret`: the kernel body runs in the Pallas
interpreter on the CPU backend and compiles everywhere else), tile
selection (autotune cache -> fallback table), and policy plumbing.
The contract is identical to the emulated path in ``repro.core.bfp_dot``
with Scheme.TILED and ``block_k == bk`` — tests assert all three
(kernel, ref oracle, core library) agree.  Model code reaches these
through ``repro.engine`` (backend "pallas"), never directly.

ISSUE 6 additions, all bit-preserving:

* Tile selection consults the ACTIVE autotune cache
  (``repro.tune.set_cache`` / a Plan's bound cache) before the fallback
  table; explicit ``tiles=`` overrides both (the autotuner's measuring
  hook).
* ``x2d``/``x`` may be an activation-prequant dict ``{"m","s"}``
  (``core.prequant.prequant_act`` wire: int8 mantissa + per-(row,
  K-chunk) steps) — produced by a previous layer's fused epilogue; the
  kernel consumes it without dequantizing.
* ``out_policy=`` requests epilogue requantization: the kernel emits
  the NEXT layer's activation-prequant input straight from the fp32
  accumulator when the blocks line up (``out_policy.block_k`` divides
  both N and the N tile); otherwise the wrapper falls back to the
  bit-identical two-step (store f32, ``prequant_act``) — callers always
  get the same dict either way.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
from typing import Any, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.conv_utils import conv_geometry, conv_weight_matrix
from repro.core.policy import BFPPolicy
from repro.core.prequant import act_block, is_prequant, prequant_act
from repro.kernels.bfp_conv import (bfp_conv2d_pallas,
                                    bfp_conv2d_prequant_pallas,
                                    bfp_conv2d_xprequant_pallas,
                                    bfp_conv2d_xwprequant_pallas)
from repro.kernels.bfp_matmul import (bfp_matmul_pallas,
                                      bfp_matmul_prequant_pallas,
                                      bfp_matmul_xprequant_pallas,
                                      bfp_matmul_xwprequant_pallas)
from repro.kernels.bfp_quantize import bfp_quantize_pallas
from repro.tune import cache as _tune
from repro.tune.tables import (MXU_DIM, aligned_tile, conv_row_tile,
                               fallback_tiles, patch_row_tile)

__all__ = ["bfp_matmul", "bfp_matmul_prequant", "bfp_conv2d",
           "bfp_conv2d_prequant", "bfp_quantize", "default_tiles",
           "aligned_tile", "count_conv_launches"]

ActOrArray = Union[jax.Array, dict]


def default_interpret() -> bool:
    """Whether kernels run in the Pallas interpreter: only on the CPU
    backend (the test suite's case).  Anywhere else they compile, and a
    backend Mosaic cannot target fails loudly instead of falling back."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, mult: Tuple[int, ...],
            values=0.0) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads, constant_values=values)
    return x


def default_tiles(b: int, k: int, n: int, block_k: Optional[int],
                  l_sum: int = 16) -> Tuple[int, int, int]:
    """MXU-aligned default tiles — delegates to THE shared fallback
    table (:func:`repro.tune.tables.fallback_tiles`), the single default
    path for fused and prequant kernels alike (ISSUE 6)."""
    return fallback_tiles(b, k, n, block_k, l_sum)


def _gemm_tiles(b: int, k: int, n: int, policy: BFPPolicy,
                interpret: bool, tiles, bk_pin: Optional[int]):
    """(bm, bn, bk) for a GEMM site: explicit ``tiles`` > active tune
    cache > fallback table.  ``bk_pin`` (a prequant sidecar's block)
    overrides whatever bk the source proposed."""
    if tiles is not None:
        bm, bn, bk = tiles
    else:
        looked = _tune.lookup_tiles("gemm", b, k, n, policy.l_i,
                                    policy.l_w, policy.block_k, interpret)
        bm, bn, bk = looked if looked is not None else fallback_tiles(
            b, k, n, policy.block_k, policy.l_w + policy.l_i)
    if bk_pin is not None:
        if tiles is not None and bk != bk_pin:
            raise ValueError(f"tiles bk={bk} != prequant block {bk_pin}")
        bk = bk_pin
    return bm, bn, bk


def _act_ops(x2d: dict, bm: int, bk: int) -> Tuple[jax.Array, jax.Array]:
    """Pad an activation-prequant dict's pieces for the kernel.  Mantissa
    rows pad with 0 (inert), step rows with 1.0 (finite, inert)."""
    xm = _pad_to(x2d["m"], (bm, bk))
    xs = _pad_to(x2d["s"].astype(jnp.float32), (bm, 1), values=1.0)
    return xm, xs


def _epilogue_cfg(out_policy: Optional[BFPPolicy], n: int, bn: int):
    """(out_bits, out_block) when the kernel can emit the consumer's
    activation blocks directly; None -> two-step fallback in the
    wrapper (bit-identical either way)."""
    if out_policy is None:
        return None
    bq = out_policy.block_k
    if bq and out_policy.l_i <= 8 and n % bq == 0 and bn % bq == 0:
        return (out_policy.l_i, bq)
    return None


def _finish_gemm(out, b: int, n: int, out_policy: Optional[BFPPolicy],
                 fused_q) -> ActOrArray:
    """Slice padding off; requantize two-step when the epilogue wasn't
    fused."""
    if fused_q is not None:
        m, s = out
        return {"m": m[:b, :n], "s": s[:b, :n // fused_q[1]]}
    out = out[:b, :n]
    if out_policy is not None:
        return prequant_act(out, out_policy)
    return out


def bfp_matmul(x2d: ActOrArray, w: jax.Array, policy: BFPPolicy,
               interpret: Optional[bool] = None, *,
               out_policy: Optional[BFPPolicy] = None,
               tiles: Optional[Tuple[int, int, int]] = None,
               dot_impl: str = "auto", pipeline: bool = True) -> ActOrArray:
    """x2d[B,K] @ w[K,N] via the fused Pallas kernel (Scheme.TILED).

    Pads every dim to tile multiples (zero K-padding is exact: zero
    mantissas contribute nothing; padded rows/cols are sliced off).
    ``x2d`` may be an activation-prequant dict (previous layer's
    epilogue output); ``out_policy`` requests requantized {"m","s"}
    output for the NEXT layer.  ``dot_impl``/``pipeline`` pass through
    to the kernel (benchmarks/tests force the legacy ``"int32"`` +
    unpipelined datapath; every combination is bit-identical).
    """
    if interpret is None:
        interpret = default_interpret()
    x_pq = is_prequant(x2d)
    if x_pq:
        b, k = x2d["m"].shape
        bk_pin = act_block(x2d)
        if policy.block_k not in (None, bk_pin):
            raise ValueError(f"policy.block_k={policy.block_k} != "
                             f"activation prequant block {bk_pin}")
    else:
        b, k = x2d.shape
        bk_pin = None
    n = w.shape[1]
    bm, bn, bk = _gemm_tiles(b, k, n, policy, interpret, tiles, bk_pin)
    fused_q = _epilogue_cfg(out_policy, n, bn)
    ob, obk = fused_q if fused_q is not None else (None, None)
    wp = _pad_to(w.astype(jnp.float32), (bk, bn))
    if x_pq:
        xm, xs = _act_ops(x2d, bm, bk)
        out = bfp_matmul_xprequant_pallas(
            xm, xs, wp, l_i=policy.l_i, l_w=policy.l_w, bm=bm, bn=bn,
            bk=bk, interpret=interpret, dot_impl=dot_impl,
            pipeline=pipeline, out_bits=ob, out_block=obk)
    else:
        xp = _pad_to(x2d.astype(jnp.float32), (bm, bk))
        out = bfp_matmul_pallas(
            xp, wp, l_i=policy.l_i, l_w=policy.l_w, bm=bm, bn=bn, bk=bk,
            interpret=interpret, dot_impl=dot_impl, pipeline=pipeline,
            out_bits=ob, out_block=obk)
    return _finish_gemm(out, b, n, out_policy, fused_q)


def bfp_matmul_prequant(x2d: ActOrArray, wm: jax.Array, ws: jax.Array,
                        policy: BFPPolicy,
                        interpret: Optional[bool] = None, *,
                        out_policy: Optional[BFPPolicy] = None,
                        tiles: Optional[Tuple[int, int, int]] = None,
                        dot_impl: str = "auto",
                        pipeline: bool = True) -> ActOrArray:
    """x2d[B,K] @ prequant weight via the sidecar-consuming kernel.

    ``wm``: int8 mantissa [K, N]; ``ws``: f32 power-of-two steps
    [K//bk, N] (core.prequant wire format).  The prequant block size IS
    the kernel K tile, so K needs no padding (it is a bk multiple by
    construction); B and N pad to tile multiples.  Scale padding uses 1.0
    — padded mantissas are zero, so the value is inert but stays finite.
    ``x2d`` may be an activation-prequant dict with the SAME block size.
    """
    if interpret is None:
        interpret = default_interpret()
    x_pq = is_prequant(x2d)
    b, k = (x2d["m"] if x_pq else x2d).shape
    n = wm.shape[1]
    t = ws.shape[0]
    if t == 0 or k % t:
        raise ValueError(f"sidecar {ws.shape} incompatible with K={k}")
    bk_pin = k // t
    if policy.block_k not in (None, bk_pin):
        # same contract as the emulated path: a sidecar blocked at bk
        # cannot honour a policy asking for different blocks
        raise ValueError(f"policy.block_k={policy.block_k} != prequant "
                         f"block {bk_pin}")
    if x_pq and act_block(x2d) != bk_pin:
        raise ValueError(f"activation prequant block {act_block(x2d)} != "
                         f"weight prequant block {bk_pin}")
    bm, bn, bk = _gemm_tiles(b, k, n, policy, interpret, tiles, bk_pin)
    fused_q = _epilogue_cfg(out_policy, n, bn)
    ob, obk = fused_q if fused_q is not None else (None, None)
    wmp = _pad_to(wm, (bk, bn))
    wsp = _pad_to(ws.astype(jnp.float32), (1, bn), values=1.0)
    if x_pq:
        xm, xs = _act_ops(x2d, bm, bk)
        out = bfp_matmul_xwprequant_pallas(
            xm, xs, wmp, wsp, l_i=policy.l_i, l_w=policy.l_w, bm=bm,
            bn=bn, bk=bk, interpret=interpret, dot_impl=dot_impl,
            pipeline=pipeline, out_bits=ob, out_block=obk)
    else:
        xp = _pad_to(x2d.astype(jnp.float32), (bm, bk))
        out = bfp_matmul_prequant_pallas(
            xp, wmp, wsp, l_i=policy.l_i, l_w=policy.l_w, bm=bm, bn=bn,
            bk=bk, interpret=interpret, dot_impl=dot_impl,
            pipeline=pipeline, out_bits=ob, out_block=obk)
    return _finish_gemm(out, b, n, out_policy, fused_q)


def _conv_plan(b: int, h: int, w_in: int, c: int, kh: int, kw: int,
               oc: int, stride: int, padding: str, bk: int,
               t_oh: Optional[int] = None, bn: Optional[int] = None):
    """Static geometry + tiling for the fused conv kernels.

    Returns (pads for x, (oh, ow, ohp, t_oh, bn, kp)).  The padded input
    covers conv padding PLUS the kernel's alignment contract
    (Hp >= s*OHp + kh - 1, Wp >= s*OW + kw - 1); extra zero rows/cols are
    only read by padded output rows, which callers slice off.  ``t_oh``
    and ``bn`` override the defaults (autotuned or explicit tiles).
    """
    oh, ow, (pt, pb), (plf, pr) = conv_geometry(h, w_in, kh, kw, stride,
                                                padding)
    if t_oh is None:
        t_oh = conv_row_tile(oh, ow)
    t_oh = min(t_oh, oh)
    ohp = -(-oh // t_oh) * t_oh
    hp = max(stride * ohp + kh - 1, pt + h + pb)
    wp = max(stride * ow + kw - 1, plf + w_in + pr)
    if bn is None:
        bn = aligned_tile(oc)
    kp = -(-(kh * kw * c) // bk) * bk
    pads = ((0, 0), (pt, hp - h - pt), (plf, wp - w_in - plf), (0, 0))
    return pads, (oh, ow, ohp, t_oh, bn, kp)


def _conv_tiles(rows: int, k: int, oc: int, policy: BFPPolicy,
                interpret: bool, tiles):
    """(t_oh, bn) overrides for a conv site: explicit ``tiles`` > active
    tune cache > None (plan defaults).  Keys on the im2col GEMM view."""
    if tiles is not None:
        return tiles
    looked = _tune.lookup_tiles("conv", rows, k, oc, policy.l_i,
                                policy.l_w, policy.block_k, interpret)
    return looked if looked is not None else (None, None)


def _conv_epilogue_cfg(out_policy: Optional[BFPPolicy], oc: int, bn: int):
    if out_policy is None:
        return None
    bq = out_policy.block_k
    if bq and out_policy.l_i <= 8 and oc % bq == 0 and bn % bq == 0:
        return (out_policy.l_i, bq)
    return None


def _finish_conv(out, oh: int, oc: int,
                 out_policy: Optional[BFPPolicy], fused_q) -> ActOrArray:
    if fused_q is not None:
        m, s = out
        return {"m": m[:, :oh, :, :oc],
                "s": s[:, :oh, :, :oc // fused_q[1]]}
    out = out[:, :oh, :, :oc]
    if out_policy is not None:
        return prequant_act(out, out_policy)
    return out


def _conv_x_prequant_check(x: dict, c: int, bk: int, policy: BFPPolicy):
    bk_act = act_block(x)
    if policy.block_k not in (None, bk_act):
        raise ValueError(f"policy.block_k={policy.block_k} != activation "
                         f"prequant block {bk_act}")
    if bk_act != bk or c % bk:
        raise ValueError(f"conv activation prequant needs block_k | C "
                         f"(block {bk_act}, C={c})")


def _pad_act_nhwc(x: dict, pads) -> Tuple[jax.Array, jax.Array]:
    """Spatial-pad an NHWC activation-prequant dict: mantissa pads 0
    (inert), steps pad 1.0 (finite, inert — padded pixels' mantissas are
    all zero)."""
    xm = jnp.pad(x["m"], pads)
    xs = jnp.pad(x["s"].astype(jnp.float32), pads, constant_values=1.0)
    return xm, xs


def _takes_patch_path(kh: int, kw: int, kp: int, bk: int) -> bool:
    """Whether a float-input conv runs as a 1x1 conv over its patch
    tensor: kh*kw > 1 and a zero-padded patch row Kp of at most two K
    tiles and two 128-lane rows.  Such a conv has so few channels that
    the implicit kernel holds one per 128-lane row of VMEM, while its
    patch tensor holds at most two lane rows per output pixel."""
    return kh * kw > 1 and kp <= 2 * min(bk, MXU_DIM)


#: the open :func:`count_conv_launches` tally, if any
_CONV_TALLY: contextvars.ContextVar = contextvars.ContextVar(
    "conv_tally", default=None)


@contextlib.contextmanager
def count_conv_launches() -> Iterator[collections.Counter]:
    """Tally the conv kernel launches made while the block is open:
    ``"patch"`` counts the :func:`bfp_conv2d` launches that take the
    patch path, ``"macs_issued"`` the MACs every launch of either conv
    wrapper issues to the MXU (:func:`_tally_launch`).  Under ``jit`` the
    tally is made while tracing: it is the tally of one call."""
    tally = collections.Counter()
    token = _CONV_TALLY.set(tally)
    try:
        yield tally
    finally:
        _CONV_TALLY.reset(token)


def _tally_launch(b: int, ohp: int, ow: int, kp: int, oc: int,
                  patch: bool = False) -> None:
    """Add one conv launch to the open tally: the MACs it issues are
    ``B * OHp * OW * Kp * ceil(OC / 128) * 128``, padded rows and K
    included, since a v5e MXU pass is 128 columns wide whatever the
    output tile ``bn`` is."""
    tally = _CONV_TALLY.get()
    if tally is not None:
        tally["macs_issued"] += b * ohp * ow * kp * (-(-oc // MXU_DIM)
                                                    * MXU_DIM)
        tally["patch"] += patch


def _conv_patches(x: jax.Array, kh: int, kw: int, stride: int,
                  padding: str, kp: int) -> jax.Array:
    """NHWC float32 -> patch tensor [B, OH, OW, Kp]: each output pixel's
    receptive field in the HWIO-major K order k = (di*kw + dj)*C + c,
    zero-padded to ``kp``.

    An XLA conv with a one-hot kernel at ``Precision.HIGHEST``: each
    output takes one product x * 1.0 and adds exact zeros, and HIGHEST
    carries float32 exactly, so a finite ``x`` is copied bit for bit.
    On a v5e it ran ~28x faster than strided slices concatenated on the
    lane axis (3.8 ms against 106 ms for the stem at batch 32, PERF.md
    §6), which XLA lays out one C = 3 value per 128-lane row."""
    c = x.shape[3]
    onehot = jnp.eye(kh * kw * c, kp, dtype=jnp.float32)
    return jax.lax.conv_general_dilated(
        x, onehot.reshape(kh, kw, c, kp), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def bfp_conv2d(x: ActOrArray, w_hwio: jax.Array, policy: BFPPolicy,
               stride: int = 1, padding: str = "SAME",
               interpret: Optional[bool] = None, *,
               out_policy: Optional[BFPPolicy] = None,
               tiles: Optional[Tuple[int, int]] = None,
               dot_impl: str = "auto", pipeline: bool = True) -> ActOrArray:
    """NHWC conv through the fused implicit-im2col kernel (Scheme.TILED).

    x: [B, H, W, C] float — or an activation-prequant dict (int8 NHWC
    mantissa + per-(pixel, C-chunk) steps, the conv epilogue wire
    format; requires ``block_k | C``); w_hwio: [kh, kw, C, OC] float.
    The K tile ``policy.block_k`` IS the BFP block (whole-K when None);
    K zero-pads to a tile multiple exactly like ops.bfp_matmul, so the
    result is bit-identical to im2col + the fused GEMM kernel.
    ``out_policy`` requests the epilogue-requantized {"m","s"} output.

    Narrow channels take the patch path (:func:`_takes_patch_path`:
    kh*kw > 1 and Kp <= 2 * min(bk, 128), as in ResNet-50's 7x7 stem
    and VGG-16's conv1_1): a float ``x`` is gathered into its patch
    tensor [B, OH, OW, Kp] in XLA (named scope ``patches``),
    which the same kernel then runs as a 1x1 stride-1 conv, with
    :func:`~repro.tune.tables.patch_row_tile` rows per program unless
    ``tiles`` or the tune cache say otherwise.  Its K tiles are the
    implicit kernel's, so the bits are the same.  The rule reads shapes
    only; :func:`count_conv_launches` counts the launches it takes.
    """
    if interpret is None:
        interpret = default_interpret()
    x_pq = is_prequant(x)
    b, h, w_in, c = (x["m"] if x_pq else x).shape
    kh, kw, c2, oc = w_hwio.shape
    if c != c2:
        raise ValueError(f"channel mismatch: x "
                         f"{(x['m'] if x_pq else x).shape} vs w "
                         f"{w_hwio.shape}")
    bk = policy.block_k or (act_block(x) if x_pq else kh * kw * c)
    if x_pq:
        _conv_x_prequant_check(x, c, bk, policy)
    t_oh, bn = _conv_tiles(b * h * w_in, kh * kw * c, oc, policy,
                           interpret, tiles)
    k = kh * kw * c
    kp = -(-k // bk) * bk
    patch = not x_pq and _takes_patch_path(kh, kw, kp, bk)
    if patch:
        with jax.named_scope("patches"):
            x = _conv_patches(x.astype(jnp.float32), kh, kw, stride,
                              padding, kp)
        w_hwio = jnp.pad(w_hwio.reshape(1, 1, k, oc),
                         ((0, 0), (0, 0), (0, kp - k), (0, 0)))
        b, h, w_in, c = x.shape
        kh = kw = stride = 1
        padding = "VALID"
        if t_oh is None:
            t_oh = patch_row_tile(h, w_in)
    pads, (oh, ow, ohp, t_oh, bn, kp) = _conv_plan(
        b, h, w_in, c, kh, kw, oc, stride, padding, bk, t_oh, bn)
    _tally_launch(b, ohp, ow, kp, oc, patch)
    fused_q = _conv_epilogue_cfg(out_policy, oc, bn)
    ob, obk = fused_q if fused_q is not None else (None, None)
    w2d = conv_weight_matrix(w_hwio.astype(jnp.float32))
    w2d = _pad_to(w2d, (kp, bn))
    kwargs = dict(kh=kh, kw=kw, stride=stride, t_oh=t_oh, ohp=ohp, ow=ow,
                  bn=bn, bk=bk, l_i=policy.l_i, l_w=policy.l_w,
                  interpret=interpret, dot_impl=dot_impl,
                  pipeline=pipeline, out_bits=ob, out_block=obk)
    if x_pq:
        xm, xs = _pad_act_nhwc(x, pads)
        out = bfp_conv2d_xprequant_pallas(xm, xs, w2d, **kwargs)
    else:
        xp = jnp.pad(x.astype(jnp.float32), pads)
        out = bfp_conv2d_pallas(xp, w2d, **kwargs)
    return _finish_conv(out, oh, oc, out_policy, fused_q)


def bfp_conv2d_prequant(x: ActOrArray, wm_hwio: jax.Array, ws: jax.Array,
                        policy: BFPPolicy, stride: int = 1,
                        padding: str = "SAME",
                        interpret: Optional[bool] = None, *,
                        out_policy: Optional[BFPPolicy] = None,
                        tiles: Optional[Tuple[int, int]] = None,
                        dot_impl: str = "auto",
                        pipeline: bool = True) -> ActOrArray:
    """NHWC conv with pre-quantized weights (int8 HWIO mantissa + GEMM-view
    step sidecar [K//bk, OC], core.prequant wire format).

    The sidecar block IS the kernel K tile (K is a ``bk`` multiple by the
    wire-format contract), so prequant execution is bit-exact vs
    :func:`bfp_conv2d` with the same policy.  ``x`` may additionally be
    an activation-prequant dict with the SAME block size (requires
    ``bk | C``) — the fully-prequantized conv->conv chain.

    There is no patch path here: a wire-format K is a ``bk`` multiple,
    and at bk = 128 a kh*kw > 1 conv with K <= 256 would need
    kh*kw*C in {128, 256}, which no 3x3, 5x5 or 7x7 conv has, so the
    narrow convs of the model zoo (C = 1 or 3) all arrive with float
    weights at :func:`bfp_conv2d`.
    """
    if interpret is None:
        interpret = default_interpret()
    x_pq = is_prequant(x)
    b, h, w_in, c = (x["m"] if x_pq else x).shape
    kh, kw, c2, oc = wm_hwio.shape
    if c != c2:
        raise ValueError(f"channel mismatch: x "
                         f"{(x['m'] if x_pq else x).shape} vs w "
                         f"{wm_hwio.shape}")
    k = kh * kw * c
    t = ws.shape[0]
    if t == 0 or k % t:
        raise ValueError(f"sidecar {ws.shape} incompatible with K={k}")
    bk = k // t
    if policy.block_k not in (None, bk):
        raise ValueError(f"policy.block_k={policy.block_k} != prequant "
                         f"block {bk}")
    if x_pq:
        _conv_x_prequant_check(x, c, bk, policy)
    t_oh, bn = _conv_tiles(b * h * w_in, k, oc, policy, interpret, tiles)
    pads, (oh, ow, ohp, t_oh, bn, kp) = _conv_plan(
        b, h, w_in, c, kh, kw, oc, stride, padding, bk, t_oh, bn)
    assert kp == k, "wire-format K is a bk multiple by construction"
    _tally_launch(b, ohp, ow, kp, oc)
    fused_q = _conv_epilogue_cfg(out_policy, oc, bn)
    ob, obk = fused_q if fused_q is not None else (None, None)
    wm2d = _pad_to(conv_weight_matrix(wm_hwio), (bk, bn))
    wsp = _pad_to(ws.astype(jnp.float32), (1, bn), values=1.0)
    kwargs = dict(kh=kh, kw=kw, stride=stride, t_oh=t_oh, ohp=ohp, ow=ow,
                  bn=bn, bk=bk, l_i=policy.l_i, l_w=policy.l_w,
                  interpret=interpret, dot_impl=dot_impl,
                  pipeline=pipeline, out_bits=ob, out_block=obk)
    if x_pq:
        xm, xs = _pad_act_nhwc(x, pads)
        out = bfp_conv2d_xwprequant_pallas(xm, xs, wm2d, wsp, **kwargs)
    else:
        xp = jnp.pad(x.astype(jnp.float32), pads)
        out = bfp_conv2d_prequant_pallas(xp, wm2d, wsp, **kwargs)
    return _finish_conv(out, oh, oc, out_policy, fused_q)


def bfp_quantize(x: jax.Array, bits: int, block_k: int,
                 interpret: Optional[bool] = None):
    """[M,K] -> (mantissa int8 [M,K], exps int32 [M,ceil(K/bk)]) padded-safe."""
    if interpret is None:
        interpret = default_interpret()
    m_rows, k = x.shape
    # same aligned floor as default_tiles (one helper, one rationale);
    # the streaming quantizer has no MXU operand so it rides a taller
    # 256-row tile for bandwidth.
    bm = aligned_tile(m_rows, 256)
    xp = _pad_to(x.astype(jnp.float32), (bm, block_k))
    m, e = bfp_quantize_pallas(xp, bits=bits, bm=bm, bk=block_k,
                               interpret=interpret)
    return m[:m_rows, :k], e[:m_rows, : -(-k // block_k)]
