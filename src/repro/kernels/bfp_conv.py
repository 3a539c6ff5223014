"""Implicit-im2col fused BFP convolution Pallas kernels.

The paper's traffic argument (§3.1, Table 1) is that BFP cuts off-chip
bytes — yet a materialized im2col inflates activation HBM traffic
kh*kw-fold (9x for 3x3) before the datapath even starts.  These kernels
read the padded NHWC input straight from HBM and form the receptive-field
rows **in VMEM**:

    HBM: x [1, Hp, Wp, C] tile, w GEMM-view [K, bn] stripe --> VMEM
      gather kh*kw strided slabs  -> patch rows [t_oh*OW, K]   (VMEM only)
      per K-tile of size bk:
        block-format patch rows  (per-row exponent over the K-tile)
        block-format w columns   (per-column exponent; or prequant sidecar)
        int8 x int8 -> int32 MXU dot, rescale 2^(e_x-(L_I-2))*2^(e_w-(L_W-2))
        fp32 accumulate (sequential over K-tiles, same order as the GEMM
        kernel -> bit-identical to im2col + bfp_matmul_pallas)
    fp32 out [1, t_oh, OW, bn] tile --> HBM   (or {"m","s"} via epilogue)

Dot modes, software pipelining, prequant activations, and the epilogue
requantizer all follow :mod:`repro.kernels.bfp_matmul` (one shared
``resolve_dot_impl`` / ``_block_format`` / ``_tile_dot``):

* ``dot_impl``: int8 (MXU-native), int32 (the datapath for L > 8), f32
  (bit-exact under the 2^24 bound, the fast interpret path) — all
  bit-identical.
* The static K loop is skewed: the quantize of tile t+1 is issued before
  the dot of tile t, so the VPU block-format and the MXU dot have no data
  dependence and Mosaic can overlap them.  Accumulation order is the
  plain 0..n_k-1, so the skew changes no bit.
* Activation-prequant input (``xm`` int8 NHWC + ``xs`` per-(pixel,
  C-chunk) steps): requires ``bk | C``, which makes every patch-row
  K-tile exactly one (input pixel, channel-chunk) block — the patch
  gather permutes whole blocks, so consuming the producer's epilogue
  output is bit-identical to quantizing f32 patches inline.
* Epilogue requantize (``out_bits``/``out_block``): emits int8 mantissas
  + steps per (output pixel, out_block-channel-chunk) — exactly the
  activation blocks the NEXT conv (with block_k = out_block) would form,
  so conv->conv chains skip the f32 HBM round-trip bit-identically.

The K-order is the repo-wide HWIO-major conv GEMM view
(core.conv_utils): k = (di*kw + dj)*C + c.  Because C is innermost and
NHWC keeps channels contiguous, every (di, dj) offset contributes one
contiguous channel slab, extractable with *static* slices — the whole
kernel body is static Python over (kh, kw) offsets; only the output-row
program id enters a dynamic slice start.

Strided columns use the reshape trick: slice [dj : dj + stride*OW] then
reshape [OW, stride, C] and keep phase 0 — exact for any static stride.

Narrow channels do not reach the kernel as kh x kw convs.  With C = 3
(ResNet-50's 7x7 stem, VGG-16's conv1_1) every channel slab is a few
lanes wide and the input plane one value per 128-lane row, so
``ops.bfp_conv2d`` gathers a float conv whose patch row spans at most
two K tiles and two 128-lane rows into its patch tensor [B, OH, OW, Kp]
in XLA, and launches this kernel on it as a 1x1 stride-1 conv: the same
K tiles in the same order, so the same bits.

Grid: (B, OHp/t_oh, OCp/bn).  The K reduction is an in-kernel static
loop (n_k tiles), so no cross-step accumulator scratch is needed.  VMEM
sizing: each program holds the full [Hp, Wp, C] input plane (C padded to
128 lanes) plus [t_oh*OW, Kp] patch rows, and the call raises the
scoped-VMEM limit to what those blocks need (:func:`_vmem_limit`,
~68 MiB at vgg16's 224² layers against a 16 MiB default).  A
row-windowed input DMA would need less; it is not built.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bfp_matmul import (_block_format, _mantissa_dtype,
                                      _tile_dot, resolve_dot_impl)


def _patch_rows(x_ref, *, kh: int, kw: int, stride: int, t_oh: int,
                ow: int, kp: int) -> jax.Array:
    """Form [t_oh*OW, Kp] receptive-field rows in VMEM for this program's
    output-row tile (program id 1), zero-padding K up to ``kp``."""
    c = x_ref.shape[3]
    oh0 = pl.program_id(1) * t_oh
    pieces = []
    for di in range(kh):
        # output rows oh0..oh0+t_oh-1 need input rows oh0*s+di + s*r:
        # one dynamic-start slice of s*t_oh rows, then keep phase 0.
        rows = x_ref[pl.ds(0, 1), pl.ds(oh0 * stride + di, stride * t_oh),
                     :, :]
        rows = rows.reshape(t_oh, stride, rows.shape[2], c)[:, 0]
        for dj in range(kw):
            # columns dj + s*i, i < OW: static slice + phase-0 reshape
            slab = rows[:, dj:dj + stride * ow, :]
            pieces.append(slab.reshape(t_oh, ow, stride, c)[:, :, 0, :])
    patches = jnp.concatenate(pieces, axis=-1)     # (di, dj, c) = HWIO-major
    patches = patches.reshape(t_oh * ow, kh * kw * c)
    if kp > kh * kw * c:
        patches = jnp.pad(patches, ((0, 0), (0, kp - kh * kw * c)))
    return patches


def _make_conv_kernel(*, kh, kw, stride, t_oh, ow, bk, n_k, l_i, l_w,
                      x_pq: bool, w_pq: bool, mode: str, out_q):
    """Build the conv kernel body for one static configuration.

    Ref order: x side (1 or 2 refs), w side (1 or 2), out (1 or 2).
    """
    x_dt = _mantissa_dtype(mode, l_i, x_pq)
    w_dt = _mantissa_dtype(mode, l_w, w_pq)

    def kernel(*refs):
        it = iter(refs)
        if x_pq:
            xm_ref, xs_ref = next(it), next(it)
        else:
            x_ref = next(it)
        if w_pq:
            wm_ref, ws_ref = next(it), next(it)
        else:
            w_ref = next(it)
        if out_q is not None:
            om_ref, os_ref = next(it), next(it)
        else:
            o_ref = next(it)

        if x_pq:
            # bk | C (checked): each patch K-tile is exactly one (input
            # pixel, channel-chunk) block, so the mantissa/step patches
            # line up tile-for-tile with inline quantization.
            pm = _patch_rows(xm_ref, kh=kh, kw=kw, stride=stride,
                             t_oh=t_oh, ow=ow, kp=n_k * bk).astype(x_dt)
            ps = _patch_rows(xs_ref, kh=kh, kw=kw, stride=stride,
                             t_oh=t_oh, ow=ow, kp=n_k)
        else:
            patches = _patch_rows(x_ref, kh=kh, kw=kw, stride=stride,
                                  t_oh=t_oh, ow=ow, kp=n_k * bk)

        def x_tile(t):
            if x_pq:
                return pm[:, t * bk:(t + 1) * bk], ps[:, t:t + 1]
            return _block_format(patches[:, t * bk:(t + 1) * bk], l_i,
                                 axis=1, mdtype=x_dt)

        def w_tile(t):
            if w_pq:
                # ws IS the step the inline quantizer would compute, so
                # the prequant path is bit-exact vs the inline kernel.
                return (wm_ref[t * bk:(t + 1) * bk, :].astype(w_dt),
                        ws_ref[t:t + 1, :])
            return _block_format(w_ref[t * bk:(t + 1) * bk, :], l_w,
                                 axis=0, mdtype=w_dt)

        bn = (wm_ref if w_pq else w_ref).shape[1]
        acc = jnp.zeros((t_oh * ow, bn), jnp.float32)
        # Skewed issue order: quantize tile t+1 BEFORE the dot of tile t
        # — the block-format (VPU) and the dot (MXU) have no data
        # dependence, so Mosaic overlaps them.  Accumulation order is
        # unchanged (0..n_k-1).
        cur = (x_tile(0), w_tile(0))
        for t in range(n_k):
            nxt = (x_tile(t + 1), w_tile(t + 1)) if t + 1 < n_k else None
            (mx, sx), (mw, sw) = cur
            acc = acc + _tile_dot(mx, mw, mode) * (sx * sw)
            cur = nxt

        if out_q is None:
            o_ref[...] = acc.reshape(1, t_oh, ow, -1)
        else:
            # Epilogue: block-format per (output pixel, out_block
            # channel chunk) — identical math, identical accumulator
            # values as the two-step store-f32-then-prequant_act path.
            ob, bq = out_q
            ms = []
            for t in range(bn // bq):
                m, step = _block_format(acc[:, t * bq:(t + 1) * bq], ob,
                                        axis=1, mdtype=jnp.int8)
                ms.append(m)
                os_ref[t] = step.reshape(t_oh, ow, 1)
            om_ref[...] = jnp.concatenate(ms, axis=1).reshape(
                1, t_oh, ow, -1)

    return kernel


def _check_conv(x_shape, kp, ocp, *, kh, kw, stride, t_oh, ohp, ow, bk,
                bn, l_sum, out_q=None):
    b, hp, wp, c = x_shape
    if ohp % t_oh or ocp % bn or kp % bk:
        raise ValueError(f"tiles (t_oh={t_oh}, bn={bn}, bk={bk}) must "
                         f"divide (OHp={ohp}, OCp={ocp}, Kp={kp})")
    if kp < kh * kw * c:
        raise ValueError(f"Kp={kp} smaller than kh*kw*C={kh * kw * c}")
    if hp < stride * ohp + kh - 1 or wp < stride * ow + kw - 1:
        raise ValueError(
            f"padded input {hp}x{wp} too small for OHp={ohp}, OW={ow}, "
            f"k={kh}x{kw}, stride={stride} (need "
            f">= {stride * ohp + kh - 1}x{stride * ow + kw - 1})")
    # Paper Fig. 2 accumulator sizing: int32 must hold bk products.
    if l_sum + math.ceil(math.log2(bk)) > 32:
        raise ValueError(f"bk={bk} overflows int32 for L_I+L_W={l_sum}")
    if out_q is not None:
        out_bits, out_block = out_q
        if not 2 <= out_bits <= 8:
            raise ValueError(f"epilogue out_bits={out_bits} must be 2..8 "
                             f"(int8 mantissa wire format)")
        if bn % out_block:
            raise ValueError(f"epilogue out_block={out_block} must divide "
                             f"bn={bn}")


#: Ceiling on the conv kernels' scoped VMEM: v5e holds 128 MiB of VMEM,
#: and the rest is left to Mosaic's internal scratch.
_VMEM_CAP = 100 * 2 ** 20
#: Room for the kernel body's values (quantized tiles, slabs, partial
#: products) beyond the blocks and patch rows: the default scoped budget.
_VMEM_BODY = 16 * 2 ** 20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one block: the minor dim pads to 128 lanes and
    the second-minor to the dtype's sublane tile (8 rows of 32 bits)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    *lead, r, c = shape
    return (math.prod(lead) * (-(-r // sub) * sub) * (-(-c // 128) * 128)
            * item)


def _vmem_limit(blocks, rows: int, kp: int) -> int:
    """Scoped-VMEM limit for one conv call: every block double-buffered,
    the f32 patch rows [rows, Kp], and the body's default budget.  The
    whole padded input plane is one block, so early layers (224² with C
    padded to 128 lanes) need ~3x the default 16 MiB limit."""
    need = (2 * sum(_vmem_bytes(s, d) for s, d in blocks)
            + _vmem_bytes((rows, kp), jnp.float32) + _VMEM_BODY)
    return min(_VMEM_CAP, need)


def _out_q(out_bits, out_block, bn):
    if out_bits is None:
        return None
    return (out_bits, out_block if out_block is not None else bn)


def _conv_call(x_ops, w_ops, *, kh, kw, stride, t_oh, ohp, ow, bn, bk,
               l_i, l_w, interpret, dot_impl, out_q):
    """Assemble specs and launch; ``x_ops`` is (x,) or (xm, xs) NHWC,
    ``w_ops`` is (w2d,) or (wm2d, ws) GEMM view."""
    x_pq, w_pq = len(x_ops) == 2, len(w_ops) == 2
    b, hp, wp, c = x_ops[0].shape
    kp, ocp = w_ops[0].shape
    n_k = kp // bk
    _check_conv(x_ops[0].shape, kp, ocp, kh=kh, kw=kw, stride=stride,
                t_oh=t_oh, ohp=ohp, ow=ow, bk=bk, bn=bn, l_sum=l_i + l_w,
                out_q=out_q)
    mode = resolve_dot_impl(dot_impl, l_i=l_i, l_w=l_w, bk=bk,
                            interpret=interpret, x_pq=x_pq, w_pq=w_pq)

    in_specs = [pl.BlockSpec((1, hp, wp, c),
                             lambda bb, i, j: (bb, 0, 0, 0))]
    if x_pq:
        in_specs.append(pl.BlockSpec((1, hp, wp, c // bk),
                                     lambda bb, i, j: (bb, 0, 0, 0)))
    in_specs.append(pl.BlockSpec((kp, bn), lambda bb, i, j: (0, j)))
    if w_pq:
        in_specs.append(pl.BlockSpec((n_k, bn), lambda bb, i, j: (0, j)))

    if out_q is None:
        out_specs = pl.BlockSpec((1, t_oh, ow, bn),
                                 lambda bb, i, j: (bb, i, 0, j))
        out_shape = jax.ShapeDtypeStruct((b, ohp, ow, ocp), jnp.float32)
    else:
        # epilogue steps leave chunk-major [B, OCp/bq, OHp, OW, 1]: a
        # (.., OW, bn/bq) block of the NHWC step layout breaks Mosaic's
        # rule that a block's last two dims divide by (8, 128) or equal
        # the array's whenever bn < OCp
        bq = out_q[1]
        out_specs = [
            pl.BlockSpec((1, t_oh, ow, bn), lambda bb, i, j: (bb, i, 0, j)),
            pl.BlockSpec((pl.Squeezed(), bn // bq, t_oh, ow, 1),
                         lambda bb, i, j: (bb, j, i, 0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, ohp, ow, ocp), jnp.int8),
            jax.ShapeDtypeStruct((b, ocp // bq, ohp, ow, 1), jnp.float32),
        ]

    kernel = _make_conv_kernel(kh=kh, kw=kw, stride=stride, t_oh=t_oh,
                               ow=ow, bk=bk, n_k=n_k, l_i=l_i, l_w=l_w,
                               x_pq=x_pq, w_pq=w_pq, mode=mode, out_q=out_q)
    blocks = [((hp, wp, c), x_ops[0].dtype), ((kp, bn), w_ops[0].dtype),
              ((t_oh * ow, bn), jnp.float32)]
    if x_pq:
        blocks.append(((hp, wp, c // bk), jnp.float32))
    if w_pq:
        blocks.append(((n_k, bn), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(b, ohp // t_oh, ocp // bn),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(blocks, t_oh * ow, kp)),
        interpret=interpret,
    )(*x_ops, *w_ops)
    if out_q is None:
        return out
    m, s = out
    return m, s[..., 0].transpose(0, 2, 3, 1)


_STATIC = ("kh", "kw", "stride", "t_oh", "ohp", "ow", "bn", "bk", "l_i",
           "l_w", "interpret", "dot_impl", "out_bits", "out_block")


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_conv2d_pallas(x: jax.Array, w2d: jax.Array, *, kh: int, kw: int,
                      stride: int, t_oh: int, ohp: int, ow: int, bn: int,
                      bk: int, l_i: int = 8, l_w: int = 8,
                      interpret: bool = False, dot_impl: str = "auto",
                      out_bits: int | None = None,
                      out_block: int | None = None):
    """Fused implicit-im2col BFP conv.

    x: pre-padded NHWC [B, Hp, Wp, C] (conv padding + alignment, ops.py
    does this); w2d: conv GEMM view [Kp, OCp], K zero-padded to a ``bk``
    multiple and OC to a ``bn`` multiple.  Returns [B, OHp, OW, OCp]
    fp32 (callers slice OH/OC) — or, with ``out_bits`` set, the epilogue
    pair (int8 mantissa NHWC, f32 steps [..., OCp/out_block]).  ``bk`` IS
    the BFP block — Scheme.TILED with block_k = bk, bit-identical to
    im2col + bfp_matmul_pallas (zero K-padding is inert: it changes no
    block amax and adds zero products, exactly as in ops.bfp_matmul's
    padding).
    """
    return _conv_call((x,), (w2d,), kh=kh, kw=kw, stride=stride,
                      t_oh=t_oh, ohp=ohp, ow=ow, bn=bn, bk=bk, l_i=l_i,
                      l_w=l_w, interpret=interpret, dot_impl=dot_impl,
                      out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_conv2d_prequant_pallas(x: jax.Array, wm2d: jax.Array,
                               ws: jax.Array, *, kh: int, kw: int,
                               stride: int, t_oh: int, ohp: int, ow: int,
                               bn: int, bk: int, l_i: int = 8,
                               l_w: int = 8, interpret: bool = False,
                               dot_impl: str = "auto",
                               out_bits: int | None = None,
                               out_block: int | None = None):
    """Prequant fused conv: weights arrive as int8 GEMM-view mantissas
    [K, OCp] + power-of-two step sidecar [K//bk, OCp] (K a ``bk``
    multiple by the wire-format contract).  ``l_w`` only sizes the
    overflow check — weight quantization already happened offline."""
    kp, ocp = wm2d.shape
    if wm2d.dtype != jnp.int8:
        raise ValueError(f"prequant conv kernel streams int8 mantissas, "
                         f"got {wm2d.dtype}")
    if ws.shape != (kp // bk, ocp):
        raise ValueError(f"scale sidecar {ws.shape} != {(kp // bk, ocp)} "
                         f"for bk={bk}")
    return _conv_call((x,), (wm2d, ws), kh=kh, kw=kw, stride=stride,
                      t_oh=t_oh, ohp=ohp, ow=ow, bn=bn, bk=bk, l_i=l_i,
                      l_w=l_w, interpret=interpret, dot_impl=dot_impl,
                      out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_conv2d_xprequant_pallas(xm: jax.Array, xs: jax.Array,
                                w2d: jax.Array, *, kh: int, kw: int,
                                stride: int, t_oh: int, ohp: int, ow: int,
                                bn: int, bk: int, l_i: int = 8,
                                l_w: int = 8, interpret: bool = False,
                                dot_impl: str = "auto",
                                out_bits: int | None = None,
                                out_block: int | None = None):
    """Prequant ACTIVATIONS: xm int8 NHWC [B,Hp,Wp,C] + xs f32 steps
    [B,Hp,Wp,C/bk] (per input pixel and channel chunk — the conv
    epilogue wire format).  Requires ``bk | C`` so patch K-tiles ==
    activation blocks; ``l_i`` only sizes the overflow check."""
    c = xm.shape[3]
    if xm.dtype != jnp.int8:
        raise ValueError(f"activation-prequant conv kernel streams int8 "
                         f"mantissas, got {xm.dtype}")
    if c % bk:
        raise ValueError(f"activation prequant requires bk | C, got "
                         f"bk={bk}, C={c}")
    if xs.shape != (*xm.shape[:3], c // bk):
        raise ValueError(f"activation sidecar {xs.shape} != "
                         f"{(*xm.shape[:3], c // bk)} for bk={bk}")
    return _conv_call((xm, xs), (w2d,), kh=kh, kw=kw, stride=stride,
                      t_oh=t_oh, ohp=ohp, ow=ow, bn=bn, bk=bk, l_i=l_i,
                      l_w=l_w, interpret=interpret, dot_impl=dot_impl,
                      out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_conv2d_xwprequant_pallas(xm: jax.Array, xs: jax.Array,
                                 wm2d: jax.Array, ws: jax.Array, *,
                                 kh: int, kw: int, stride: int, t_oh: int,
                                 ohp: int, ow: int, bn: int, bk: int,
                                 l_i: int = 8, l_w: int = 8,
                                 interpret: bool = False,
                                 dot_impl: str = "auto",
                                 out_bits: int | None = None,
                                 out_block: int | None = None):
    """Both sides prequantized — the steady state of a conv->conv chain
    on a bound plan: no in-kernel quantization at all."""
    c = xm.shape[3]
    kp, ocp = wm2d.shape
    if xm.dtype != jnp.int8 or wm2d.dtype != jnp.int8:
        raise ValueError(f"prequant kernels stream int8 mantissas, got "
                         f"{xm.dtype} / {wm2d.dtype}")
    if c % bk:
        raise ValueError(f"activation prequant requires bk | C, got "
                         f"bk={bk}, C={c}")
    if xs.shape != (*xm.shape[:3], c // bk):
        raise ValueError(f"activation sidecar {xs.shape} != "
                         f"{(*xm.shape[:3], c // bk)} for bk={bk}")
    if ws.shape != (kp // bk, ocp):
        raise ValueError(f"scale sidecar {ws.shape} != {(kp // bk, ocp)} "
                         f"for bk={bk}")
    return _conv_call((xm, xs), (wm2d, ws), kh=kh, kw=kw, stride=stride,
                      t_oh=t_oh, ohp=ohp, ow=ow, bn=bn, bk=bk, l_i=l_i,
                      l_w=l_w, interpret=interpret, dot_impl=dot_impl,
                      out_q=_out_q(out_bits, out_block, bn))
