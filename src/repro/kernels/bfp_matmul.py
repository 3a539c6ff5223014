"""Fused BFP matmul Pallas kernels — the paper's accelerator datapath on TPU.

One kernel family fuses the paper's whole pipeline (Fig. 2):

    HBM tiles --> VMEM (float tiles, or int8 mantissa + step sidecars)
      block-format x-tile  (per-row exponent over the K-tile)     \
      block-format w-tile  (per-column exponent over the K-tile)   } in VMEM
      int8 x int8 -> int32 systolic matmul on the MXU             /
      power-of-two rescale + fp32 accumulate in VMEM scratch
    fp32 out tile --> HBM   (or requantized {"m","s"} via the epilogue)

This is the TPU adaptation of the paper's FPGA design (DESIGN.md §2): the
block is the K-tile the matmul pipeline stages through VMEM anyway, so
block formatting costs no extra HBM traffic; the fixed-point MAC array is
the MXU's native int8 path.  Accumulation is int32-exact within a tile
(paper's accumulator-width rule: L_W + L_I + log2(block_k) <= 32 is
asserted) and fp32 across tiles.

Dot implementations (``dot_impl``, static):

* ``"int8"`` — mantissas stay int8 and the dot asks for an int32 result
  (``preferred_element_type``): the MXU's native 8-bit systolic path.
  Requires every inline-quantized operand to have L <= 8 (prequant
  mantissas are int8 by wire contract regardless of the stated L).
* ``"int32"`` — operands widened to int32 before the dot: the datapath
  for L > 8, the only mode legal there.  Mosaic refuses an int32 dot
  when compiling for the v5e, so it runs in the interpreter only.
* ``"f32"`` — mantissas kept/cast to f32 and dotted in f32.  BIT-exact
  whenever ``bk * (2^(L_I-1)-1) * (2^(L_W-1)-1) <= 2^24``: every product
  and partial sum is an integer of magnitude <= 2^24, all exactly
  representable in f32 (e.g. L=8, bk=512 -> max 8.26e6 < 2^24).  On
  CPU/interpret this routes through BLAS and is ~8x faster than XLA's
  scalar integer dots, so it is the auto choice off-TPU.
* ``"auto"`` — int32 when an inline operand has L > 8; on TPU, int8;
  in interpret mode, f32 when the exactness bound holds, else int32.

All modes produce bit-identical outputs (tests force each mode and
assert equality), so mode choice is purely a speed decision.

Pipelining: tiles are staged through a 2-slot VMEM scratch with a
one-step skew — grid step k quantizes tile k into slot k%2 and dots tile
k-1 from slot (k-1)%2 (the last step dots both).  Quantization (VPU) of
tile k then has no data dependence on the dot (MXU) of tile k-1, so
Mosaic can overlap them; accumulation order is unchanged (tile 0, 1,
..., n_k-1), so the result is that of the plain tile-by-tile sum.

Epilogue requantization (``out_bits``/``out_block``, static): instead of
storing the fp32 accumulator, the kernel block-formats it per
(row, out_block-column-chunk) and emits int8 mantissas + power-of-two
steps — the activation-prequant wire format the NEXT layer's kernel
consumes directly.  Bit-identical to storing f32 and requantizing
(``core.prequant.prequant_act``) because it runs the same block-format
math on the same accumulator values; saves one f32 HBM round-trip per
layer.

Grid: (B/bm, N/bn, K/bk) with K innermost so each (i, j) output tile is
accumulated across sequential k steps in a VMEM scratch accumulator.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bfp import pow2
from repro.tune.tables import fallback_tiles

_ZERO_BLOCK_EXP = -126

#: f32 holds every integer of magnitude <= 2^24 exactly — the bound for
#: the "f32" dot mode to be bit-identical to integer accumulation.
_F32_EXACT_BOUND = 1 << 24


def _floor_log2(amax: jax.Array) -> jax.Array:
    """floor(log2 x), x >= 0, via float32 exponent-field extraction."""
    bits = jax.lax.bitcast_convert_type(amax.astype(jnp.float32), jnp.uint32)
    e = (jnp.right_shift(bits, jnp.uint32(23)) & jnp.uint32(0xFF)).astype(
        jnp.int32) - 127
    return jnp.where(amax > 0, e, _ZERO_BLOCK_EXP)


def _block_format(tile: jax.Array, bits: int, axis: int, mdtype=None):
    """Block-format ``tile`` along ``axis``; returns (mantissa, scale).

    scale is the dequantization step 2^(e - (bits-2)) as fp32, shaped with
    a keepdims-1 on ``axis``.  ``mdtype`` picks the mantissa storage type;
    by default int8 feeds the MXU's native 8-bit path (L <= 8, the paper's
    headline config) and wider mantissas take int32 (still integer-exact).
    The "f32" dot mode passes float32 — the rounded mantissa is already a
    small exact integer in f32, so the cast is free and exact.
    """
    amax = jnp.max(jnp.abs(tile), axis=axis, keepdims=True)
    e = _floor_log2(amax)
    step = pow2(e - (bits - 2))
    lim = float(2 ** (bits - 1) - 1)
    m = jnp.clip(jnp.round(tile.astype(jnp.float32) / step), -lim, lim)
    # All-zero blocks take the sentinel exponent, whose step can flush
    # to zero (subnormal) under XLA: force the 0/0 -> NaN mantissa to 0
    # explicitly — the int cast used to hide this; f32 mantissas don't.
    m = jnp.where(amax > 0, m, 0.0)
    if mdtype is None:
        mdtype = jnp.int8 if bits <= 8 else jnp.int32
    return m.astype(mdtype), step


def f32_dot_exact(l_i: int, l_w: int, bk: int) -> bool:
    """True when an f32 dot over ``bk``-long int-mantissa products is
    bit-identical to int32 accumulation: every product and partial sum
    is an integer of magnitude <= 2^24."""
    return bk * (2 ** (l_i - 1) - 1) * (2 ** (l_w - 1) - 1) \
        <= _F32_EXACT_BOUND


def resolve_dot_impl(dot_impl: str, *, l_i: int, l_w: int, bk: int,
                     interpret: bool, x_pq: bool = False,
                     w_pq: bool = False) -> str:
    """Resolve ``"auto"`` to a concrete dot mode and validate the choice.

    Prequant operands arrive as int8 mantissas by wire contract, so their
    stated L never forces the int32 path — only inline-quantized sides do.
    """
    li_eff = min(l_i, 8) if x_pq else l_i
    lw_eff = min(l_w, 8) if w_pq else l_w
    if dot_impl == "auto":
        if max(li_eff, lw_eff) > 8:
            return "int32"
        if interpret:
            # XLA:CPU integer dots are scalar loops (no BLAS); use the
            # bit-exact f32 path when the bound holds, else stay exact
            # on int32.
            return "f32" if f32_dot_exact(li_eff, lw_eff, bk) else "int32"
        return "int8"
    if dot_impl == "int8" and max(li_eff, lw_eff) > 8:
        raise ValueError(f"dot_impl='int8' needs inline L <= 8, got "
                         f"L_I={l_i}, L_W={l_w}")
    if dot_impl == "f32" and not f32_dot_exact(li_eff, lw_eff, bk):
        raise ValueError(f"dot_impl='f32' not exact for L_I={l_i}, "
                         f"L_W={l_w}, bk={bk} (bound 2^24)")
    if dot_impl not in ("int8", "int32", "f32"):
        raise ValueError(f"unknown dot_impl {dot_impl!r}")
    return dot_impl


def _mantissa_dtype(mode: str, bits: int, pq: bool):
    """Storage dtype of one operand's mantissa tile under a dot mode."""
    if mode == "f32":
        return jnp.float32
    if pq:
        return jnp.int8           # wire contract
    return jnp.int8 if bits <= 8 else jnp.int32


def _tile_dot(mx: jax.Array, mw: jax.Array, mode: str) -> jax.Array:
    """One K-tile mantissa dot under ``mode``; always returns f32."""
    if mode == "f32":
        return jax.lax.dot(mx, mw, preferred_element_type=jnp.float32)
    if mode == "int32":
        mx, mw = mx.astype(jnp.int32), mw.astype(jnp.int32)
    part = jax.lax.dot(mx, mw, preferred_element_type=jnp.int32)
    return part.astype(jnp.float32)


def _requant_store(acc: jax.Array, om_ref, os_ref, *, out_bits: int,
                   out_block: int) -> None:
    """Epilogue: block-format the fp32 accumulator per (row, out_block
    column chunk) and store int8 mantissas + power-of-two steps — the
    activation-prequant wire format, bit-identical to storing f32 and
    running core.prequant.prequant_act on it.  ``os_ref`` is the
    chunk-major step block [bn/out_block, bm, 1] (see :func:`_matmul_call`)."""
    for t in range(acc.shape[1] // out_block):
        chunk = acc[:, t * out_block:(t + 1) * out_block]
        m, step = _block_format(chunk, out_bits, axis=1, mdtype=jnp.int8)
        om_ref[:, t * out_block:(t + 1) * out_block] = m
        os_ref[t] = step


def _make_matmul_kernel(*, l_i: int, l_w: int, n_k: int, x_pq: bool,
                        w_pq: bool, mode: str, out_q):
    """Build the kernel body for one static configuration.

    Ref order: x side (1 or 2 refs), w side (1 or 2), out (1 or 2),
    accumulator scratch, then the four staging buffers.
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
        acc_ref = next(it)
        mxb, sxb, mwb, swb = next(it), next(it), next(it), next(it)

        k_step = pl.program_id(2)

        @pl.when(k_step == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def load_x():
            if x_pq:
                # already block-formatted: int8 mantissas + step sidecar
                return xm_ref[...].astype(x_dt), xs_ref[...]   # [bm,bk],[bm,1]
            return _block_format(x_ref[...], l_i, axis=1, mdtype=x_dt)

        def load_w():
            if w_pq:
                # ws IS the step the in-kernel quantizer would compute,
                # so prequant and inline paths agree bit-exactly.
                return wm_ref[...].astype(w_dt), ws_ref[...]   # [bk,bn],[1,bn]
            return _block_format(w_ref[...], l_w, axis=0, mdtype=w_dt)

        def accum(mx, sx, mw, sw):
            acc_ref[...] += _tile_dot(mx, mw, mode) * (sx * sw)

        def store():
            if out_q is None:
                o_ref[...] = acc_ref[...]
            else:
                _requant_store(acc_ref[...], om_ref, os_ref,
                               out_bits=out_q[0], out_block=out_q[1])

        # Skewed double buffer: stage tile k into slot k%2, dot tile k-1
        # from the other slot.  Quantize(k) has no dependence on
        # dot(k-1), so the VPU and MXU overlap; the accumulation order
        # (0, 1, ..., n_k-1) — and hence the result — is unchanged.
        slot = jax.lax.rem(k_step, 2)
        mx, sx = load_x()
        mw, sw = load_w()
        mxb[slot], sxb[slot] = mx, sx
        mwb[slot], swb[slot] = mw, sw

        @pl.when(k_step > 0)
        def _dot_prev():
            prev = 1 - slot
            accum(mxb[prev], sxb[prev], mwb[prev], swb[prev])

        @pl.when(k_step == n_k - 1)
        def _drain():
            accum(mxb[slot], sxb[slot], mwb[slot], swb[slot])
            store()

    return kernel


def _check_tiles(b, k, n, bm, bn, bk, l_sum, out_q=None):
    if b % bm or n % bn or k % bk:
        raise ValueError(f"shapes ({b},{k})x({k},{n}) not multiples of "
                         f"tiles ({bm},{bn},{bk})")
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


def _resolve_bk(bk, b, k, n, l_sum):
    """Shared default: the autotuner's fallback table (ISSUE 6 — fused
    and prequant kernels used to disagree, bk=512 vs bk=128)."""
    return bk if bk is not None else fallback_tiles(b, k, n, None, l_sum)[2]


def _matmul_call(x_ops, w_ops, *, b, k, n, l_i, l_w, bm, bn, bk, interpret,
                 dot_impl, out_q):
    """Assemble specs and launch; ``x_ops``/``w_ops`` are (float,) or
    (mantissa, steps) operand tuples; ``out_q`` is None or
    (out_bits, out_block)."""
    x_pq, w_pq = len(x_ops) == 2, len(w_ops) == 2
    _check_tiles(b, k, n, bm, bn, bk, l_i + l_w, out_q)
    mode = resolve_dot_impl(dot_impl, l_i=l_i, l_w=l_w, bk=bk,
                            interpret=interpret, x_pq=x_pq, w_pq=w_pq)
    n_k = k // bk
    grid = (b // bm, n // bn, n_k)

    # Step sidecars travel chunk-major with a unit dim (xs [B, K/bk] as
    # [K/bk, B, 1], ws [K/bk, N] as [K/bk, 1, N], epilogue steps as
    # [N/bq, B, 1]): Mosaic needs a block's last two dims divisible by
    # (8, 128) or equal to the array's, which a (bm, 1) or (1, bn) block
    # of the [B, K/bk] / [K/bk, N] layouts is not.
    if x_pq:
        x_ops = (x_ops[0], x_ops[1].T[:, :, None])
    if w_pq:
        w_ops = (w_ops[0], w_ops[1][:, None, :])
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))]
    if x_pq:
        in_specs.append(pl.BlockSpec((pl.Squeezed(), bm, 1),
                                     lambda i, j, kk: (kk, i, 0)))
    in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    if w_pq:
        in_specs.append(pl.BlockSpec((pl.Squeezed(), 1, bn),
                                     lambda i, j, kk: (kk, 0, j)))

    if out_q is None:
        out_specs = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        out_shape = jax.ShapeDtypeStruct((b, n), jnp.float32)
    else:
        bq = out_q[1]
        out_specs = [
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bn // bq, bm, 1), lambda i, j, kk: (j, i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, n), jnp.int8),
            jax.ShapeDtypeStruct((n // bq, b, 1), jnp.float32),
        ]

    scratch = [
        pltpu.VMEM((bm, bn), jnp.float32),
        pltpu.VMEM((2, bm, bk), _mantissa_dtype(mode, l_i, x_pq)),
        pltpu.VMEM((2, bm, 1), jnp.float32),
        pltpu.VMEM((2, bk, bn), _mantissa_dtype(mode, l_w, w_pq)),
        pltpu.VMEM((2, 1, bn), jnp.float32),
    ]

    kernel = _make_matmul_kernel(l_i=l_i, l_w=l_w, n_k=n_k, x_pq=x_pq,
                                 w_pq=w_pq, mode=mode, out_q=out_q)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*x_ops, *w_ops)
    if out_q is None:
        return out
    m, s = out
    return m, s[:, :, 0].T


def _out_q(out_bits, out_block, bn):
    if out_bits is None:
        return None
    return (out_bits, out_block if out_block is not None else bn)


_STATIC = ("l_i", "l_w", "bm", "bn", "bk", "interpret", "dot_impl",
           "out_bits", "out_block")


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_matmul_pallas(x: jax.Array, w: jax.Array, *, l_i: int = 8,
                      l_w: int = 8, bm: int = 128, bn: int = 128,
                      bk: int | None = None, interpret: bool = False,
                      dot_impl: str = "auto", out_bits: int | None = None,
                      out_block: int | None = None):
    """x[B,K] @ w[K,N] through the fused BFP datapath.

    Shapes must be multiples of the block sizes (ops.py pads).  The K tile
    ``bk`` IS the BFP block size (Scheme.TILED with block_k = bk);
    ``bk=None`` takes the autotuner's fallback table.  With ``out_bits``
    set, returns (int8 mantissa [B,N], f32 steps [B, N/out_block]) — the
    epilogue-requantized activation wire format — instead of f32.
    """
    b, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    bk = _resolve_bk(bk, b, k, n, l_i + l_w)
    return _matmul_call((x,), (w,), b=b, k=k, n=n, l_i=l_i, l_w=l_w,
                        bm=bm, bn=bn, bk=bk, interpret=interpret,
                        dot_impl=dot_impl,
                        out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_matmul_prequant_pallas(x: jax.Array, wm: jax.Array, ws: jax.Array,
                               *, l_i: int = 8, l_w: int = 8, bm: int = 128,
                               bn: int = 128, bk: int | None = None,
                               interpret: bool = False,
                               dot_impl: str = "auto",
                               out_bits: int | None = None,
                               out_block: int | None = None):
    """x[B,K] @ prequant weight (int8 mantissa [K,N] + steps [K//bk,N]).

    ``bk`` must equal the prequant block size (K // ws.shape[0]); the BFP
    block IS the K tile, as in the fused kernel.  ``l_w`` only sizes the
    overflow check — weight quantization already happened offline.
    """
    b, k = x.shape
    k2, n = wm.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {x.shape} @ {wm.shape}")
    bk = _resolve_bk(bk, b, k, n, l_i + l_w)
    if ws.shape != (k // bk, n):
        raise ValueError(f"scale sidecar {ws.shape} != {(k // bk, n)} "
                         f"for bk={bk}")
    if wm.dtype != jnp.int8:
        raise ValueError(f"prequant kernel streams int8 mantissas, got "
                         f"{wm.dtype}")
    return _matmul_call((x,), (wm, ws), b=b, k=k, n=n, l_i=l_i, l_w=l_w,
                        bm=bm, bn=bn, bk=bk, interpret=interpret,
                        dot_impl=dot_impl,
                        out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_matmul_xprequant_pallas(xm: jax.Array, xs: jax.Array, w: jax.Array,
                                *, l_i: int = 8, l_w: int = 8, bm: int = 128,
                                bn: int = 128, bk: int | None = None,
                                interpret: bool = False,
                                dot_impl: str = "auto",
                                out_bits: int | None = None,
                                out_block: int | None = None):
    """Prequant ACTIVATIONS (int8 mantissa [B,K] + steps [B,K//bk]) @
    float w[K,N] — the consumer half of epilogue-requantize chaining.
    ``l_i`` only sizes the overflow check; activation quantization
    already happened in the producing layer's epilogue."""
    b, k = xm.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {xm.shape} @ {w.shape}")
    bk = _resolve_bk(bk, b, k, n, l_i + l_w)
    if xs.shape != (b, k // bk):
        raise ValueError(f"activation sidecar {xs.shape} != "
                         f"{(b, k // bk)} for bk={bk}")
    if xm.dtype != jnp.int8:
        raise ValueError(f"activation-prequant kernel streams int8 "
                         f"mantissas, got {xm.dtype}")
    return _matmul_call((xm, xs), (w,), b=b, k=k, n=n, l_i=l_i, l_w=l_w,
                        bm=bm, bn=bn, bk=bk, interpret=interpret,
                        dot_impl=dot_impl,
                        out_q=_out_q(out_bits, out_block, bn))


@functools.partial(jax.jit, static_argnames=_STATIC)
def bfp_matmul_xwprequant_pallas(xm: jax.Array, xs: jax.Array,
                                 wm: jax.Array, ws: jax.Array, *,
                                 l_i: int = 8, l_w: int = 8, bm: int = 128,
                                 bn: int = 128, bk: int | None = None,
                                 interpret: bool = False,
                                 dot_impl: str = "auto",
                                 out_bits: int | None = None,
                                 out_block: int | None = None):
    """Both sides prequantized — the steady state of a bound plan chain:
    weights offline, activations from the previous layer's epilogue.  No
    in-kernel quantization at all; the datapath is pure int8 dots plus
    power-of-two rescales."""
    b, k = xm.shape
    k2, n = wm.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {xm.shape} @ {wm.shape}")
    bk = _resolve_bk(bk, b, k, n, l_i + l_w)
    if xs.shape != (b, k // bk):
        raise ValueError(f"activation sidecar {xs.shape} != "
                         f"{(b, k // bk)} for bk={bk}")
    if ws.shape != (k // bk, n):
        raise ValueError(f"scale sidecar {ws.shape} != {(k // bk, n)} "
                         f"for bk={bk}")
    if xm.dtype != jnp.int8 or wm.dtype != jnp.int8:
        raise ValueError(f"prequant kernels stream int8 mantissas, got "
                         f"{xm.dtype} / {wm.dtype}")
    return _matmul_call((xm, xs), (wm, ws), b=b, k=k, n=n, l_i=l_i,
                        l_w=l_w, bm=bm, bn=bn, bk=bk, interpret=interpret,
                        dot_impl=dot_impl,
                        out_q=_out_q(out_bits, out_block, bn))
