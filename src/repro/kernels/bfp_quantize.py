"""Standalone block-formatting Pallas kernel (paper eq. 1).

Streams an [M, K] float tensor through VMEM in (bm, bk) tiles and emits
int8 mantissas plus one int32 exponent per (row, K-tile) block — the
"block formatting" stage of the paper's accelerator, used when weights are
formatted once offline and streamed to HBM as int8 + exponent sidecar
(4x HBM traffic cut at L=8, the paper's bandwidth argument).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bfp import pow2

_ZERO_BLOCK_EXP = -126



def _bfp_quantize_kernel(x_ref, m_ref, e_ref, *, bits: int):
    tile = x_ref[...]
    amax = jnp.max(jnp.abs(tile), axis=1, keepdims=True)
    fbits = jax.lax.bitcast_convert_type(amax.astype(jnp.float32), jnp.uint32)
    e = (jnp.right_shift(fbits, jnp.uint32(23)) & jnp.uint32(0xFF)).astype(
        jnp.int32) - 127
    e = jnp.where(amax > 0, e, _ZERO_BLOCK_EXP)
    step = pow2(e - (bits - 2))
    lim = float(2 ** (bits - 1) - 1)
    m = jnp.clip(jnp.round(tile.astype(jnp.float32) / step), -lim, lim)
    m_ref[...] = m.astype(jnp.int8)  # quantize kernel is the L<=8 streaming path
    e_ref[...] = e


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bk", "interpret"))
def bfp_quantize_pallas(x: jax.Array, *, bits: int = 8, bm: int = 256,
                        bk: int = 512, interpret: bool = False):
    """[M, K] -> (int8 mantissa [M, K], int32 exponents [M, K//bk]).

    Each (row, bk-tile) is one BFP block.  M % bm == 0 and K % bk == 0
    (ops.py pads).
    """
    m_rows, k = x.shape
    if m_rows % bm or k % bk:
        raise ValueError(f"shape {x.shape} not a multiple of ({bm},{bk})")
    grid = (m_rows // bm, k // bk)
    kernel = functools.partial(_bfp_quantize_kernel, bits=bits)
    # exponents leave the kernel block-major [K/bk, M, 1]: a (bm, 1) block
    # of [M, K/bk] breaks Mosaic's rule that a block's last two dims
    # divide by (8, 128) or equal the array's
    m, e = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((pl.Squeezed(), bm, 1), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_rows, k), jnp.int8),
            jax.ShapeDtypeStruct((k // bk, m_rows, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    return m, e[:, :, 0].T
