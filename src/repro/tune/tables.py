"""THE default tile table — one documented fallback path for every kernel.

Before the autotuner existed, the fused and prequant matmul kernels
carried *different* hardcoded defaults (``bk=512`` vs ``bk=128``) in
their signatures, and ``ops.default_tiles`` re-derived a third opinion.
This module is now the single source of truth: the autotune cache
(:mod:`repro.tune.cache`) is consulted first, and when it has no entry
for a site, :func:`fallback_tiles` answers — for BOTH the fused and the
prequant paths, GEMM and conv alike.  ``kernels.ops`` re-exports
:func:`aligned_tile` / delegates ``default_tiles`` here so legacy
imports keep working.

Pure Python (no jax import): the table must be consultable at trace
time and from the autotuner CLI without touching a backend.
"""
from __future__ import annotations

from typing import Optional, Tuple

__all__ = ["aligned_tile", "fallback_tiles", "overflow_cap",
           "conv_row_tile", "patch_row_tile", "MXU_DIM", "DEEP_K_BK",
           "PATCH_ROWS"]

#: The MXU systolic array dimension — bm/bn never exceed it by default.
MXU_DIM = 128

#: Default K tile for deep contractions (bandwidth-friendly multiple of
#: the MXU dim).  Shallow contractions take the aligned tile instead.
DEEP_K_BK = 512


def _pow2_ge(d: int) -> int:
    """Smallest power of two >= d (d >= 1)."""
    return 1 << max(0, d - 1).bit_length()


def aligned_tile(d: int, cap: int = MXU_DIM) -> int:
    """THE power-of-two-aligned tile floor, shared by every wrapper:
    next power of two >= d, floored at 8 (sublane minimum) and capped at
    ``cap`` (the MXU dimension, or a bandwidth-friendly multiple of it).
    Small/odd problem dims pad to the NEAREST aligned tile, not a full
    cap."""
    return min(cap, max(8, _pow2_ge(d)))


def overflow_cap(l_sum: int) -> int:
    """Largest K tile whose int32 accumulation cannot overflow (paper
    Fig. 2 sizing): 2^(32 - (L_I + L_W))."""
    return 1 << max(0, 32 - l_sum)


def fallback_tiles(b: int, k: int, n: int, block_k: Optional[int],
                   l_sum: int = 16) -> Tuple[int, int, int]:
    """Default MXU-aligned tiles for a (b, k) x (k, n) problem.

    bm/bn: the MXU dimension capped below at 8 and shrunk to the next
    power of two when the problem dimension is smaller — small or odd
    shapes pad to the NEAREST aligned tile instead of a full 128.
    bk: the BFP block size when given (block == K tile by construction);
    otherwise ``DEEP_K_BK`` for deep contractions and the aligned tile
    for shallow ones, capped by the int32 overflow bound (paper Fig. 2)
    so auto-picked tiles are always accumulation-safe for the policy's
    mantissa widths.
    """
    bm = aligned_tile(b)
    bn = aligned_tile(n)
    if block_k:
        bk = block_k
    else:
        bk = DEEP_K_BK if k >= DEEP_K_BK else aligned_tile(k)
        bk = min(bk, overflow_cap(l_sum))   # always accumulation-safe
    return bm, bn, bk


def conv_row_tile(oh: int, ow: int) -> int:
    """Default output-row tile for the fused conv kernels: enough rows
    per program to feed the MXU a >=128-row M tile when OW is small;
    one row when OW alone is wide enough."""
    return max(1, min(oh, MXU_DIM // max(1, ow)))


#: Rows per program, at most, of the pointwise conv a narrow-channel conv
#: becomes over its patch tensor (``kernels.ops.bfp_conv2d``).  On a v5e
#: at batch 32 the stem's 1x1 kernel took 3.26 ms at 112 rows a program
#: and 2.09 ms at 3,136; beyond that the gain is < 5 % and Mosaic's
#: compile time grows with the tile (PERF.md §6).
PATCH_ROWS = 4096


def patch_row_tile(oh: int, ow: int) -> int:
    """Default output-row tile of a conv over a patch tensor: the largest
    divisor of ``oh`` whose rows hold at most :data:`PATCH_ROWS` pixels,
    so no output row is padded (and no patch row either)."""
    return max(t for t in range(1, oh + 1)
               if oh % t == 0 and (t == 1 or t * ow <= PATCH_ROWS))
