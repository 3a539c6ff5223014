"""Hillclimb autotuner for BFP kernel tile configs.

The measure-and-cache shape follows ``launch/hillclimb.py``: each named
candidate is measured (median wall-clock over a few calls, after a
warmup that also pays compilation), results land in a persistent cache,
and already-cached sites are skipped.  Here the variants are not
hand-named though — the tuner walks the power-of-two tile lattice
greedily: evaluate the fallback config, then all single-axis x2 / /2
neighbors, move to the best, repeat until no neighbor wins (or
``max_steps`` evaluations).

Constraints baked into the neighborhood (never evaluated, not just
rejected): the int32-overflow bound ``L_I + L_W + ceil(log2 bk) <= 32``
(paper Fig. 2), the 8-sublane floor, and tiles never more than one
power of two beyond the problem dim (padding past that is pure waste).
When ``policy.block_k`` is pinned, the BFP block IS the K tile —
semantics, not a knob — so only (bm, bn) (GEMM) or (t_oh, bn) (conv)
move.

Usage (CLI, writes/updates the JSON cache):

    PYTHONPATH=src python -m repro.tune --out tune_cache.json [--smoke]

Programmatic:

    cache = TuneCache.load("tune_cache.json")
    tune_gemm(b, k, n, policy, cache=cache)   # no-op if already cached
    cache.save()
    plan = engine.bind(params, pm, paths, tune_cache=cache)
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax

from repro.tune.cache import TuneCache
from repro.tune.tables import conv_row_tile, fallback_tiles, overflow_cap

__all__ = ["tune_gemm", "tune_conv", "time_us"]


def time_us(fn: Callable[[], Any], iters: int = 3,
            warmup: int = 1) -> float:
    """Median wall-clock microseconds of ``fn()`` (jax-blocking)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def _pow2_ge(d: int) -> int:
    return 1 << max(0, d - 1).bit_length()


def _axis_neighbors(v: int, lo: int, hi: int) -> Iterable[int]:
    if v * 2 <= hi:
        yield v * 2
    if v // 2 >= lo:
        yield v // 2


def _hillclimb(start: Tuple[int, ...],
               neighbors: Callable[[Tuple[int, ...]],
                                   Iterable[Tuple[int, ...]]],
               evaluate: Callable[[Tuple[int, ...]], float],
               max_steps: int) -> Tuple[Tuple[int, ...], float, int]:
    """Greedy best-neighbor walk; returns (best config, best us, evals)."""
    seen: Dict[Tuple[int, ...], float] = {}

    def ev(cfg):
        if cfg not in seen:
            seen[cfg] = evaluate(cfg)
        return seen[cfg]

    best, best_us = start, ev(start)
    improved = True
    while improved and len(seen) < max_steps:
        improved = False
        for cand in neighbors(best):
            if len(seen) >= max_steps:
                break
            if cand in seen:
                continue
            us = ev(cand)
            if us < best_us:
                best, best_us, improved = cand, us, True
    return best, best_us, len(seen)


def tune_gemm(b: int, k: int, n: int, policy, *, cache: TuneCache,
              interpret: Optional[bool] = None, max_steps: int = 12,
              iters: int = 3, x: Optional[jax.Array] = None,
              w: Optional[jax.Array] = None) -> Dict[str, Any]:
    """Tune (bm, bn, bk) for one GEMM site; returns the cache entry.

    Already-cached sites return immediately (the launch/hillclimb.py
    skip-if-cached shape).  ``bk`` only moves when ``policy.block_k`` is
    None; a pinned block is the BFP block and stays fixed.
    """
    from repro.kernels import ops  # late: ops imports tune.tables

    if interpret is None:
        interpret = ops.default_interpret()
    target = TuneCache.target(interpret)
    ent = cache.lookup("gemm", b, k, n, policy.l_i, policy.l_w,
                       policy.block_k, target)
    if ent is not None:
        return ent

    if x is None:
        x = jax.random.normal(jax.random.PRNGKey(0), (b, k))
    if w is None:
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.1
    l_sum = policy.l_i + policy.l_w
    start = fallback_tiles(b, k, n, policy.block_k, l_sum)
    bk_free = not policy.block_k
    bm_hi = max(8, _pow2_ge(b))
    bn_hi = max(8, _pow2_ge(n))
    bk_hi = min(max(8, _pow2_ge(k)), overflow_cap(l_sum))

    def neighbors(cfg):
        bm, bn, bk = cfg
        for v in _axis_neighbors(bm, 8, bm_hi):
            yield (v, bn, bk)
        for v in _axis_neighbors(bn, 8, bn_hi):
            yield (bm, v, bk)
        if bk_free:
            for v in _axis_neighbors(bk, 8, bk_hi):
                yield (bm, bn, v)

    def evaluate(cfg):
        return time_us(
            lambda: ops.bfp_matmul(x, w, policy, interpret, tiles=cfg),
            iters=iters)

    best, us, steps = _hillclimb(start, neighbors, evaluate, max_steps)
    entry = {"bm": best[0], "bn": best[1], "bk": best[2],
             "us": round(us, 1), "steps": steps}
    cache.store("gemm", b, k, n, policy.l_i, policy.l_w, policy.block_k,
                target, entry)
    return entry


def tune_conv(b: int, h: int, w_in: int, c: int, kh: int, oc: int,
              policy, *, stride: int = 1, padding: str = "SAME",
              cache: TuneCache, interpret: Optional[bool] = None,
              max_steps: int = 10, iters: int = 3) -> Dict[str, Any]:
    """Tune (t_oh, bn) for one conv site (bk is the policy block —
    pinned); keys on the im2col GEMM view of the problem."""
    from repro.core.conv_utils import conv_geometry
    from repro.kernels import ops  # late: ops imports tune.tables

    if interpret is None:
        interpret = ops.default_interpret()
    target = TuneCache.target(interpret)
    kk = kh * kh * c
    oh, ow, _, _ = conv_geometry(h, w_in, kh, kh, stride, padding)
    rows = b * oh * ow
    ent = cache.lookup("conv", rows, kk, oc, policy.l_i, policy.l_w,
                       policy.block_k, target)
    if ent is not None:
        return ent

    x = jax.random.normal(jax.random.PRNGKey(0), (b, h, w_in, c))
    wk = jax.random.normal(jax.random.PRNGKey(1), (kh, kh, c, oc)) * 0.1
    start = (conv_row_tile(oh, ow), fallback_tiles(rows, kk, oc, None)[1])
    t_hi = max(1, _pow2_ge(oh))
    bn_hi = max(8, _pow2_ge(oc))

    def neighbors(cfg):
        t_oh, bn = cfg
        for v in _axis_neighbors(t_oh, 1, t_hi):
            yield (v, bn)
        for v in _axis_neighbors(bn, 8, bn_hi):
            yield (t_oh, v)

    def evaluate(cfg):
        return time_us(
            lambda: ops.bfp_conv2d(x, wk, policy, stride, padding,
                                   interpret, tiles=cfg),
            iters=iters)

    best, us, steps = _hillclimb(start, neighbors, evaluate, max_steps)
    entry = {"t_oh": best[0], "bn": best[1], "bk": policy.block_k,
             "us": round(us, 1), "steps": steps}
    cache.store("conv", rows, kk, oc, policy.l_i, policy.l_w,
                policy.block_k, target, entry)
    return entry
