"""Autotuner (ISSUE 6): cache round-trip, hillclimb, ops integration.

Contracts:
  * the JSON cache round-trips exactly and invalidates on schema bumps;
  * keys carry everything that changes the optimum (shape, L, block,
    target) — nothing else hits;
  * tuned tiles are PERFORMANCE-ONLY: with block_k pinned they may never
    change a bit of output, so a stale/wrong cache entry can cost speed
    but not correctness;
  * tune_gemm/tune_conv hillclimb within budget, store the winner, and
    skip already-cached sites.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import BFPPolicy, Scheme
from repro.kernels import ops
from repro.tune.autotune import time_us, tune_conv, tune_gemm
from repro.tune.cache import (SCHEMA, TuneCache, get_cache, lookup_tiles,
                              use_cache)
from repro.tune.tables import (DEEP_K_BK, aligned_tile, conv_row_tile,
                               fallback_tiles, overflow_cap)

TILED16 = BFPPolicy(scheme=Scheme.TILED, block_k=16,
                    straight_through=False)


# ---------------------------------------------------------------------------
# cache: keying, persistence, schema invalidation
# ---------------------------------------------------------------------------

def test_cache_key_stability():
    """The key format is persisted in committed JSON — it must not move."""
    assert TuneCache.key("gemm", 64, 512, 128, 8, 8, 128, "interpret") == \
        "gemm:b64k512n128:L8.8:bk128:interpret"
    assert TuneCache.key("conv", 1024, 27, 64, 8, 8, None, "cpu") == \
        "conv:b1024k27n64:L8.8:bk0:cpu"


def test_cache_roundtrip(tmp_path):
    p = str(tmp_path / "cache.json")
    ent = {"bm": 8, "bn": 8, "bk": 16, "us": 1.5, "steps": 3}
    c = TuneCache(path=p)
    c.store("gemm", 8, 64, 8, 8, 8, 16, "interpret", ent)
    assert c.save() == p
    c2 = TuneCache.load(p)
    assert len(c2) == 1
    assert c2.lookup("gemm", 8, 64, 8, 8, 8, 16, "interpret") == ent
    assert (c2.hits, c2.misses) == (1, 0)
    # any keyed field changing is a different site: no hit
    assert c2.lookup("gemm", 9, 64, 8, 8, 8, 16, "interpret") is None
    assert c2.lookup("gemm", 8, 64, 8, 6, 8, 16, "interpret") is None
    assert c2.lookup("gemm", 8, 64, 8, 8, 8, None, "interpret") is None
    assert c2.lookup("gemm", 8, 64, 8, 8, 8, 16, "cpu") is None
    assert c2.misses == 4


def test_cache_schema_invalidation(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"schema": SCHEMA + 1,
                             "entries": {"k": {"bm": 8}}}))
    assert len(TuneCache.load(str(p))) == 0      # stale schema dropped
    assert len(TuneCache.load(str(tmp_path / "missing.json"))) == 0


def test_lookup_tiles_scoped_by_use_cache():
    c = TuneCache()
    c.store("gemm", 8, 64, 8, 8, 8, None, "interpret",
            {"bm": 8, "bn": 8, "bk": 32, "us": 1.0, "steps": 1})
    c.store("conv", 128, 27, 16, 8, 8, 3, "interpret",
            {"t_oh": 4, "bn": 16, "bk": 3, "us": 1.0, "steps": 1})
    assert lookup_tiles("gemm", 8, 64, 8, 8, 8, None, True) is None
    with use_cache(c):
        assert get_cache() is c
        assert lookup_tiles("gemm", 8, 64, 8, 8, 8, None, True) == (8, 8, 32)
        assert lookup_tiles("conv", 128, 27, 16, 8, 8, 3, True) == (4, 16)
        assert lookup_tiles("gemm", 9, 64, 8, 8, 8, None, True) is None
    assert get_cache() is None and \
        lookup_tiles("gemm", 8, 64, 8, 8, 8, None, True) is None


# ---------------------------------------------------------------------------
# tuned tiles flow into ops and never change output bits
# ---------------------------------------------------------------------------

def test_tuned_tiles_bit_identical():
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 64)) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 24)) * 0.1
    base = ops.bfp_matmul(x, w, TILED16, True)
    c = TuneCache()
    c.store("gemm", 24, 64, 24, 8, 8, 16, "interpret",
            {"bm": 8, "bn": 8, "bk": 16, "us": 1.0, "steps": 1})
    with use_cache(c):
        out = ops.bfp_matmul(x, w, TILED16, True)
    assert c.hits >= 1      # the kernel wrapper consulted the cache
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_tuned_conv_tiles_bit_identical():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 8)) * 2.0
    wk = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 8, 12)) * 0.1
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=24,
                    straight_through=False)
    base = ops.bfp_conv2d(x, wk, pol, 1, "SAME", True)
    c = TuneCache()
    c.store("conv", 2 * 8 * 8, 72, 12, 8, 8, 24, "interpret",
            {"t_oh": 2, "bn": 8, "bk": 24, "us": 1.0, "steps": 1})
    with use_cache(c):
        out = ops.bfp_conv2d(x, wk, pol, 1, "SAME", True)
    assert c.hits >= 1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


# ---------------------------------------------------------------------------
# the hillclimber itself
# ---------------------------------------------------------------------------

def test_tune_gemm_small_site_and_cache_skip():
    c = TuneCache()
    ent = tune_gemm(16, 32, 16, TILED16, cache=c, interpret=True,
                    max_steps=4, iters=1)
    assert ent["bk"] == 16          # pinned block == K tile, never moves
    assert 1 <= ent["steps"] <= 4 and ent["us"] > 0
    assert len(c) == 1
    hits0 = c.hits
    assert tune_gemm(16, 32, 16, TILED16, cache=c, interpret=True,
                     max_steps=4, iters=1) == ent    # skip-if-cached
    assert c.hits == hits0 + 1 and len(c) == 1


def test_tune_gemm_free_bk_respects_overflow():
    """With block_k=None the K tile is a knob, but the neighborhood must
    stay inside the int32 accumulation bound for wide mantissas."""
    pol = BFPPolicy(l_i=12, l_w=12, scheme=Scheme.TILED, block_k=None,
                    straight_through=False)
    c = TuneCache()
    ent = tune_gemm(8, 1024, 8, pol, cache=c, interpret=True,
                    max_steps=3, iters=1)
    assert ent["bk"] <= overflow_cap(24)


def test_tune_conv_small_site_and_cache_skip():
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=24,
                    straight_through=False)
    c = TuneCache()
    ent = tune_conv(1, 8, 8, 8, 3, 16, pol, cache=c, interpret=True,
                    max_steps=4, iters=1)
    assert set(ent) >= {"t_oh", "bn", "bk", "us", "steps"}
    assert ent["bk"] == 24
    assert tune_conv(1, 8, 8, 8, 3, 16, pol, cache=c, interpret=True,
                     max_steps=4, iters=1) == ent
    assert len(c) == 1


def test_time_us_returns_positive_median():
    assert time_us(lambda: jax.numpy.zeros(4), iters=3, warmup=1) > 0


# ---------------------------------------------------------------------------
# fallback table (the no-cache answer the tuner starts from)
# ---------------------------------------------------------------------------

def test_fallback_tiles_contract():
    assert overflow_cap(16) == 65536
    assert fallback_tiles(100, 2048, 300, None) == (128, 128, DEEP_K_BK)
    assert fallback_tiles(8, 64, 8, None, l_sum=30)[2] == 4   # capped
    assert fallback_tiles(8, 64, 8, 16)[2] == 16              # pinned
    assert aligned_tile(1) == 8 and aligned_tile(300) == 128
    assert conv_row_tile(32, 16) == 8       # 128-row M tile for the MXU
    assert conv_row_tile(8, 200) == 1       # one wide row is enough
