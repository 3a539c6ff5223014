"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes/dtypes/bit-widths and asserts allclose (mostly bit-exact)
against ref.py, and triangulates against the core-library emulated path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import BFPPolicy, Scheme
from repro.core.bfp_dot import bfp_matmul_2d
from repro.kernels import ops, ref
from repro.kernels.bfp_matmul import bfp_matmul_pallas
from repro.kernels.bfp_quantize import bfp_quantize_pallas


def _rand(key, shape, dtype, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


@pytest.mark.parametrize("b,k,n", [(8, 128, 8), (128, 256, 128),
                                   (64, 512, 32), (256, 1024, 128)])
@pytest.mark.parametrize("bits", [4, 6, 8])
def test_matmul_kernel_matches_ref(b, k, n, bits):
    x = _rand(jax.random.PRNGKey(0), (b, k), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(1), (k, n), jnp.float32, 0.1)
    bk = min(128, k)
    out_k = bfp_matmul_pallas(x, w, l_i=bits, l_w=bits, bm=min(128, b),
                              bn=min(128, n), bk=bk, interpret=True)
    out_r = ref.bfp_matmul_ref(x, w, bits, bits, bk)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_dtypes(dtype):
    x = _rand(jax.random.PRNGKey(2), (128, 256), dtype)
    w = _rand(jax.random.PRNGKey(3), (256, 128), dtype, 0.05)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128, straight_through=False)
    out_k = ops.bfp_matmul(x, w, pol, interpret=True)
    out_r = ref.bfp_matmul_ref(x.astype(jnp.float32),
                               w.astype(jnp.float32), 8, 8, 128)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


def test_matmul_kernel_matches_core_library():
    x = _rand(jax.random.PRNGKey(4), (128, 512), jnp.float32, 4.0)
    w = _rand(jax.random.PRNGKey(5), (512, 128), jnp.float32, 0.2)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128, straight_through=False)
    out_k = ops.bfp_matmul(x, w, pol, interpret=True)
    out_c = bfp_matmul_2d(x, w, pol)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                               rtol=1e-6, atol=1e-6)


def test_matmul_kernel_ragged_padding():
    """Non-multiple shapes go through ops.py padding and stay exact."""
    x = _rand(jax.random.PRNGKey(6), (100, 300), jnp.float32)
    w = _rand(jax.random.PRNGKey(7), (300, 70), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128, straight_through=False)
    out = ops.bfp_matmul(x, w, pol, interpret=True)
    assert out.shape == (100, 70)
    xp = jnp.pad(x, ((0, 28), (0, 84)))
    wp = jnp.pad(w, ((0, 84), (0, 58)))
    out_r = ref.bfp_matmul_ref(xp, wp, 8, 8, 128)[:100, :70]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


def test_matmul_kernel_accuracy_vs_float():
    """BFP-8 GEMM should be within ~2% relative error of the float GEMM."""
    x = _rand(jax.random.PRNGKey(8), (256, 512), jnp.float32)
    w = _rand(jax.random.PRNGKey(9), (512, 256), jnp.float32, 0.05)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128, straight_through=False)
    out = ops.bfp_matmul(x, w, pol, interpret=True)
    rel = float(jnp.linalg.norm(out - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.02, rel


def test_matmul_kernel_overflow_guard():
    x = jnp.ones((128, 65536 * 2), jnp.float32)
    w = jnp.ones((65536 * 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="overflow"):
        bfp_matmul_pallas(x, w, l_i=8, l_w=8, bk=65536 * 2, interpret=True)


@pytest.mark.parametrize("m,k,bk", [(256, 512, 128), (8, 128, 128),
                                    (256, 2048, 512)])
@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_kernel_matches_ref(m, k, bk, bits):
    x = _rand(jax.random.PRNGKey(10), (m, k), jnp.float32, 3.0)
    mq, eq = bfp_quantize_pallas(x, bits=bits, bm=min(256, m), bk=bk,
                                 interpret=True)
    mr, er = ref.bfp_quantize_ref(x, bits, bk)
    np.testing.assert_array_equal(np.asarray(mq), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(eq), np.asarray(er))


def test_quantize_kernel_zero_block():
    x = jnp.zeros((8, 128), jnp.float32)
    mq, eq = bfp_quantize_pallas(x, bits=8, bm=8, bk=128, interpret=True)
    assert int(jnp.max(jnp.abs(mq))) == 0


@pytest.mark.parametrize("b,k,n", [(1, 32, 1), (100, 300, 70), (7, 129, 9),
                                   (130, 512, 200)])
def test_default_tiles_align_odd_shapes(b, k, n):
    """Tiles are power-of-two, capped at the MXU dim, and divide the
    padded problem; auto-bk respects the int32 overflow bound."""
    bm, bn, bk = ops.default_tiles(b, k, n, None)
    for tile in (bm, bn, bk):
        assert tile & (tile - 1) == 0 and tile >= 8
    assert bm <= 128 and bn <= 128
    assert (-b % bm) < bm and (-n % bn) < bn     # padding < one tile
    # overflow cap: auto bk must be accumulation-safe for wide mantissas
    _, _, bk24 = ops.default_tiles(b, k, n, None, l_sum=24)
    assert bk24 <= 2 ** (32 - 24)


@pytest.mark.parametrize("b,k,n", [(1, 32, 1), (100, 300, 70), (7, 129, 9)])
def test_matmul_kernel_odd_shapes_match_ref(b, k, n):
    """Odd/padded shapes through ops.bfp_matmul stay exact vs the oracle
    run on the identically padded problem."""
    x = _rand(jax.random.PRNGKey(20), (b, k), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(21), (k, n), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=None,
                    straight_through=False)
    out = ops.bfp_matmul(x, w, pol, interpret=True)
    assert out.shape == (b, n)
    bm, bn, bk = ops.default_tiles(b, k, n, None)
    xp = jnp.pad(x, ((0, -b % bm), (0, -k % bk)))
    wp = jnp.pad(w, ((0, -k % bk), (0, -n % bn)))
    out_r = ref.bfp_matmul_ref(xp, wp, 8, 8, bk)[:b, :n]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b,n", [(100, 70), (8, 8), (1, 200)])
def test_prequant_kernel_matches_fused(b, n):
    """The sidecar-consuming kernel == the fused kernel, bit for bit,
    including B/N padding paths."""
    from repro.core.bfp_dot import bfp_matmul_2d
    from repro.core.prequant import prequant_leaf
    k = 256
    x = _rand(jax.random.PRNGKey(22), (b, k), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(23), (k, n), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    pq = prequant_leaf(w, pol)
    out_pq = ops.bfp_matmul_prequant(x, pq["m"], pq["s"], pol,
                                     interpret=True)
    out_fused = ops.bfp_matmul(x, w, pol, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pq), np.asarray(out_fused))
    # and both equal the emulated core datapath
    np.testing.assert_allclose(np.asarray(out_pq),
                               np.asarray(bfp_matmul_2d(x, w, pol)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    b=st.sampled_from([8, 16, 64]),
    kt=st.sampled_from([1, 2, 4]),
    n=st.sampled_from([8, 32, 128]),
    bits=st.integers(min_value=3, max_value=9),
    scale_pow=st.integers(min_value=-8, max_value=8),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_matmul_kernel_property(b, kt, n, bits, scale_pow, seed):
    """Property: kernel == oracle for random shapes/bits/dynamic ranges."""
    bk = 128
    k = kt * bk
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (b, k)) * (2.0 ** scale_pow)
    w = jax.random.normal(kw, (k, n))
    out_k = bfp_matmul_pallas(x, w, l_i=bits, l_w=bits, bm=min(128, b),
                              bn=min(128, n), bk=bk, interpret=True)
    out_r = ref.bfp_matmul_ref(x, w, bits, bits, bk)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-30)

# ---------------------------------------------------------------------------
# ISSUE 6 — dot-mode datapaths, pipelining, fused requantize epilogue
# ---------------------------------------------------------------------------

from repro.core.prequant import dequantize_act, is_prequant, prequant_act  # noqa: E402
from repro.kernels.bfp_matmul import f32_dot_exact, resolve_dot_impl  # noqa: E402


@pytest.mark.parametrize("dot_impl", ["int8", "int32", "f32"])
@pytest.mark.parametrize("pipeline", [False, True])
def test_matmul_dot_modes_bit_identical(dot_impl, pipeline):
    """Every dot datapath x pipelining matches the oracle AND the legacy
    int32/unpipelined kernel bit for bit (f32 is exact at bk=128, L=8:
    128 * 127 * 127 < 2^24; int8 products widen to int32 in the MXU)."""
    x = _rand(jax.random.PRNGKey(30), (64, 384), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(31), (384, 48), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    out = ops.bfp_matmul(x, w, pol, True, dot_impl=dot_impl,
                         pipeline=pipeline)
    base = ops.bfp_matmul(x, w, pol, True, dot_impl="int32",
                          pipeline=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    out_r = ref.bfp_matmul_ref(x, w, 8, 8, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("li,lw", [(4, 4), (6, 8), (8, 6), (10, 10),
                                   (12, 12)])
def test_matmul_auto_dot_bitwidth_sweep(li, lw):
    """auto mode stays exact across L=4..12 (L > 8 forces the widened
    int32 path; the overflow cap 2^(32-L_I-L_W) still admits bk=128)."""
    x = _rand(jax.random.PRNGKey(32), (32, 256), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(33), (256, 24), jnp.float32, 0.1)
    pol = BFPPolicy(l_i=li, l_w=lw, scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    out = ops.bfp_matmul(x, w, pol, True)
    out_r = ref.bfp_matmul_ref(x, w, li, lw, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


def test_resolve_dot_impl_rules():
    """Mode resolution: auto picks the exact-f32 BLAS path on interpret
    within the 2^24 bound, int32 past it or for wide mantissas, int8 on
    a compiled target; explicit modes validate their preconditions."""
    assert f32_dot_exact(8, 8, 128) and f32_dot_exact(8, 8, 1024)
    assert not f32_dot_exact(8, 8, 2048)
    assert resolve_dot_impl("auto", l_i=8, l_w=8, bk=128,
                            interpret=True) == "f32"
    assert resolve_dot_impl("auto", l_i=8, l_w=8, bk=2048,
                            interpret=True) == "int32"
    assert resolve_dot_impl("auto", l_i=10, l_w=8, bk=128,
                            interpret=True) == "int32"
    assert resolve_dot_impl("auto", l_i=8, l_w=8, bk=128,
                            interpret=False) == "int8"
    # prequant operands are int8 on the wire whatever the stated L
    assert resolve_dot_impl("auto", l_i=12, l_w=12, bk=128,
                            interpret=False, x_pq=True, w_pq=True) == "int8"
    with pytest.raises(ValueError, match="int8"):
        resolve_dot_impl("int8", l_i=10, l_w=8, bk=128, interpret=True)
    with pytest.raises(ValueError, match="not exact"):
        resolve_dot_impl("f32", l_i=12, l_w=12, bk=128, interpret=True)
    with pytest.raises(ValueError, match="unknown"):
        resolve_dot_impl("fp8", l_i=8, l_w=8, bk=128, interpret=True)


@pytest.mark.parametrize("tiles", [(8, 8, 128), (32, 64, 128),
                                   (128, 128, 128)])
def test_matmul_tiles_are_performance_only(tiles):
    """With block_k pinned, (bm, bn) tiling must never change a bit —
    the invariant that makes the autotuner safe to trust blindly."""
    x = _rand(jax.random.PRNGKey(34), (96, 256), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(35), (256, 80), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    base = ops.bfp_matmul(x, w, pol, True)
    out = ops.bfp_matmul(x, w, pol, True, tiles=tiles)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("bq,n", [(8, 64), (16, 48), (8, 72)])
def test_matmul_epilogue_requant_bit_identical(pipeline, bq, n):
    """Fused epilogue requantization == dequantize-then-prequant_act,
    bit for bit, across out-block sizes and an N the default bn does
    not divide (which exercises the two-step fallback inside ops)."""
    x = _rand(jax.random.PRNGKey(36), (64, 256), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(37), (256, n), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    out_pol = pol.with_(block_k=bq)
    fused = ops.bfp_matmul(x, w, pol, True, out_policy=out_pol,
                           pipeline=pipeline)
    two = prequant_act(ops.bfp_matmul(x, w, pol, True, pipeline=pipeline),
                       out_pol)
    assert is_prequant(fused) and fused["m"].dtype == jnp.int8
    assert fused["m"].shape == (64, n)
    assert fused["s"].shape == (64, n // bq)
    np.testing.assert_array_equal(np.asarray(fused["m"]),
                                  np.asarray(two["m"]))
    np.testing.assert_array_equal(np.asarray(fused["s"]),
                                  np.asarray(two["s"]))


def test_matmul_act_dict_input_bit_identical():
    """int8 wire-format activations consumed natively == dequantize +
    inline re-quantization (idempotence on matching blocks) — the
    layer-to-layer handoff contract."""
    x = _rand(jax.random.PRNGKey(38), (48, 256), jnp.float32, 2.0)
    w = _rand(jax.random.PRNGKey(39), (256, 32), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    xq = prequant_act(x, pol)
    assert is_prequant(xq) and xq["m"].dtype == jnp.int8
    out_d = ops.bfp_matmul(xq, w, pol, True)
    out_f = ops.bfp_matmul(dequantize_act(xq), w, pol, True)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_f))


def test_matmul_epilogue_then_consume_chain():
    """gemm -> gemm entirely on the wire format: the fused-epilogue
    output feeds the next kernel directly and lands bit-identical to
    the all-float-activation chain with inline quantization."""
    x = _rand(jax.random.PRNGKey(40), (32, 256), jnp.float32, 2.0)
    w1 = _rand(jax.random.PRNGKey(41), (256, 128), jnp.float32, 0.1)
    w2 = _rand(jax.random.PRNGKey(42), (128, 16), jnp.float32, 0.1)
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=128,
                    straight_through=False)
    y1 = ops.bfp_matmul(x, w1, pol, True, out_policy=pol)
    out = ops.bfp_matmul(y1, w2, pol, True)
    y1_f = ops.bfp_matmul(x, w1, pol, True)
    out_ref = ops.bfp_matmul(y1_f, w2, pol, True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
