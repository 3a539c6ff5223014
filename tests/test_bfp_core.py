"""Unit + property tests for the core BFP library (paper §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bfp
from repro.core.bfp import Rounding, Scheme
from repro.core.bfp_dot import bfp_dot, bfp_matmul_2d
from repro.core.policy import BFPPolicy, PAPER_DEFAULT


def test_block_exponent_exact():
    x = jnp.asarray([[1.5, -3.0, 0.25, 7.9]])
    e = bfp.block_exponent(x, (1,))
    assert int(e[0, 0]) == 2  # floor(log2 7.9) = 2


def test_zero_block():
    b = bfp.quantize(jnp.zeros((4, 8)), 8, (1,))
    assert int(jnp.max(jnp.abs(b.mantissa))) == 0
    np.testing.assert_allclose(np.asarray(b.dequantize()), 0.0)


def test_quantize_error_bound():
    """|x - q(x)| <= step/2 for every element (round-off, paper eq. 1)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 10
    b = bfp.quantize(x, 8, (0, 1))
    step = float(b.scale.reshape(-1)[0])
    err = np.abs(np.asarray(b.dequantize() - x))
    assert err.max() <= step / 2 + 1e-9


def test_largest_element_representable():
    """The block max must survive quantization without clipping."""
    x = jnp.asarray([[100.0, 0.001]])
    b = bfp.quantize(x, 8, (1,))
    assert abs(float(b.dequantize()[0, 0]) - 100.0) / 100.0 < 0.01


def test_rounding_beats_truncation_bias():
    """Paper §3.1: truncation has a DC bias, rounding is ~zero-mean."""
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    br = bfp.quantize(x, 6, (1,), Rounding.ROUND)
    bt = bfp.quantize(x, 6, (1,), Rounding.TRUNCATE)
    bias_r = abs(float(jnp.mean(br.dequantize() - x)))
    bias_t = abs(float(jnp.mean(bt.dequantize() - x)))
    assert bias_t > 5 * bias_r


def test_stochastic_rounding_unbiased():
    x = jnp.full((1, 512), 0.3)
    keys = jax.random.split(jax.random.PRNGKey(2), 64)
    deq = jnp.stack([bfp.quantize(x, 4, (1,), Rounding.STOCHASTIC, k)
                     .dequantize() for k in keys])
    assert abs(float(jnp.mean(deq)) - 0.3) < 0.01


@pytest.mark.parametrize("scheme", list(Scheme))
def test_scheme_shapes(scheme):
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    blk = bfp.bfp_quantize_matrix(w, 8, "w", scheme, block_k=16)
    assert blk.mantissa.shape == w.shape
    exp = {Scheme.EQ2: 1, Scheme.EQ3: 64, Scheme.EQ4: 64, Scheme.EQ5: 1,
           Scheme.TILED: 64 * 2}[scheme]  # 64 rows x (K=32)/(bk=16) tiles
    assert blk.exponent.size == exp


def test_scheme_accuracy_ordering():
    """Finer blocks never hurt: TILED >= EQ3 >= EQ4 >= EQ2 output SNR
    (activations with heavy dynamic range; paper Table 2 direction)."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (128, 256)) * \
        jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (128, 256)))
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 64)) * 0.1
    ref = x @ w

    def snr(scheme, bk=None):
        p = BFPPolicy(scheme=scheme, block_k=bk, straight_through=False)
        y = bfp_dot(x, w, p)
        return 10 * np.log10(float(jnp.sum(ref**2) /
                                   jnp.sum((y - ref)**2)))

    s2, s4, s3 = snr(Scheme.EQ2), snr(Scheme.EQ4), snr(Scheme.EQ3)
    st = snr(Scheme.TILED, 32)
    assert s3 >= s4 - 0.5 and s4 >= s2 - 0.5
    assert st >= s3 - 0.5


def test_paper_worked_example():
    """Paper §3.4 numeric example: I block-formatted with eps_I = 2."""
    i_mat = jnp.asarray([[1.25 * 2 ** 0, 1.25 * 2 ** 0],
                         [1.25 * 2 ** 1, 1.25 * 2 ** 2]])
    b = bfp.quantize(i_mat, 4, (0, 1))  # L=4 incl sign ~ paper L_I=3 + sign
    assert int(b.exponent.reshape(-1)[0]) == 2
    # largest value 5.0 must be exact: 5 = 1.01b * 2^2
    assert float(b.dequantize()[1, 1]) == 5.0


def test_storage_accounting():
    # paper Table 1: eq4 stores 1 + M exponents
    assert bfp.num_block_exponents(Scheme.EQ4, m=64, k=9, n=50176) == 65
    assert bfp.num_block_exponents(Scheme.EQ2, m=64, k=9, n=50176) == 2
    assert bfp.num_block_exponents(Scheme.EQ3, m=64, k=9, n=50176) == 50240
    # avg bits: 8-bit mantissa(incl sign) + 8-bit exp over 512-block
    assert bfp.average_bits_per_element(8, 8, 512) == 8 + 8 / 512


def test_accumulator_sizing():
    # paper Fig. 2: L_W + L_I + ceil(log2 K)
    assert bfp.accumulator_bits(8, 8, 4608) == 16 + 13
    assert bfp.max_safe_k(8, 8) == 65536


def test_int_datapath_exactness():
    """The integer path must equal exact math on the dequantized operands
    (the fixed-point MACs add NO error beyond quantization, paper Fig. 2)."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (32, 128)) * 4
    w = jax.random.normal(jax.random.PRNGKey(8), (128, 16))
    p = PAPER_DEFAULT.with_(straight_through=False)
    from repro.core.bfp_dot import quantize_activations, quantize_weights
    xq = quantize_activations(x, p).dequantize().astype(jnp.float64 if False
                                                        else jnp.float32)
    wq = quantize_weights(w, p).dequantize()
    np.testing.assert_allclose(np.asarray(bfp_matmul_2d(x, w, p)),
                               np.asarray(xq) @ np.asarray(wq), rtol=1e-6)


def test_big_k_chunked_accumulation():
    """K beyond the int32-safe bound splits into exact chunks."""
    k = bfp.max_safe_k(8, 8) * 2 + 37
    x = jnp.ones((2, k)) * 0.5
    w = jnp.ones((k, 2)) * 0.5
    p = PAPER_DEFAULT.with_(straight_through=False)
    out = bfp_matmul_2d(x, w, p)
    ref = x @ w
    assert abs(float(out[0, 0] - ref[0, 0])) / float(ref[0, 0]) < 0.01


def test_ste_gradients():
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(10), (64, 8)) * 0.1

    def loss(w):
        return jnp.sum(bfp_dot(x, w, PAPER_DEFAULT) ** 2)

    g = jax.grad(loss)(w)
    assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g)))
    # STE grad should approximate the float grad
    gf = jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)
    cos = float(jnp.sum(g * gf) /
                (jnp.linalg.norm(g) * jnp.linalg.norm(gf)))
    assert cos > 0.99


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(bits=st.integers(3, 12), scale_pow=st.integers(-10, 10),
       seed=st.integers(0, 2 ** 31 - 1))
def test_quantize_dequantize_property(bits, scale_pow, seed):
    """Relative matrix error bounded by 2^-(L-2) regardless of scale."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (16, 64)) \
        * (2.0 ** scale_pow)
    b = bfp.quantize(x, bits, (1,))
    err = np.asarray(b.dequantize() - x)
    ref = np.abs(np.asarray(x)).max(axis=1)
    rel = np.abs(err).max(axis=1) / np.maximum(ref, 1e-30)
    assert rel.max() <= 2.0 ** -(bits - 2)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1))
def test_scale_invariance_property(seed):
    """BFP is scale-invariant across powers of two (shared exponent)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (8, 32))
    b1 = bfp.quantize(x, 8, (1,))
    b2 = bfp.quantize(x * 4.0, 8, (1,))
    np.testing.assert_array_equal(np.asarray(b1.mantissa),
                                  np.asarray(b2.mantissa))
    np.testing.assert_array_equal(np.asarray(b2.exponent),
                                  np.asarray(b1.exponent) + 2)
