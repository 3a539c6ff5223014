"""Validation of the paper's 3-stage NSR model (§4) against measurement.

The paper's own bar is <= 8.9 dB worst-case deviation on VGG-16 (Table 4);
since our theory and code share the exact quantization convention, we
assert much tighter bounds on synthetic data.
"""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import nsr
from repro.core.policy import BFPPolicy


def _acts(key, shape, spread=1.0):
    k1, k2 = jax.random.split(key)
    return jax.random.normal(k1, shape) * \
        jnp.exp(spread * jax.random.normal(k2, shape))


def test_quantization_snr_prediction():
    """Stage 1 (eq. 8-13): predicted matrix SNR tracks measurement.

    At low bit widths on heavy-tailed data the step^2/12 model
    overestimates noise (elements far below the step quantize to zero with
    error = the element itself, variance << step^2/12), so measurement
    beats prediction by a couple of dB — well inside the paper's 8.9 dB
    Table-4 envelope.  >= 8 bits must agree within 1 dB.
    """
    for bits in (6, 8, 10):
        for op in ("i", "w"):
            x = _acts(jax.random.PRNGKey(bits), (256, 256))
            p = BFPPolicy(l_w=bits, l_i=bits)
            pred = float(nsr.predict_matrix_snr(x, bits, op, p))
            meas = float(nsr.measure_matrix_snr(x, bits, op, p))
            tol = 3.0 if bits <= 6 else 1.0
            assert abs(pred - meas) < tol, (bits, op, pred, meas)


def test_snr_scales_6db_per_bit():
    """Each extra mantissa bit adds ~6.02 dB SNR (eq. 8)."""
    x = _acts(jax.random.PRNGKey(0), (512, 128))
    p = BFPPolicy()
    snrs = [float(nsr.predict_matrix_snr(x, b, "i", p)) for b in (6, 7, 8)]
    d1, d2 = snrs[1] - snrs[0], snrs[2] - snrs[1]
    assert 5.5 < d1 < 6.5 and 5.5 < d2 < 6.5


def test_single_layer_model():
    """Stage 2 (eq. 18): eta_O = eta_I + eta_W within 1.5 dB."""
    x = _acts(jax.random.PRNGKey(1), (512, 384))
    w = jax.random.normal(jax.random.PRNGKey(2), (384, 256)) * 0.05
    p = BFPPolicy(straight_through=False)
    reps = nsr.analyze_gemm_chain(x, [w], p)
    r = reps[0]
    assert abs(r.snr_output_measured - r.snr_output_single) < 1.5


def test_multi_layer_model_tracks_chain():
    """Stage 3 (eq. 19-20): multi-layer prediction tracks a 6-deep chain
    within 3 dB, and beats the single-layer model in later layers."""
    x = _acts(jax.random.PRNGKey(3), (256, 256))
    ws = [jax.random.normal(jax.random.PRNGKey(10 + i), (256, 256)) * 0.08
          for i in range(6)]
    reps = nsr.analyze_gemm_chain(x, ws, BFPPolicy(straight_through=False))
    for r in reps:
        assert abs(r.snr_output_measured - r.snr_output_multi) < 3.0, r
    last = reps[-1]
    err_multi = abs(last.snr_output_measured - last.snr_output_multi)
    err_single = abs(last.snr_output_measured - last.snr_output_single)
    assert err_multi <= err_single + 0.5


def test_multi_layer_within_paper_envelope():
    """Paper's own bar at its headline config (8-bit): <= 8.9 dB deviation
    through a deep chain (Table 4 reports up to 8.9 dB on VGG-16)."""
    x = _acts(jax.random.PRNGKey(4), (128, 128), spread=1.0)
    ws = [jax.random.normal(jax.random.PRNGKey(20 + i), (128, 128)) * 0.1
          for i in range(8)]
    reps = nsr.analyze_gemm_chain(x, ws, BFPPolicy(l_w=8, l_i=8,
                                                   straight_through=False))
    for r in reps:
        assert abs(r.snr_output_measured - r.snr_output_multi) < 8.9


def test_relu_snr_neutral():
    """Paper §4.4: ReLU leaves SNR approximately unchanged."""
    y = _acts(jax.random.PRNGKey(5), (512, 512))
    noise = 0.01 * jax.random.normal(jax.random.PRNGKey(6), y.shape)
    before = float(nsr.snr_db(y, y + noise))
    after = float(nsr.snr_db(jax.nn.relu(y), jax.nn.relu(y + noise)))
    assert abs(before - after) < 1.5


def test_nsr_snr_roundtrip():
    s = jnp.asarray(23.4)
    assert abs(float(nsr.snr_db_from_nsr(nsr.nsr_from_snr_db(s))) - 23.4) \
        < 1e-4


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(bits=st.integers(5, 10), seed=st.integers(0, 2 ** 31 - 1))
def test_eta_additivity_property(bits, seed):
    """eta_O ~= eta_I + eta_W across random bit-widths/data (eq. 16)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _acts(k1, (256, 128))
    w = jax.random.normal(k2, (128, 64)) * 0.1
    p = BFPPolicy(l_w=bits, l_i=bits, straight_through=False)
    r = nsr.analyze_gemm_chain(x, [w], p)[0]
    eta_meas = 10 ** (-r.snr_output_measured / 10)
    eta_pred = 10 ** (-r.snr_output_single / 10)
    assert 0.15 < eta_meas / eta_pred < 6.0  # order-of-magnitude check
