"""Hypothesis property suite: core BFP invariants + the paper's NSR bound.

Replaces ad-hoc point checks with generated cases (ISSUE 4): every
property runs 200+ examples.

Invariants pinned here are exactly what the CNN serving stack relies on:

  * the shared block exponent IS the block max exponent (paper eq. 1);
  * mantissas saturate at +/-(2^(L-1) - 1) — and the block max actually
    uses the top half of the mantissa range;
  * requantization is idempotent (serving may re-format formatted data:
    prequant weights, cached activations — no drift allowed);
  * all-zero blocks round-trip exactly;
  * measured NSR never exceeds the analytic worst-case bounds from
    ``core.nsr`` (matrix formatting AND full GEMMs), across mantissa
    widths 4-12, block sizes, schemes, and input scales.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bfp, nsr, packed, prequant
from repro.core.bfp import Rounding, Scheme
from repro.core.bfp_dot import bfp_matmul_2d

# every test here is a generated-example sweep: the whole module is
# the slow profile (deselect with -m 'not slow' for quick iteration)
pytestmark = pytest.mark.slow
from repro.core.policy import BFPPolicy

#: ISSUE 4 acceptance: 200+ generated cases per property
N_EXAMPLES = 200

SEEDS = st.integers(0, 2 ** 31 - 1)
BITS = st.integers(4, 12)
SCALE_POWS = st.integers(-12, 12)


def _block(seed: int, rows: int, cols: int, scale_pow: int) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, cols)) * \
        (2.0 ** scale_pow)


def test_pow2_exact_everywhere():
    """The scale primitive is EXACTLY 2^e for every representable float32
    exponent, denormals included — ``jnp.exp2`` is not (1 ulp off at many
    negative integer exponents), which the idempotence property below
    caught breaking TRUNCATE requantization."""
    e = np.arange(-160, 140)
    got = np.asarray(bfp.pow2(jnp.asarray(e)))
    with np.errstate(over="ignore"):     # e > 127 overflows to inf — wanted
        want = np.exp2(e.astype(np.float64)).astype(np.float32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# BFP formatting invariants
# ---------------------------------------------------------------------------

@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       cols=st.sampled_from([1, 3, 8, 33, 64]))
def test_shared_exponent_is_block_max_exponent(bits, scale_pow, seed, cols):
    """eps = max_i floor(log2 |x_i|) over the block (paper eq. 1)."""
    x = _block(seed, 8, cols, scale_pow)
    b = bfp.quantize(x, bits, (1,))
    amax = np.abs(np.asarray(x)).max(axis=1)
    _, e = np.frexp(amax)                      # amax = f * 2^e, f in [.5, 1)
    np.testing.assert_array_equal(np.asarray(b.exponent).reshape(-1),
                                  (e - 1).astype(np.int32))


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       rounding=st.sampled_from([Rounding.ROUND, Rounding.TRUNCATE]))
def test_mantissas_saturate_at_limit(bits, scale_pow, seed, rounding):
    """|m| <= 2^(L-1)-1 always, and the block max lands in the top half
    of the mantissa range [2^(L-2), 2^(L-1)-1] — the format wastes no
    headroom on the element that defines the exponent."""
    x = _block(seed, 4, 32, scale_pow)
    b = bfp.quantize(x, bits, (1,), rounding)
    lim = 2 ** (bits - 1) - 1
    m = np.abs(np.asarray(b.mantissa, dtype=np.int64))
    assert m.max() <= lim
    # per block, the max element's mantissa uses the top half
    assert (m.max(axis=1) >= 2 ** (bits - 2)).all()


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=st.integers(-8, 8), seed=SEEDS)
def test_mantissa_clipping_hits_limit_exactly(bits, scale_pow, seed):
    """An element just under the next power of two rounds past the top
    mantissa and must CLIP to exactly +/-(2^(L-1)-1), not wrap."""
    x = np.array(_block(seed, 1, 16, scale_pow), dtype=np.float32)
    _, e = np.frexp(np.abs(x).max())
    eps = int(e) - 1                    # the block exponent
    x[0, 0] = (2.0 - 2.0 ** -12) * 2.0 ** eps    # 1.111...b * 2^eps
    x[0, 1] = -x[0, 0]                  # eps unchanged: |x00| < 2^(eps+1)
    b = bfp.quantize(jnp.asarray(x), bits, (1,))
    lim = 2 ** (bits - 1) - 1
    m = np.asarray(b.mantissa, dtype=np.int64)
    assert m[0, 0] == lim and m[0, 1] == -lim


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       rounding=st.sampled_from([Rounding.ROUND, Rounding.TRUNCATE]))
def test_requantization_idempotent(bits, scale_pow, seed, rounding):
    """quantize(dequantize(quantize(x))) == quantize(x) bit-for-bit:
    already-formatted data (prequant weights, requantized activations)
    never drifts through a second pass."""
    x = _block(seed, 4, 32, scale_pow)
    b1 = bfp.quantize(x, bits, (1,), rounding)
    x1 = b1.dequantize()
    b2 = bfp.quantize(x1, bits, (1,), rounding)
    np.testing.assert_array_equal(np.asarray(b1.mantissa),
                                  np.asarray(b2.mantissa))
    np.testing.assert_array_equal(np.asarray(b1.exponent),
                                  np.asarray(b2.exponent))
    np.testing.assert_array_equal(np.asarray(x1),
                                  np.asarray(b2.dequantize()))


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       zero_row=st.integers(0, 3))
def test_all_zero_blocks_round_trip_exactly(bits, scale_pow, seed,
                                            zero_row):
    """A zero block among live blocks dequantizes to EXACT zeros (no
    denormal junk from the sentinel exponent), and its mantissas are 0."""
    x = np.array(_block(seed, 4, 16, scale_pow), dtype=np.float32)
    x[zero_row] = 0.0
    b = bfp.quantize(jnp.asarray(x), bits, (1,))
    m = np.asarray(b.mantissa)
    deq = np.asarray(b.dequantize())
    assert (m[zero_row] == 0).all()
    assert (deq[zero_row] == 0.0).all()
    # and the all-zero matrix round-trips exactly too
    bz = bfp.quantize(jnp.zeros((2, 8)), bits, (0, 1))
    assert (np.asarray(bz.dequantize()) == 0.0).all()


# ---------------------------------------------------------------------------
# The paper's NSR upper bound (core.nsr) — measurement never exceeds it
# ---------------------------------------------------------------------------

@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       operand=st.sampled_from(["i", "w"]),
       block_k=st.sampled_from([8, 16, 32, None]))
def test_matrix_nsr_never_exceeds_bound(bits, scale_pow, seed, operand,
                                        block_k):
    """Measured formatting NSR <= the hard per-block bound n*2^(-2(L-2)),
    for the paper scheme and TILED at several block sizes."""
    # the contraction axis (axis 0 for "w" weights, axis 1 for "i"
    # activations) must be divisible by every TILED block size
    x = _block(seed, 64, 48, scale_pow) if operand == "w" \
        else _block(seed, 12, 64, scale_pow)
    scheme = Scheme.EQ4 if block_k is None else Scheme.TILED
    pol = BFPPolicy(l_w=bits, l_i=bits, scheme=scheme, block_k=block_k,
                    straight_through=False)
    snr = float(nsr.measure_matrix_snr(x, bits, operand, pol))
    eta = 10.0 ** (-snr / 10.0)
    _, elems = nsr._block_sizes_and_exps(x, bits, operand, pol)
    assert eta <= nsr.matrix_nsr_upper_bound(elems, bits) * (1 + 1e-4), \
        (eta, elems, bits)


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=st.integers(-8, 8), seed=SEEDS,
       block_k=st.sampled_from([8, 16, 32, None]),
       w_scale_pow=st.integers(-6, 2))
def test_gemm_nsr_never_exceeds_bound(bits, scale_pow, seed, block_k,
                                      w_scale_pow):
    """ISSUE 4 acceptance: measured NSR of random GEMMs never exceeds the
    analytic bound from core/nsr.py, across mantissa widths 4-12, block
    sizes, and input scales."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (12, 64)) * (2.0 ** scale_pow)
    w = jax.random.normal(k2, (64, 16)) * (2.0 ** w_scale_pow)
    scheme = Scheme.EQ4 if block_k is None else Scheme.TILED
    pol = BFPPolicy(l_w=bits, l_i=bits, scheme=scheme, block_k=block_k,
                    straight_through=False)
    y_f = x @ w
    y_q = bfp_matmul_2d(x, w, pol)
    eta = float(jnp.sum(jnp.square(y_q - y_f)) /
                jnp.maximum(jnp.sum(jnp.square(y_f)),
                            jnp.finfo(jnp.float32).tiny))
    bound = float(nsr.gemm_nsr_upper_bound(x, w, pol))
    assert eta <= bound * (1 + 1e-3), (eta, bound, bits, block_k)


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=st.integers(4, 11), seed=SEEDS)
def test_gemm_bound_tightens_with_bits(bits, seed):
    """The bound is guidance, not vacuous: one more mantissa bit cuts it
    4x (6 dB/bit, the paper's design trade-off), tracking the format."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (8, 32))
    w = jax.random.normal(k2, (32, 8)) * 0.1
    pol = BFPPolicy(l_w=bits, l_i=bits, straight_through=False)
    b1 = float(nsr.gemm_nsr_upper_bound(x, w, pol))
    b2 = float(nsr.gemm_nsr_upper_bound(
        x, w, pol.with_(l_w=bits + 1, l_i=bits + 1)))
    assert b2 < b1
    assert b1 / b2 > 2.0     # ~4x in the small-error regime


# ---------------------------------------------------------------------------
# Packed BFP container (ISSUE 5): serialize -> bytes -> deserialize is
# bit-exact for every scheme x mantissa width x odd geometry
# ---------------------------------------------------------------------------

@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       scheme=st.sampled_from([Scheme.EQ2, Scheme.EQ3, Scheme.EQ4,
                               Scheme.EQ5, Scheme.TILED]),
       operand=st.sampled_from(["w", "i"]),
       rows=st.sampled_from([1, 3, 7, 8, 16]),
       cols=st.sampled_from([1, 4, 12, 33, 64]))
def test_packed_container_round_trip_bit_exact(bits, scale_pow, seed,
                                               scheme, operand, rows, cols):
    """pack -> to_bytes -> from_bytes -> unpack reproduces the EXACT
    BFPBlock (integer mantissas, integer exponents, identical dequant)
    for every scheme, mantissa width 4-12, and odd shapes whose bit
    count does not land on a byte boundary.  The payload is exactly
    ceil(n*L/8) bytes — a 6-bit mantissa really takes 6 bits."""
    x = _block(seed, rows, cols, scale_pow)
    k = x.shape[1] if operand == "w" else x.shape[0]
    block_k = (k if k % 4 else 4) if scheme is Scheme.TILED else None
    blk = bfp.bfp_quantize_matrix(x, bits, operand, scheme, block_k)
    p = packed.pack_block(blk, scheme=scheme.value, operand=operand)
    assert len(p.payload) == -(-x.size * bits // 8)
    assert p.nbytes == len(p.to_bytes())
    p2 = packed.PackedBFP.from_bytes(p.to_bytes())
    assert p2.bits == bits and p2.shape == tuple(x.shape)
    assert p2.meta["scheme"] == scheme.value
    b2 = packed.unpack_block(p2)
    np.testing.assert_array_equal(np.asarray(blk.mantissa),
                                  np.asarray(b2.mantissa))
    np.testing.assert_array_equal(np.asarray(blk.exponent),
                                  np.asarray(b2.exponent))
    np.testing.assert_array_equal(np.asarray(blk.dequantize()),
                                  np.asarray(b2.dequantize()))


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=BITS, scale_pow=SCALE_POWS, seed=SEEDS,
       k=st.sampled_from([4, 6, 12, 16]),
       n=st.sampled_from([1, 5, 10, 33]),
       block_k=st.sampled_from([1, 2, None]))
def test_packed_prequant_round_trip_bit_exact(bits, scale_pow, seed, k, n,
                                              block_k):
    """The prequant {"m", "s"} sidecar survives the packed container
    bit-exactly: integer mantissas AND the float32 power-of-two step
    sidecar (recovered from int8 block exponents) are identical, so a
    packed checkpoint restore is indistinguishable from binding the
    float tree."""
    w = _block(seed, k, n, scale_pow)
    pol = BFPPolicy(l_w=bits, scheme=Scheme.TILED, block_k=block_k,
                    straight_through=False)
    d = prequant.prequant_leaf(w, pol)
    assert prequant.is_prequant(d)
    p = packed.PackedBFP.from_bytes(
        packed.pack_prequant(d, pol.l_w).to_bytes())
    d2 = packed.unpack_prequant(p)
    np.testing.assert_array_equal(np.asarray(d["m"]), np.asarray(d2["m"]))
    np.testing.assert_array_equal(np.asarray(d["s"]), np.asarray(d2["s"]))
    np.testing.assert_array_equal(
        np.asarray(prequant.dequantize_prequant(d)),
        np.asarray(packed.unpack_dequant(p)))
