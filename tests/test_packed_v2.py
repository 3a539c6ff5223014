"""Variable-width PackedBFP (v3 container) + precision search (ISSUE 10).

Adversarial property suite for the self-describing per-block width
header: lossless round trips across schemes x L 4-12 x odd shapes,
adversarial blocks (all-zero, single max-magnitude element, sign-only
mantissas, exponents at the int8 extremes), exact ``nbytes`` accounting,
and typed :class:`~repro.core.packed.IntegrityError` on width-header
corruption/truncation naming the byte offset.  Back-compat: hand-crafted
v1 bytes and fixed-L v2 containers restore bit-identically under the new
reader, and the ``bfp_packed_v2`` vgg16-reduced checkpoint serves logits
BIT-identical to the float path (extends the PR 5 pin in
tests/test_packed.py).  Plus the ``repro.tune.precision`` search
contract: determinism, per-site measured NSR within budget and fresh NSR
within the analytic bound, and a typed error on unsatisfiable budgets.

Generated sweeps (200+ cases per property) are ``@pytest.mark.slow``;
every point regression stays in the fast profile.
"""
import json
import os
import struct
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as EG
from repro.checkpoint import store
from repro.core import bfp, packed
from repro.core.bfp import BFPBlock, Scheme
from repro.core.policy import TPU_TILED
from repro.dist import compress
from repro.engine import PolicyMap
from repro.models.cnn import MODELS
from repro.serve.cnn import CnnServeEngine
from repro.tune.precision import PrecisionSearchError, search_precision

KEY = jax.random.PRNGKey(0)
POL = TPU_TILED.with_(block_k=None, straight_through=False)

#: ISSUE 10 acceptance: 200+ generated cases per property
N_EXAMPLES = 200


def _same_block(a: BFPBlock, b: BFPBlock) -> None:
    assert a.bits == b.bits
    assert a.mantissa.dtype == b.mantissa.dtype
    np.testing.assert_array_equal(np.asarray(a.mantissa),
                                  np.asarray(b.mantissa))
    np.testing.assert_array_equal(np.asarray(a.exponent),
                                  np.asarray(b.exponent))


def _width_plane_off(p: packed.PackedBFP) -> int:
    """Byte offset of the v3 width plane inside ``p.to_bytes()``."""
    meta_len = len(json.dumps(p.meta).encode())
    return (packed._FIXED_HEADER
            + 4 * (len(p.shape) + len(p.exp_shape))
            + meta_len + p.exponents.size)


# ---------------------------------------------------------------------------
# Adversarial blocks (fast)
# ---------------------------------------------------------------------------

def test_all_zero_leaf_packs_at_minimal_width():
    blk = bfp.quantize(jnp.zeros((4, 32)), 8, (1,))
    p = packed.pack_block(blk, variable=True)
    assert p.variable
    assert p.widths.shape == p.exp_shape
    assert int(p.widths.max()) == 1            # 1 bit/element, not 8
    assert len(p.payload) == -(-4 * 32 * 1 // 8)
    _same_block(blk, packed.unpack_block(
        packed.PackedBFP.from_bytes(p.to_bytes())))


def test_single_max_magnitude_element_widens_only_its_block():
    m = np.zeros((2, 16), np.int8)
    m[0, 3] = 127                              # one saturated element
    blk = BFPBlock(mantissa=jnp.asarray(m),
                   exponent=jnp.zeros((2, 1), jnp.int32), bits=8)
    p = packed.pack_block(blk, variable=True)
    assert p.widths.reshape(-1).tolist() == [8, 1]
    assert len(p.payload) == -(-(16 * 8 + 16 * 1) // 8)
    _same_block(blk, packed.unpack_block(
        packed.PackedBFP.from_bytes(p.to_bytes())))


def test_sign_only_mantissas_take_two_bits():
    m = np.array([[-1, 1, 0, -1], [1, 1, -1, 0]], np.int8)
    blk = BFPBlock(mantissa=jnp.asarray(m),
                   exponent=jnp.zeros((2, 1), jnp.int32), bits=8)
    p = packed.pack_block(blk, variable=True)
    assert int(p.widths.max()) == 2            # sign + 1 magnitude bit
    _same_block(blk, packed.unpack_block(
        packed.PackedBFP.from_bytes(p.to_bytes())))


def test_exponents_at_int8_extremes_roundtrip():
    m = np.array([[3, -7], [100, 1]], np.int8)
    blk = BFPBlock(mantissa=jnp.asarray(m),
                   exponent=jnp.asarray([[-128], [127]], jnp.int32), bits=8)
    p = packed.pack_block(blk, variable=True)
    q = packed.PackedBFP.from_bytes(p.to_bytes())
    assert q.exponents.reshape(-1).tolist() == [-128, 127]
    _same_block(blk, packed.unpack_block(q))
    # the prequant path hits the same extremes through its float32
    # power-of-two step sidecar (2^-134 is a subnormal f32; frexp on
    # float64 recovers the exponent exactly)
    s = np.ldexp(1.0, np.array([[-134], [121]])).astype(np.float32)
    d = {"m": jnp.asarray(m), "s": jnp.asarray(s)}
    pp = packed.pack_prequant(d, 8, variable=True)
    assert pp.exponents.reshape(-1).tolist() == [-128, 127]
    r = packed.unpack_prequant(packed.PackedBFP.from_bytes(pp.to_bytes()))
    assert r["m"].dtype == d["m"].dtype        # dtype follows container L
    np.testing.assert_array_equal(np.asarray(r["m"]), m)
    np.testing.assert_array_equal(np.asarray(r["s"]), s)


def test_nbytes_exactly_matches_byte_stream():
    for variable in (False, True):
        for shape, axes in (((3, 7), (1,)), ((5, 13), (0,)), ((1, 17), (1,))):
            blk = bfp.quantize(jax.random.normal(KEY, shape), 6, axes)
            p = packed.pack_block(blk, variable=variable)
            assert p.nbytes == len(p.to_bytes())
            q = packed.PackedBFP.from_bytes(p.to_bytes())
            assert q.nbytes == p.nbytes


# ---------------------------------------------------------------------------
# Width-header corruption / truncation -> typed IntegrityError (fast)
# ---------------------------------------------------------------------------

def _adversarial_container() -> packed.PackedBFP:
    m = np.zeros((2, 16), np.int8)
    m[0, 3] = 127                              # widths [8, 1]
    blk = BFPBlock(mantissa=jnp.asarray(m),
                   exponent=jnp.zeros((2, 1), jnp.int32), bits=8)
    return packed.pack_block(blk, variable=True)


def test_width_out_of_range_raises_integrity_error_naming_offset():
    p = _adversarial_container()
    off = _width_plane_off(p)
    for bad in (0, 200):                       # below 1 / above L=8
        buf = bytearray(p.to_bytes())
        buf[off + 1] = bad
        with pytest.raises(packed.IntegrityError,
                           match=rf"width plane corrupt: block 1 .*"
                                 rf"byte offset {off + 1}"):
            packed.PackedBFP.from_bytes(bytes(buf))


def test_width_plane_truncation_raises_integrity_error_naming_offset():
    p = _adversarial_container()
    off = _width_plane_off(p)
    with pytest.raises(packed.IntegrityError,
                       match=rf"width plane needs 2 bytes at offset {off}"):
        packed.PackedBFP.from_bytes(p.to_bytes()[:off + 1])


def test_bitstream_truncation_raises_integrity_error():
    p = _adversarial_container()
    with pytest.raises(packed.IntegrityError,
                       match="variable-width bitstream"):
        packed.PackedBFP.from_bytes(p.to_bytes()[:-1])


def test_in_range_width_corruption_caught():
    p = _adversarial_container()
    off = _width_plane_off(p)
    # widening a block's declared width starves the bitstream
    buf = bytearray(p.to_bytes())
    buf[off + 1] = 8
    with pytest.raises(packed.IntegrityError,
                       match="variable-width bitstream"):
        packed.PackedBFP.from_bytes(bytes(buf))
    # narrowing stays structurally plausible — the CRC catches it
    buf = bytearray(p.to_bytes())
    buf[off] = 1
    with pytest.raises(packed.IntegrityError, match="checksum mismatch"):
        packed.PackedBFP.from_bytes(bytes(buf))


def test_widths_validated_at_construction():
    p = _adversarial_container()
    with pytest.raises(ValueError, match="width plane shape"):
        packed.PackedBFP(bits=p.bits, shape=p.shape, exp_shape=p.exp_shape,
                         exponents=p.exponents, payload=p.payload,
                         meta=p.meta, widths=np.ones((3, 1), np.uint8))
    with pytest.raises(ValueError, match=r"outside the legal \[1, 8\]"):
        packed.PackedBFP(bits=p.bits, shape=p.shape, exp_shape=p.exp_shape,
                         exponents=p.exponents, payload=p.payload,
                         meta=p.meta, widths=np.full((2, 1), 9, np.uint8))


# ---------------------------------------------------------------------------
# Back-compat: v1 bytes and fixed-L v2 under the new reader (fast)
# ---------------------------------------------------------------------------

def _v1_bytes(p: packed.PackedBFP) -> bytes:
    """Hand-craft the pre-CRC v1 serialization of a fixed container (no
    v1 writer exists anymore — this is the archived layout)."""
    assert not p.variable
    meta_b = json.dumps(p.meta).encode()
    out = [b"BFPK", struct.pack("<BBBBI", 1, p.bits, len(p.shape),
                                len(p.exp_shape), len(meta_b))]
    for d in (*p.shape, *p.exp_shape):
        out.append(struct.pack("<I", d))
    out.append(meta_b)
    out.append(p.exponents.astype(np.int8).tobytes(order="C"))
    out.append(p.payload)
    return b"".join(out)


def test_v1_container_restores_bit_identically():
    blk = bfp.quantize(jax.random.normal(KEY, (6, 24)), 8, (1,))
    p = packed.pack_block(blk)
    q = packed.PackedBFP.from_bytes(_v1_bytes(p))
    assert q.stored_crc is None and not q.variable
    _same_block(blk, packed.unpack_block(q))


def test_fixed_width_data_still_writes_v2_bytes():
    # pre-existing fixed-L artifacts parse byte-identically because the
    # writer only emits version 3 when a width plane exists
    blk = bfp.quantize(jax.random.normal(KEY, (6, 24)), 8, (1,))
    buf = packed.pack_block(blk).to_bytes()
    assert buf[4] == packed._VERSION            # still version 2
    q = packed.PackedBFP.from_bytes(buf)
    assert not q.variable and q.widths is None
    _same_block(blk, packed.unpack_block(q))
    vbuf = packed.pack_block(blk, variable=True).to_bytes()
    assert vbuf[4] == packed._VERSION_VAR


# ---------------------------------------------------------------------------
# Checkpoint traffic (fast)
# ---------------------------------------------------------------------------

def test_mixed_fixed_and_variable_leaves_in_one_manifest():
    params = MODELS["lenet"].init(KEY)
    # pre-pack c1 as a FIXED container, then save the rest variable
    pre = PolicyMap.of(("^c1$", POL), default=None)
    tree = packed.pack_param_tree(params, pre, "cnn")
    rest = PolicyMap.of(("^c1$", None), default=POL)
    with tempfile.TemporaryDirectory() as d:
        store.save(d, 0, tree, format="bfp_packed_v2", policy=rest,
                   tree_kind="cnn")
        step_dir = os.path.join(d, "step_00000000")
        with open(os.path.join(step_dir, "manifest.json")) as f:
            man = json.load(f)
        assert man["format"] == "bfp_packed_v2"
        packed_dtypes = [man["dtypes"][i] for i in man["packed_leaves"]]
        assert "bfp_packed8" in packed_dtypes          # the fixed leaf
        assert "bfp_packed8v" in packed_dtypes         # variable leaves
        # both kinds restore to the exact sidecars a bind would produce
        got, step = store.restore(d, params)
    assert step == 0
    want = EG.prequantize_cnn(params, POL)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vgg16_reduced_v2_checkpoint_serves_bit_identical():
    """Extends the PR 5 pin: the VARIABLE-WIDTH checkpoint restores to
    the same sidecars, so served logits stay BIT-identical to the
    float-checkpoint path."""
    spec = MODELS["vgg16"]
    params = spec.init(KEY)
    img = jax.random.normal(jax.random.PRNGKey(1), spec.input_shape())
    with tempfile.TemporaryDirectory() as d:
        store.save(os.path.join(d, "f32"), 0, params)
        store.save(os.path.join(d, "var"), 0, params,
                   format="bfp_packed_v2", policy=POL, tree_kind="cnn")
        with open(os.path.join(d, "var", "step_00000000",
                               "manifest.json")) as f:
            assert json.load(f)["format"] == "bfp_packed_v2"
        p_f, _ = store.restore(os.path.join(d, "f32"), params)
        p_q, _ = store.restore(os.path.join(d, "var"), params)
    eng_f = CnnServeEngine(p_f, spec.apply, POL, slots=2, jit=False)
    eng_q = CnnServeEngine(p_q, spec.apply, POL, slots=2, jit=False)
    r_f = eng_f.submit(image=img)
    r_q = eng_q.submit(image=img)
    eng_f.run()
    eng_q.run()
    np.testing.assert_array_equal(r_f.logits, r_q.logits)


# ---------------------------------------------------------------------------
# Wire traffic (fast)
# ---------------------------------------------------------------------------

def test_wire_variable_container_roundtrips_crc_verified():
    g = jax.random.normal(KEY, (33, 7))
    p = compress.pack_leaf(g, 8, block=16, variable=True)
    assert p.variable
    want = compress.unpack_leaf(compress.pack_leaf(g, 8, block=16))
    got = compress.unpack_leaf(p.to_bytes())   # parse + CRC verify path
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    buf = bytearray(p.to_bytes())
    buf[-1] ^= 0xFF
    with pytest.raises(packed.IntegrityError):
        compress.unpack_leaf(bytes(buf))


def test_packed_allreduce_variable_matches_fixed():
    # same quantize -> mean path, so the reduced mean and residual are
    # identical; only the wire accounting (honest bytes) may differ
    grads = {"w": jax.random.normal(KEY, (4, 16, 8)),
             "b": jax.random.normal(jax.random.PRNGKey(2), (4, 8))}
    zeros = jax.tree_util.tree_map(jnp.zeros_like, grads)
    m_f, r_f, by_f = compress.packed_allreduce(grads, zeros, bits=8,
                                               block=16)
    m_v, r_v, by_v = compress.packed_allreduce(grads, zeros, bits=8,
                                               block=16, variable=True)
    for a, b in zip(jax.tree_util.tree_leaves((m_f, r_f)),
                    jax.tree_util.tree_leaves((m_v, r_v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert by_v > 0


def test_wire_report_variable_counts_real_bytes():
    tree = {"w": jax.random.normal(KEY, (256, 64))}
    rep_f = compress.wire_report(tree, bits=8, block=512)
    rep_v = compress.wire_report(tree, bits=8, block=512, variable=True)
    # dense Gaussian blocks saturate, so variable pays only the width
    # plane on top (one byte per block) — never more
    n_blocks = 256 * 64 // 512
    assert rep_f["wire_bytes"] < rep_v["wire_bytes"] \
        <= rep_f["wire_bytes"] + n_blocks


# ---------------------------------------------------------------------------
# Precision search (fast)
# ---------------------------------------------------------------------------

def test_precision_search_meets_budget_and_analytic_bounds():
    res = search_precision("lenet", seed=0, batch=4, nsr_budget=5e-3,
                           top1_tol=0.0)
    assert res.sites
    for s in res.sites:
        assert res.l_min <= s.l_w <= res.l_max
        assert s.nsr_measured <= res.nsr_budget
        assert s.nsr_fresh <= s.nsr_bound
        # the emitted map resolves each site to its chosen width
        assert res.policy_map.resolve(s.path).l_w == s.l_w
    assert res.top1_agreement >= 1.0 - res.top1_tol
    # the report round-trips through plain data (the --policy-out file)
    assert PolicyMap.from_dict(res.policy_map.to_dict()) == res.policy_map
    assert json.loads(json.dumps(res.to_dict())) == res.to_dict()


def test_precision_search_deterministic():
    a = search_precision("lenet", seed=0, batch=4, nsr_budget=5e-3)
    b = search_precision("lenet", seed=0, batch=4, nsr_budget=5e-3)
    assert a.assignment == b.assignment
    assert a.policy_map == b.policy_map
    assert a.to_dict() == b.to_dict()


def test_precision_search_unsatisfiable_budget_raises_typed_error():
    with pytest.raises(PrecisionSearchError, match="unsatisfiable"):
        search_precision("lenet", seed=0, batch=2, nsr_budget=0.0)


def test_precision_search_rejects_bad_arguments():
    with pytest.raises(ValueError, match="unknown model"):
        search_precision("nope")
    with pytest.raises(ValueError, match="l_min"):
        search_precision("lenet", l_min=9, l_max=8)
    with pytest.raises(ValueError, match="nsr_budget"):
        search_precision("lenet", nsr_budget=-1.0)


# ---------------------------------------------------------------------------
# Fast-profile collection guard (satellite: CI smoke)
# ---------------------------------------------------------------------------

def test_fast_profile_collects_this_suite():
    """CI's pack-smoke job runs ``-m "not slow"`` on this file; a stray
    module-level slow mark would silently drop every regression above
    (pytest would exit 5 on empty collection — this guards the intent
    in-suite too)."""
    import sys
    mod = sys.modules[__name__]
    marks = getattr(mod, "pytestmark", [])
    marks = marks if isinstance(marks, list) else [marks]
    assert not any(getattr(m, "name", "") == "slow" for m in marks)


# ---------------------------------------------------------------------------
# Generated sweeps (slow profile): 200+ cases per property
# ---------------------------------------------------------------------------

_SHAPES = ((3, 7), (5, 13), (1, 17), (16, 16), (7, 1), (2, 63), (31, 2))
_SCHEMES = (Scheme.EQ2, Scheme.EQ3, Scheme.EQ4, Scheme.EQ5, Scheme.TILED)


@pytest.mark.slow
@settings(max_examples=N_EXAMPLES, deadline=None)
@given(bits=st.integers(4, 12), si=st.integers(0, len(_SHAPES) - 1),
       ci=st.integers(0, len(_SCHEMES) - 1), seed=st.integers(0, 10_000),
       operand=st.sampled_from(["w", "i"]))
def test_variable_roundtrip_lossless_across_schemes(bits, si, ci, seed,
                                                    operand):
    w = jax.random.normal(jax.random.PRNGKey(seed), _SHAPES[si])
    blk = bfp.bfp_quantize_matrix(w, bits, operand, _SCHEMES[ci])
    p = packed.pack_block(blk, variable=True)
    buf = p.to_bytes()
    assert p.nbytes == len(buf)
    q = packed.PackedBFP.from_bytes(buf)
    assert q.nbytes == len(buf)
    _same_block(blk, packed.unpack_block(q))


@pytest.mark.slow
@settings(max_examples=N_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 10_000), bits=st.integers(4, 12),
       tenths=st.integers(0, 10))
def test_variable_bytes_bounded_and_sparsity_shrinks(seed, bits, tenths):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((24, 32)).astype(np.float32)
    w[rng.random((24, 32)) < tenths / 10] = 0.0
    blk = bfp.quantize(jnp.asarray(w), bits, (1,))
    pf = packed.pack_block(blk)
    pv = packed.pack_block(blk, variable=True)
    # widths never exceed L, so the only possible overhead is the width
    # plane itself (one byte per block)
    assert len(pv.payload) <= len(pf.payload)
    assert pv.nbytes <= pf.nbytes + pv.exponents.size
    if tenths == 10:
        assert int(pv.widths.max()) == 1
    _same_block(packed.unpack_block(pf), packed.unpack_block(pv))


@pytest.mark.slow
@settings(max_examples=N_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 10_000), cut=st.integers(0, 1 << 30))
def test_any_truncation_raises(seed, cut):
    w = jax.random.normal(jax.random.PRNGKey(seed), (6, 24))
    p = packed.pack_matrix(w, 8, "w", Scheme.EQ2, variable=True)
    buf = p.to_bytes()
    k = 1 + cut % (len(buf) - 1)               # any strict prefix
    with pytest.raises(ValueError):            # IntegrityError included
        packed.PackedBFP.from_bytes(buf[:k])


@pytest.mark.slow
@settings(max_examples=N_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 10_000), pos=st.integers(0, 1 << 30),
       flip=st.integers(1, 255))
def test_any_plane_or_payload_corruption_raises_integrity_error(seed, pos,
                                                                flip):
    w = jax.random.normal(jax.random.PRNGKey(seed), (6, 24))
    p = packed.pack_matrix(w, 8, "w", Scheme.EQ2, variable=True)
    buf = bytearray(p.to_bytes())
    start = _width_plane_off(p) - p.exponents.size  # exponent plane on
    idx = start + pos % (len(buf) - start)
    buf[idx] ^= flip
    with pytest.raises(packed.IntegrityError):
        packed.PackedBFP.from_bytes(bytes(buf))
