"""Fused implicit-im2col conv: kernel vs oracle vs im2col+GEMM (ISSUE 2).

Triangulation contract:
  * kernel == independent oracle (ref.bfp_conv2d_ref) over a
    stride x padding x odd-spatial grid;
  * kernel == materialized im2col + the fused GEMM kernel, BIT-identical
    (same TILED blocks, same K zero-padding, same fp32 accumulation
    order);
  * prequant (int8 HWIO mantissa + sidecar) == inline quantization,
    bit-identical, through both the raw ops and engine.conv2d;
  * engine.conv2d falls back honestly (paper schemes -> emulated im2col
    route) and resolves PolicyMap layer paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as EG
from repro.core import BFPPolicy, Scheme
from repro.core.conv_utils import conv_weight_matrix, im2col
from repro.core.prequant import prequant_conv_leaf
from repro.engine import PolicyMap
from repro.kernels import ops, ref
from repro.kernels.bfp_conv import bfp_conv2d_pallas
from repro.models.cnn import small

KEY = jax.random.PRNGKey(0)
EQ4 = BFPPolicy(straight_through=False)


def _case(h, w, c, oc, kh, kw, seed=0, xs=2.0):
    kx, kw_ = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (2, h, w, c)) * xs
    wk = jax.random.normal(kw_, (kh, kw, c, oc)) * 0.1
    return x, wk


def _tiled(bk, backend=None):
    return BFPPolicy(scheme=Scheme.TILED, block_k=bk,
                     straight_through=False, backend=backend)


# ---------------------------------------------------------------------------
# kernel vs oracle: stride x padding x odd-spatial grid (ISSUE 2 satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("h,w", [(8, 8), (7, 9)])
def test_conv_kernel_matches_oracle(stride, padding, h, w):
    x, wk = _case(h, w, 8, 10, 3, 3, seed=h * 10 + stride)
    pol = _tiled(24)          # 24 | 72 = kh*kw*C: no K padding
    out = ops.bfp_conv2d(x, wk, pol, stride, padding, interpret=True)
    out_r = ref.bfp_conv2d_ref(x, wk, 8, 8, 24, stride, padding)
    assert out.shape == out_r.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kh,kw,bk", [(1, 1, 8), (5, 5, 32), (3, 3, 128)])
def test_conv_kernel_kernel_sizes_and_ragged_k(kh, kw, bk):
    """1x1 / 5x5 kernels and a block_k that does NOT divide K (the last
    block zero-pads, exactly like ops.bfp_matmul)."""
    x, wk = _case(9, 7, 6, 5, kh, kw, seed=kh)
    pol = _tiled(bk)
    out = ops.bfp_conv2d(x, wk, pol, 1, "SAME", interpret=True)
    out_r = ref.bfp_conv2d_ref(x, wk, 8, 8, bk, 1, "SAME")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID"), (2, "VALID")])
def test_fused_bitidentical_to_im2col_gemm(stride, padding):
    """ISSUE 2 acceptance: fused conv == im2col + bfp_matmul_pallas,
    bit for bit (TILED, matching block_k, incl. K/OC padding paths)."""
    x, wk = _case(8, 10, 16, 24, 3, 3, seed=stride * 7)
    pol = _tiled(128)         # K=144 -> pads to 256: partial-block path
    out_f = ops.bfp_conv2d(x, wk, pol, stride, padding, interpret=True)
    cols, (b, oh, ow) = im2col(x, 3, 3, stride, padding)
    out_g = ops.bfp_matmul(cols, conv_weight_matrix(wk), pol,
                           interpret=True).reshape(b, oh, ow, 24)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_g))


def test_conv_kernel_accuracy_vs_float():
    """BFP-8 fused conv stays within ~2% of the float conv."""
    x, wk = _case(8, 8, 16, 16, 3, 3, seed=3, xs=1.0)
    out = ops.bfp_conv2d(x, wk, _tiled(16), 1, "SAME", interpret=True)
    ref_f = jax.lax.conv_general_dilated(
        x, wk, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    rel = float(jnp.linalg.norm(out - ref_f) / jnp.linalg.norm(ref_f))
    assert rel < 0.02, rel


def test_conv_kernel_overflow_guard():
    x, wk = _case(4, 4, 4, 4, 3, 3)
    pol = BFPPolicy(l_w=15, l_i=15, scheme=Scheme.TILED, block_k=36,
                    straight_through=False)
    with pytest.raises(ValueError, match="overflow"):
        ops.bfp_conv2d(x, wk, pol, 1, "SAME", interpret=True)


# ---------------------------------------------------------------------------
# prequant: bit-exact vs inline on the fused path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "VALID")])
def test_prequant_fused_bitexact_inline(stride, padding):
    x, wk = _case(8, 9, 8, 10, 3, 3, seed=11)
    pol = _tiled(24)
    pq = prequant_conv_leaf(wk, pol)
    assert EG.is_prequant(pq) and pq["m"].shape == wk.shape
    out_pq = ops.bfp_conv2d_prequant(x, pq["m"], pq["s"], pol, stride,
                                     padding, interpret=True)
    out_in = ops.bfp_conv2d(x, wk, pol, stride, padding, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pq), np.asarray(out_in))


def test_prequant_block_mismatch_rejected():
    x, wk = _case(6, 6, 8, 8, 3, 3)
    pq = prequant_conv_leaf(wk, _tiled(24))
    with pytest.raises(ValueError, match="block"):
        ops.bfp_conv2d_prequant(x, pq["m"], pq["s"], _tiled(36), 1, "SAME",
                                interpret=True)


# ---------------------------------------------------------------------------
# engine.conv2d: dispatch, fallback honesty, PolicyMap paths
# ---------------------------------------------------------------------------

def test_engine_conv2d_pallas_equals_emulated_im2col():
    """The fused kernel and the emulated im2col route implement the same
    TILED math: engine.conv2d(backend=pallas) == engine.conv2d(emulated)."""
    x, wk = _case(8, 8, 8, 12, 3, 3, seed=5)
    out_pl = EG.conv2d(x, wk, _tiled(24, backend="pallas"))
    out_em = EG.conv2d(x, wk, _tiled(24))
    np.testing.assert_array_equal(np.asarray(out_pl), np.asarray(out_em))


def test_engine_conv2d_fallback_on_paper_scheme():
    """pallas + a paper scheme must NOT silently run TILED math: it
    falls back to the emulated im2col route."""
    x, wk = _case(7, 7, 4, 6, 3, 3, seed=6)
    out = EG.conv2d(x, wk, EQ4.with_(backend="pallas"))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(EG.conv2d(x, wk, EQ4)))


def test_engine_conv2d_float_matches_lax_conv():
    x, wk = _case(8, 8, 3, 5, 3, 3, seed=7, xs=1.0)
    out = EG.conv2d(x, wk, None, stride=2, padding="SAME")
    ref_f = jax.lax.conv_general_dilated(
        x, wk, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_f),
                               rtol=2e-4, atol=2e-4)


def test_engine_conv2d_policy_map_paths():
    """PolicyMap rules resolve on conv layer paths exactly as for GEMMs."""
    x, wk = _case(6, 6, 4, 6, 3, 3, seed=8)
    pm = PolicyMap.of(("^stem$", None), default=_tiled(12))
    np.testing.assert_array_equal(
        np.asarray(EG.conv2d(x, wk, pm, path="stem")),
        np.asarray(EG.conv2d(x, wk, None)))
    np.testing.assert_array_equal(
        np.asarray(EG.conv2d(x, wk, pm, path="blocks/0/c1")),
        np.asarray(EG.conv2d(x, wk, _tiled(12))))


def test_model_forward_pallas_fused_equals_emulated():
    """Whole-model check: LeNet forward on the fused conv path ==
    emulated backend, bit for bit (convs fused, dense on the GEMM
    kernel), including the prequantize_cnn wire format."""
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    # conv K's (c1: 25, c2: 400) are block_k=5 multiples; the dense K's
    # are not, so the map scopes TILED to the convs (fc layers float) —
    # the emulated route requires block_k | K, and a faithful comparison
    # must execute the SAME math on both backends.
    pm_pl = PolicyMap.of(("^fc", None), default=_tiled(5, backend="pallas"))
    pm_em = PolicyMap.of(("^fc", None), default=_tiled(5))
    out_pl = small.lenet_apply(params, x, pm_pl)
    out_em = small.lenet_apply(params, x, pm_em)
    np.testing.assert_array_equal(np.asarray(out_pl), np.asarray(out_em))

    pq = EG.prequantize_cnn(params, pm_pl)
    assert EG.is_prequant(pq["c1"]["w"])
    assert not EG.is_prequant(pq["fc1"]["w"])
    out_pq = small.lenet_apply(pq, x, pm_pl)
    np.testing.assert_array_equal(np.asarray(out_pq), np.asarray(out_pl))


def test_aligned_tile_shared_floor():
    """ops.bfp_quantize rides the same aligned floor as default_tiles
    (ISSUE 2 satellite: one helper, one rationale)."""
    assert ops.aligned_tile(1) == 8
    assert ops.aligned_tile(100) == 128
    assert ops.aligned_tile(300) == 128
    assert ops.aligned_tile(100, 256) == 128
    assert ops.aligned_tile(300, 256) == 256
    bm, bn, _ = ops.default_tiles(100, 256, 300, None)
    assert (bm, bn) == (ops.aligned_tile(100), ops.aligned_tile(300))
    m, e = ops.bfp_quantize(jax.random.normal(KEY, (100, 256)), 8, 128,
                            interpret=True)
    assert m.shape == (100, 256) and e.shape == (100, 2)

# ---------------------------------------------------------------------------
# ISSUE 6 — dot modes, fused requantize epilogue (conv)
# ---------------------------------------------------------------------------

from repro.core.prequant import dequantize_act, prequant_act  # noqa: E402


def _conv(weights, x, wk, pol, stride, **kw):
    """``ops`` conv with float weights (the fused kernel) or with the
    weights prequantized (the sidecar kernel a bound plan launches)."""
    if weights == "float":
        return ops.bfp_conv2d(x, wk, pol, stride, "SAME", True, **kw)
    pq = prequant_conv_leaf(wk, pol)
    return ops.bfp_conv2d_prequant(x, pq["m"], pq["s"], pol, stride,
                                   "SAME", True, **kw)


@pytest.mark.parametrize("dot_impl", ["int8", "int32", "f32"])
@pytest.mark.parametrize("weights", ["float", "prequant"])
def test_conv_dot_modes_bit_identical(dot_impl, weights):
    """Every conv dot datapath, with float or prequantized weights, ==
    the int32 datapath on the same weight form bit for bit, and == the
    oracle."""
    x, wk = _case(8, 8, 8, 10, 3, 3, seed=21)
    pol = _tiled(24)
    out = _conv(weights, x, wk, pol, 1, dot_impl=dot_impl)
    base = _conv(weights, x, wk, pol, 1, dot_impl="int32")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    out_r = ref.bfp_conv2d_ref(x, wk, 8, 8, 24, 1, "SAME")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("weights", ["float", "prequant"])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv_epilogue_requant_bit_identical(weights, stride):
    """Fused conv epilogue == conv-then-prequant_act, bit for bit, with
    float or prequantized weights, including the NHWC sidecar shape
    (blocks along OC per pixel)."""
    x, wk = _case(8, 8, 8, 16, 3, 3, seed=22)
    pol = _tiled(24)
    out_pol = _tiled(8)
    fused = _conv(weights, x, wk, pol, stride, out_policy=out_pol)
    two = prequant_act(_conv(weights, x, wk, pol, stride), out_pol)
    oh = 8 // stride
    assert EG.is_prequant(fused) and fused["m"].dtype == jnp.int8
    assert fused["m"].shape == (2, oh, oh, 16)
    assert fused["s"].shape == (2, oh, oh, 2)
    np.testing.assert_array_equal(np.asarray(fused["m"]),
                                  np.asarray(two["m"]))
    np.testing.assert_array_equal(np.asarray(fused["s"]),
                                  np.asarray(two["s"]))


def test_conv_act_dict_input_bit_identical():
    """int8 wire-format NHWC activations consumed natively == dequantize
    + inline re-quantization (C blocks align with patch K blocks)."""
    x, wk = _case(8, 8, 16, 12, 3, 3, seed=23)
    pol = _tiled(16)
    xq = prequant_act(x, pol)
    assert EG.is_prequant(xq) and xq["m"].shape == x.shape
    out_d = ops.bfp_conv2d(xq, wk, pol, 1, "SAME", True)
    out_f = ops.bfp_conv2d(dequantize_act(xq), wk, pol, 1, "SAME", True)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_f))


def test_conv_epilogue_then_consume_chain():
    """conv -> conv entirely on the wire format == the all-float-
    activation chain with inline quantization, bit for bit."""
    x, w1 = _case(8, 8, 8, 16, 3, 3, seed=24)
    w2 = jax.random.normal(jax.random.PRNGKey(25), (3, 3, 16, 12)) * 0.1
    pol1, pol2 = _tiled(24), _tiled(16)
    y1 = ops.bfp_conv2d(x, w1, pol1, 1, "SAME", True, out_policy=pol2)
    out = ops.bfp_conv2d(y1, w2, pol2, 1, "SAME", True)
    y1_f = ops.bfp_conv2d(x, w1, pol1, 1, "SAME", True)
    out_ref = ops.bfp_conv2d(y1_f, w2, pol2, 1, "SAME", True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


# ---------------------------------------------------------------------------
# narrow channels: the patch path (a 1x1 conv over an XLA patch tensor)
# ---------------------------------------------------------------------------

def _implicit_conv(x, wk, pol, stride, out_policy):
    """The implicit-im2col kernel launched directly, as ops.bfp_conv2d
    launched every float conv before the patch path."""
    b, h, w, c = x.shape
    kh, kw, _, oc = wk.shape
    pads, (oh, ow, ohp, t_oh, bn, kp) = ops._conv_plan(
        b, h, w, c, kh, kw, oc, stride, "SAME", pol.block_k)
    fused_q = ops._conv_epilogue_cfg(out_policy, oc, bn)
    ob, obk = fused_q if fused_q is not None else (None, None)
    out = bfp_conv2d_pallas(
        jnp.pad(x, pads), ops._pad_to(conv_weight_matrix(wk), (kp, bn)),
        kh=kh, kw=kw, stride=stride, t_oh=t_oh, ohp=ohp, ow=ow, bn=bn,
        bk=pol.block_k, interpret=True, out_bits=ob, out_block=obk)
    return ops._finish_conv(out, oh, oc, out_policy, fused_q)


def _as_list(out):
    return ([out["m"], out["s"]] if EG.is_prequant(out) else [out])


@pytest.mark.parametrize("h,w,c,k,stride,epilogue,patch", [
    (20, 20, 3, 7, 2, False, 1),     # resnet50's stem, even H/W
    (11, 9, 3, 7, 2, False, 1),      # odd H/W
    (11, 9, 3, 7, 2, True, 1),
    (12, 12, 3, 3, 1, False, 1),     # vgg16's conv1_1
    (7, 9, 3, 3, 1, True, 1),
    (8, 8, 64, 3, 1, False, 0),      # C = 64, Kp 640: implicit
    (8, 8, 3, 1, 1, False, 0),       # 1x1: implicit
], ids=["stem", "stem_odd", "stem_odd_epilogue", "conv1_1",
        "conv1_1_odd_epilogue", "c64_3x3", "c3_1x1"])
def test_narrow_conv_patch_path(h, w, c, k, stride, epilogue, patch):
    """A narrow-channel conv (kh*kw > 1, Kp <= 2 * min(bk, 128)) runs as
    a 1x1 conv over its XLA patch tensor; the bits are the implicit
    kernel's and the im2col + bfp_matmul_pallas oracle's, epilogue on or
    off.  A C = 64 3x3 conv and a 1x1 conv stay implicit, and the
    counter says which path each took."""
    x, wk = _case(h, w, c, 16, k, k, seed=h * k + c)
    pol = _tiled(128)
    out_pol = _tiled(8) if epilogue else None
    with ops.count_conv_launches() as tally:
        out = ops.bfp_conv2d(x, wk, pol, stride, "SAME", interpret=True,
                             out_policy=out_pol)
    assert tally["patch"] == patch
    assert EG.is_prequant(out) == epilogue
    cols, (b, oh, ow) = im2col(x, k, k, stride, "SAME")
    gemm = ops.bfp_matmul(cols, conv_weight_matrix(wk), pol,
                          interpret=True, out_policy=out_pol)
    gemm = [a.reshape(b, oh, ow, -1) for a in _as_list(gemm)]
    for want in (_as_list(_implicit_conv(x, wk, pol, stride, out_pol)),
                 gemm):
        for got, exp in zip(_as_list(out), want, strict=True):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
