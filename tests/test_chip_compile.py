"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Nothing runs here: each test compiles one kernel at a real VGG-16 shape
(batch 8, 224x224x3 input, 1000 classes) for a v5e that is described,
not attached, so what the chip's compiler refuses (a block shape that
breaks the (8, 128) rule, scoped VMEM overflow, a removed Pallas API)
fails here and costs no chip time.  One more compiles a whole small
CNN forward, to check the names its kernels carry.  The topology is
described inside a fixture, so only the worker that runs this file
loads the TPU compiler.
"""
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine
from repro.core.bfp import Scheme
from repro.core.policy import BFPPolicy
from repro.kernels import bfp_conv as CV
from repro.kernels import bfp_matmul as MM
from repro.kernels import bfp_quantize as QZ
from repro.kernels import ops as OPS
from repro.kernels.ops import _conv_plan
from repro.models.cnn import MODELS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from bench import spec as S  # noqa: E402
from bench import trace as T  # noqa: E402
BATCH, BK = 8, 128
#: vgg16 fc6: [8, 25088] x [25088, 4096]
FC6_K, FC6_N = 25088, 4096
#: (input H == W, C, OC) of the vgg16 convs compiled below
CONVS = {"conv1_1": (224, 3, 64), "conv1_2": (224, 64, 64),
         "conv5_1": (14, 512, 512)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                yield topologies.get_topology_desc(platform="tpu",
                                                   topology_name="v5e:2x2")
            except Exception as e:   # noqa: BLE001 — any describe failure
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles(fn, shapes, sharding) -> None:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("pipeline", [True, False])
def test_fused_gemm_fc6(one_chip, pipeline):
    _compiles(lambda x, w: MM.bfp_matmul_pallas(
        x, w, bm=BATCH, bn=128, bk=BK, pipeline=pipeline),
        [((BATCH, FC6_K), jnp.float32), ((FC6_K, FC6_N), jnp.float32)],
        one_chip)


@pytest.mark.parametrize("out_bits", [None, 8])
def test_prequant_gemm_fc6(one_chip, out_bits):
    """The fc path vgg16 serves, and the same kernel with the fused
    requantize epilogue (its [N/bq, B, 1] step output)."""
    _compiles(lambda x, wm, ws: MM.bfp_matmul_prequant_pallas(
        x, wm, ws, bm=BATCH, bn=128, bk=BK, out_bits=out_bits,
        out_block=BK if out_bits else None),
        [((BATCH, FC6_K), jnp.float32), ((FC6_K, FC6_N), jnp.int8),
         ((FC6_K // BK, FC6_N), jnp.float32)], one_chip)


def test_quantize_fc7_weight(one_chip):
    _compiles(lambda x: QZ.bfp_quantize_pallas(x, bm=256, bk=BK),
              [((4096, 4096), jnp.float32)], one_chip)


def _conv_geometry(layer):
    hw, c, oc = CONVS[layer]
    pads, (_, ow, ohp, t_oh, bn, kp) = _conv_plan(
        BATCH, hw, hw, c, 3, 3, oc, 1, "SAME", BK)
    x = (BATCH, hw + sum(pads[1]), hw + sum(pads[2]), c)
    kw = dict(kh=3, kw=3, stride=1, t_oh=t_oh, ohp=ohp, ow=ow, bn=bn, bk=BK)
    return x, kp, -(-oc // bn) * bn, kw


@pytest.mark.parametrize("layer", ["conv1_1", "conv1_2"])
@pytest.mark.parametrize("prequant", [False, True])
def test_conv_full_resolution(one_chip, layer, prequant):
    """224² layers: the whole padded input plane is one VMEM block, so
    these fail under the default scoped-VMEM limit.  vgg16 serves them
    with float weights (K = 27 and 576 are not block multiples, so they
    stay unprequantized); the prequant kernel gets the same geometry."""
    x, kp, ocp, kw = _conv_geometry(layer)
    if prequant:
        _compiles(lambda x, wm, ws: CV.bfp_conv2d_prequant_pallas(
            x, wm, ws, **kw),
            [(x, jnp.float32), ((kp, ocp), jnp.int8),
             ((kp // BK, ocp), jnp.float32)], one_chip)
    else:
        _compiles(lambda x, w: CV.bfp_conv2d_pallas(x, w, **kw),
                  [(x, jnp.float32), ((kp, ocp), jnp.float32)], one_chip)


@pytest.mark.parametrize("batch,hw,oc", [(32, 224, 64), (2, 32, 8)],
                         ids=["resnet50_224", "reduced_32"])
def test_stem_patch_path(one_chip, monkeypatch, batch, hw, oc):
    """ResNet-50's 7x7/2 stem (C = 3) takes the patch path: an XLA patch
    tensor and a 1x1 kernel.  At 224² and batch 32 (the serving bucket),
    and at the reduced widths and 32x32, where the implicit kernel ran
    out of scoped VMEM (19.05 MB against 17.98 MB)."""
    monkeypatch.setattr(OPS, "default_interpret", lambda: False)
    pol = BFPPolicy(l_w=8, l_i=8, scheme=Scheme.TILED, block_k=BK,
                    backend="pallas", straight_through=False)
    with OPS.count_conv_launches() as tally:
        _compiles(lambda x, w: OPS.bfp_conv2d(x, w, pol, 2, "SAME"),
                  [((batch, hw, hw, 3), jnp.float32),
                   ((7, 7, 3, oc), jnp.float32)], one_chip)
    assert tally["patch"] == 1


#: (input H == W, C, k, OC, OHp) of GoogLeNet's distinct branch conv
#: shapes; OHp pads H to conv_row_tile's rows a program (4, 9, 9, 7)
GOOGLENET_CONVS = {"inc3a/b5": (28, 16, 5, 32, 28),
                   "inc4a/b5r": (14, 480, 1, 16, 18),
                   "inc4e/b3": (14, 160, 3, 320, 18),
                   "inc5b/b1": (7, 832, 1, 384, 7)}


@pytest.mark.parametrize("site", sorted(GOOGLENET_CONVS))
def test_googlenet_branch_conv(one_chip, monkeypatch, site):
    """GoogLeNet's 5x5 window, narrow output tiles (bn 16-32) and
    K = 480, 832 and 1440, which no block of 128 divides (the served
    path keeps such weights in float), at the serving bucket."""
    hw, c, k, oc, ohp = GOOGLENET_CONVS[site]
    monkeypatch.setattr(OPS, "default_interpret", lambda: False)
    pol = BFPPolicy(l_w=8, l_i=8, scheme=Scheme.TILED, block_k=BK,
                    backend="pallas", straight_through=False)
    with OPS.count_conv_launches() as tally:
        _compiles(lambda x, w: OPS.bfp_conv2d(x, w, pol, 1, "SAME"),
                  [((32, hw, hw, c), jnp.float32),
                   ((k, k, c, oc), jnp.float32)], one_chip)
    assert tally["patch"] == 0
    kp = -(-k * k * c // BK) * BK
    assert tally["macs_issued"] == 32 * ohp * hw * kp * (-(-oc // 128) * 128)


def test_googlenet_forward(one_chip, monkeypatch):
    """The served GoogLeNet at 224² and bucket 32, bound as the benchmark
    binds it: one kernel a site (57 convs and the fc, no aux head), the
    stem alone on the patch path, and the MACs the conv launches issue,
    B * OHp * OW * Kp * ceil(OC / 128) * 128 summed over the sites
    (2,829,811,712 an image: row padding, K padded to 128 and OC to the
    MXU's 128 columns), 1.79x the 1,581,647,872 the convs need."""
    cfg = S.load_json("configs", "googlenet")
    ref = S.load_module("configs", "googlenet")
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    shapes)
    pol = BFPPolicy(l_w=8, l_i=8, scheme=Scheme.TILED, block_k=BK,
                    backend="pallas", straight_through=False)
    plan = engine.bind(params, pol, tree="cnn", strict=True,
                       prequantize=True)
    monkeypatch.setattr(OPS, "default_interpret", lambda: False)
    fwd = plan.jit_forward(MODELS["googlenet"].apply)
    arrays = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in fwd.arrays]
    x = jax.ShapeDtypeStruct((32, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    text = fwd._run.lower(arrays, x).compile().as_text()
    assert fwd.patch_convs == 1
    assert fwd.conv_macs_issued == {32: 32 * 2_829_811_712}
    sites = {s["name"] for s in ref.sites(cfg)}
    assert set(plan.sites) == sites and len(sites) == 58
    calls = [m.group(2) for m in map(T._CALL.match, text.splitlines()) if m]
    assert len(calls) == 58
    assert {re.match(r"jit\(run\)/(.+)/jit\(\w+\)/pallas_call$",
                     n).group(1) for n in calls} == sites


def test_xw_prequant_conv5_1(one_chip):
    x, kp, ocp, kw = _conv_geometry("conv5_1")
    c = x[3]
    _compiles(lambda xm, xs, wm, ws: CV.bfp_conv2d_xwprequant_pallas(
        xm, xs, wm, ws, **kw),
        [(x, jnp.int8), ((*x[:3], c // BK), jnp.float32),
         ((kp, ocp), jnp.int8), ((kp // BK, ocp), jnp.float32)], one_chip)


def _zero_params(spec):
    """``spec``'s reduced parameter tree with zero arrays (a compile needs
    shapes only; drawing the weights on the CPU would take seconds)."""
    box = {}

    def arrays(key):
        p = spec.init(key)
        box["meta"] = p.pop("meta")
        return p

    shapes = jax.eval_shape(arrays, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    shapes)
    params["meta"] = box["meta"]
    return params


def test_cnn_forward_kernels_name_their_sites(one_chip, monkeypatch):
    """A bound ResNet-50 forward (the reduced widths, 64x64, BFP-8 TILED
    pallas, prequantized where K allows) compiled for the v5e: every
    kernel's ``op_name`` carries the path of the plan site that launched
    it (``Plan`` runs each site under ``jax.named_scope``), the
    benchmark still classes each kernel as conv or matmul, and the site
    paths' ``/`` stay out of the HLO instruction names."""
    spec = MODELS["resnet50"]
    pol = BFPPolicy(l_w=8, l_i=8, scheme=Scheme.TILED, block_k=128,
                    backend="pallas", straight_through=False)
    plan = engine.bind(_zero_params(spec), pol, tree="cnn", strict=True,
                       prequantize=True)
    monkeypatch.setattr(OPS, "default_interpret", lambda: False)
    fwd = plan.jit_forward(spec.apply)
    arrays = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in fwd.arrays]
    x = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32, sharding=one_chip)
    text = fwd._run.lower(arrays, x).compile().as_text()
    calls = dict(m.groups() for m in map(T._CALL.match, text.splitlines())
                 if m)
    assert len(calls) == len(plan.sites)
    named = set()
    for instr, op_name in calls.items():
        site = re.match(r"jit\(run\)/(.+)/jit\(\w+\)/pallas_call$", op_name)
        assert site and site.group(1) in plan.sites, op_name
        named.add(site.group(1))
    assert named == set(plan.sites)
    classes = T.kernel_classes([text])["kernels"]
    assert classes.keys() == calls.keys()
    assert set(classes.values()) == {"conv", "matmul"}
    assert any("prequant" in n for n in classes)
    instrs = re.findall(r"^\s*(?:ROOT )?%?(\S+) = ", text, re.M)
    assert len(instrs) > len(calls)
    bad = [n for n in instrs if not re.fullmatch(r"[\w.\-]+", n)]
    assert not bad, bad[:5]


def test_chip_smoke_refuses_without_tpu(capsys):
    """On a machine whose first device is no TPU, chip_smoke.py exits
    non-zero before any work and prints no result."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert mod.main([]) != 0
    assert mod.main(["--four-chips"]) != 0
    assert '"ok"' not in capsys.readouterr().out
