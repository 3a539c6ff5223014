"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Nothing runs here: each test compiles one kernel at a real VGG-16 shape
(batch 8, 224x224x3 input, 1000 classes) for a v5e that is described,
not attached, so what the chip's compiler refuses (a block shape that
breaks the (8, 128) rule, scoped VMEM overflow, a removed Pallas API)
fails here and costs no chip time.  The topology is described inside a
fixture, so only the worker that runs this file loads the TPU compiler.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bfp_conv as CV
from repro.kernels import bfp_matmul as MM
from repro.kernels import bfp_quantize as QZ
from repro.kernels.ops import _conv_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def test_xw_prequant_conv5_1(one_chip):
    x, kp, ocp, kw = _conv_geometry("conv5_1")
    c = x[3]
    _compiles(lambda xm, xs, wm, ws: CV.bfp_conv2d_xwprequant_pallas(
        xm, xs, wm, ws, **kw),
        [(x, jnp.int8), ((*x[:3], c // BK), jnp.float32),
         ((kp, ocp), jnp.int8), ((kp // BK, ocp), jnp.float32)], one_chip)


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
