"""bench/work.py counts from shapes, against the published sizes."""
import pytest

import _paths  # noqa: F401
from bench import spec as S
from bench import work as W


@pytest.mark.parametrize("name,sites", [("vgg16", 16), ("resnet50", 54)])
def test_macs_and_params_match_published(name, sites):
    cfg = S.load_json("configs", name)
    ref = S.load_module("configs", name)
    s = ref.sites(cfg)
    assert len(s) == sites
    macs = W.model_work(s, 1)["macs"]
    assert macs == pytest.approx(cfg["published"]["macs_per_image"],
                                 rel=0.01)
    assert ref.param_count(cfg) == cfg["published"]["params"]


def test_conv_counts_by_hand():
    # conv1_2 of vgg16 at batch 2: 224x224 out, K = 9 * 64, N = 64
    site = dict(kind="conv", h=224, w=224, cin=64, cout=64, k=3, stride=1,
                ho=224, wo=224)
    w = W.site_work(site, 2)
    assert w["macs"] == 2 * 224 * 224 * 576 * 64
    assert w["ops"] == 2 * w["macs"]
    weights = 576 * 64 + 5 * 64 * 4          # ceil(576 / 128) = 5 steps
    act = 2 * 224 * 224 * (64 + 4)           # one step per 64 channels
    assert w["bytes"] == weights + 2 * act


def test_work_scales_with_batch_and_kind_filter():
    cfg = S.load_json("configs", "resnet50")
    s = S.load_module("configs", "resnet50").sites(cfg)
    one, eight = W.model_work(s, 1), W.model_work(s, 8)
    assert eight["macs"] == 8 * one["macs"]
    conv, fc = W.model_work(s, 4, kind="conv"), W.model_work(s, 4,
                                                             kind="fc")
    assert conv["macs"] + fc["macs"] == 4 * one["macs"]
    assert fc["macs"] == 4 * 2048 * 1000


def test_least_seconds_and_peaks():
    peak = W.load_peaks("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert W.least_seconds({"ops": 393e12, "bytes": 1.0}, peak) == 1.0
    assert W.least_seconds({"ops": 1.0, "bytes": 819e9}, peak) == 1.0
    with pytest.raises(KeyError):
        W.load_peaks("cpu")
