"""The ``googlenet`` configuration on the CPU: its reference's shapes and
counts against the paper, its local response norm against a plain loop,
and the served program against the reference at an eighth of the widths
and a 64x64 input, through the harness's own path (``bench/run.py``
from set-up on, Pallas kernels in the interpreter).  The stem keeps the
configuration's pixel-scaled weights, so the two norms act as they do in
the deployed net, and a program that leaves them out fails the limit."""
import copy
import time

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import run as R
from bench import spec as S
from bench.work import model_work
from repro.models.cnn import layers as L

MIX = {"arrivals": "backlog", "queue_factor": 2, "slots": 2,
       "buckets": [2], "image_pool": 4, "image_rects": 12, "warm_s": 0.2}
CELL = {"name": "tiny", "chips": 1}
SEED = 2 ** 31 + 15
CFG = S.load_json("configs", "googlenet")
REF = S.load_module("configs", "googlenet")


def tiny_googlenet():
    """GoogLeNet's layer pattern at 64x64 with an eighth of the widths
    (at least 4 channels a branch); the limits of the full
    configuration."""
    cfg = copy.deepcopy(CFG)
    cfg.update(input_hw=64, num_classes=10)
    for key in ("stem1", "stem2r", "stem2"):
        cfg[key]["out"] = max(4, cfg[key]["out"] // 8)
    cfg["inception"] = [row if row[0] == "pool" else
                        [row[0], *(max(4, c // 8) for c in row[1:])]
                        for row in cfg["inception"]]
    cfg["correct"]["sample"] = 8
    return cfg


def _run(cfg):
    return R.run_cell(CELL, cfg, MIX, seed=SEED, seconds=0.5, trace=False,
                      devices=jax.devices(), t_start=time.perf_counter())


def test_sites_and_params_follow_table_1():
    sites = REF.sites(CFG)
    convs = [s for s in sites if s["kind"] == "conv"]
    assert len(convs) == 57 and [s["kind"] for s in sites[57:]] == ["fc"]
    assert {(s["k"], s["stride"]) for s in convs} == {
        (7, 2), (1, 1), (3, 1), (5, 1)}
    # Table 1's ops column sums to 1.50 G, but its conv1 row reads 34 M
    # where a 7x7x3 -> 64 conv at 112x112 takes 118 M; with that row as
    # computed the table gives 1.586 G
    macs = model_work(sites, 1, kind="conv")["macs"]
    assert macs == pytest.approx(1.58e9, rel=0.05)
    # Table 1's params column (6.8 M) rounds each row and reads below its
    # own widths in several rows (inception (4c): 463 K, where its widths
    # give 509 K; conv1: 2.7 K for 9.4 K); the widths give 6,990,272
    # weights and 8,280 biases
    assert REF.param_count(CFG) == 6_998_552
    assert REF.param_count(CFG) == pytest.approx(
        CFG["published"]["params"], rel=0.03)


def _lrn_loop(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    """Caffe's formula, one channel at a time."""
    out = np.empty_like(x)
    c = x.shape[-1]
    for ch in range(c):
        lo, hi = max(0, ch - size // 2), min(c, ch + size // 2 + 1)
        s = np.sum(x[..., lo:hi] ** 2, axis=-1)
        out[..., ch] = x[..., ch] / (k + alpha / size * s) ** beta
    return out


def test_local_response_norm_matches_a_loop():
    # |x| ~ 100: alpha/size * sum x^2 is about 1, so the norm halves and
    # bends the values, and a wrong window or exponent shows
    x = 100.0 * np.random.default_rng(0).standard_normal(
        (2, 3, 4, 12)).astype(np.float32)
    x64 = x.astype(np.float64)
    want = _lrn_loop(x64)
    # a window sums about 5 mean squares: alpha/size * 5 * E[x^2]
    assert 0.5 < 1e-4 * np.mean(x64 ** 2) < 2.0
    for got in (L.local_response_norm(x), REF.lrn(x, CFG)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_served_program_matches_the_reference():
    run = _run(tiny_googlenet())
    assert all(v["ok"] for v in run.compared.values()), run.compared
    assert run.attempted >= 4 and run.failed == 0
    assert run.compared["rel_err_max"]["n"] == 8
    # the counters count: at these widths the stem and the narrow 3x3 and
    # 5x5 convs take the patch path, and the MXU is less than filled
    assert run.stats["patch_convs"] >= run.stats["forwards"] > 0
    need = sum(model_work(run.sites, s.bucket, kind="conv")["macs"]
               for s in run.record.steps)
    assert 0 < need < run.stats["conv_macs_issued"]


def test_program_without_the_norms_fails_the_limit(monkeypatch):
    monkeypatch.setattr(L, "local_response_norm", lambda x: x)
    run = _run(tiny_googlenet())
    rel = run.compared["rel_err_max"]
    assert not rel["ok"], run.compared
