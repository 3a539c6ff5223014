"""The ``kernels.conv_mxu_fill`` reader on a hand-made run: the model's
conv MACs at each step's bucket over the window's issued MACs, and None
where the program keeps no such counter."""
import pytest

import _paths  # noqa: F401
from bench import spec as S
from bench.drive import Record, StepRec
from bench.run import Run

#: one 3x3 conv, 8x8x16 -> 8x8x32, and an fc the reader leaves out
SITES = [dict(name="c", kind="conv", h=8, w=8, cin=16, cout=32, k=3,
              stride=1, ho=8, wo=8),
         dict(name="fc", kind="fc", cin=2048, cout=10)]
MACS = 8 * 8 * 9 * 16 * 32          # one image's conv MACs


def _read(stats, buckets=(4, 2)):
    rec = Record(t0=0.0, close=1.0)
    rec.steps = [StepRec(0.0, 0.1, b, b) for b in buckets]
    return S.load_module("metrics", "kernels.conv_mxu_fill").read(
        Run(stats=stats, sites=SITES, record=rec))


def test_fill_is_needed_over_issued():
    # Kp 256 of K 144 and 128 of OC 32 issued: 144 * 32 / (256 * 128)
    issued = 6 * 8 * 8 * 256 * 128
    assert _read({"conv_macs_issued": issued}) == pytest.approx(
        100.0 * 6 * MACS / issued)
    assert _read({"conv_macs_issued": issued}) == pytest.approx(
        100.0 * 144 * 32 / (256 * 128))


@pytest.mark.parametrize("stats", [{"forwards": 2}, {"forwards": 2,
                                                     "conv_macs_issued": 0}],
                         ids=["no_counter", "nothing_issued"])
def test_none_without_the_counter(stats):
    assert _read(stats) is None
