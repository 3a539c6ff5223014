"""``correct`` on the CPU at a size a test run holds: a sound run passes,
and the control and each fault a serving cell can have fail it.

The harness runs as on the chip from set-up on (its look for a chip is
the one step skipped), through the same server, traffic and comparison,
with the Pallas kernels in the interpreter.  Faults (``bench/faults.py``)
are planted under the timed path, in the forward the server calls: the
second half of each batch left out (zeros) or answered with the first
half's rows, each request answered with the next row's logits, and each
answer's classes shifted by one.  A serving cell has no state for a step
to return unchanged and, on one chip, no exchange between chips to leave
out.
"""
import copy
import time

import jax
import pytest

import _paths  # noqa: F401
from bench import faults as F
from bench import run as R
from bench import spec as S

MIX = {"arrivals": "backlog", "queue_factor": 2, "slots": 2,
       "buckets": [2], "image_pool": 4, "image_rects": 12, "warm_s": 0.2}
CELL = {"name": "tiny", "chips": 1}
SEED = 2 ** 31 + 11


def tiny_vgg16():
    """VGG-16's layer pattern at 32x32 with an eighth of the widths; the
    limits of the full configuration."""
    cfg = copy.deepcopy(S.load_json("configs", "vgg16"))
    cfg.update(input_hw=32, num_classes=10, fc_dims=[64, 64])
    cfg["conv_plan"] = [[n, max(8, c // 8)] for n, c in cfg["conv_plan"]]
    cfg["correct"]["sample"] = 8
    return cfg


def _run(cfg, precision=None, trace=False):
    return R.run_cell(CELL, cfg, MIX, seed=SEED, seconds=0.5, trace=trace,
                      devices=jax.devices(), t_start=time.perf_counter(),
                      precision=precision)


def _correct(run):
    return all(v["ok"] for v in run.compared.values())


def test_sound_run_is_correct():
    run = _run(tiny_vgg16())
    assert _correct(run), run.compared
    assert run.attempted >= 4 and run.failed == 0
    assert run.compared["rel_err_max"]["n"] == 8
    assert run.compared["misrouted"]["value"] == 0


def test_traced_run_is_correct_too():
    # the CPU trace holds no TPU plane: nothing to read, the same answer
    run = _run(tiny_vgg16(), trace=True)
    assert _correct(run), run.compared
    assert run.trace is not None and run.trace.idle_share is None


def test_control_precision_is_not_correct():
    cfg = tiny_vgg16()
    run = _run(cfg, precision=cfg["control_precision"])
    assert not run.compared["rel_err_max"]["ok"], run.compared
    assert not _correct(run)


@pytest.mark.parametrize("fault", sorted(F.FAULTS))
def test_planted_fault_is_not_correct(fault):
    with F.planted(fault):
        run = _run(tiny_vgg16())
    assert not _correct(run), run.compared
    if fault in ("half_duplicated", "rows_rolled"):
        # answers served to the wrong request lie nearer another image
        assert run.compared["misrouted"]["value"] > 0, run.compared
