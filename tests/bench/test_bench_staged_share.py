"""The ``serve.staged_share`` reader on a hand-made window of
``run.stats``: the share of forwards whose batch was staged, and None
where the program counts no forwards or keeps no such counter."""
import pytest

import _paths  # noqa: F401
from bench import spec as S
from bench.run import Run

#: a window of 4 forwards, 3 of them fed from a staged batch
STATS = {"forwards": 4, "completed": 128, "batches_staged": 3}


def _read(stats):
    return S.load_module("metrics", "serve.staged_share").read(
        Run(stats=stats))


@pytest.mark.parametrize("staged, want", [(3, 75.0), (0, 0.0), (4, 100.0)])
def test_share_of_forwards(staged, want):
    assert _read({**STATS, "batches_staged": staged}) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    {**STATS, "forwards": 0},
    {k: v for k, v in STATS.items() if k != "batches_staged"},
    {"completed": 0, "float_retries": 0}],
    ids=["no_forwards", "no_staged_counter", "no_counters"])
def test_none_without_the_counter(stats):
    # the second: a program that counts forwards but stages nothing it
    # could count (the engine before staging) reads None, not 0
    assert _read(stats) is None
