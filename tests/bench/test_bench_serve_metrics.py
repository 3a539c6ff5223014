"""The serve-step readers (``serve.host_ms``, ``serve.batch_in_ms``,
``serve.logits_wait_ms``) on a hand-made window of ``run.stats``, and
the trace reduction given the engine's own span records."""
import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import spec as S
from bench import trace as T
from bench.run import Run

MS = 1_000_000
#: a window of 4 forwards: ns per phase in all
STATS = {"forwards": 4, "completed": 128, "host_ns.serve.admit": 4 * MS,
         "host_ns.serve.batch_in": 60 * MS, "host_ns.serve.dispatch": 2 * MS,
         "host_ns.serve.logits_wait": 420 * MS,
         "host_ns.serve.finish": 6 * MS}


def _read(metric, stats):
    return S.load_module("metrics", metric).read(Run(stats=stats))


@pytest.mark.parametrize("metric, want", [
    ("serve.host_ms", (4 + 60 + 2 + 6) / 4),
    ("serve.batch_in_ms", 60 / 4),
    ("serve.logits_wait_ms", 420 / 4)])
def test_reader_per_forward(metric, want):
    assert _read(metric, STATS) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["serve.host_ms", "serve.batch_in_ms",
                                    "serve.logits_wait_ms"])
@pytest.mark.parametrize("stats", [{**STATS, "forwards": 0},
                                   {"completed": 0, "float_retries": 0}],
                         ids=["no_forwards", "no_counters"])
def test_reader_none_without_forwards(metric, stats):
    # the second: a program that keeps no such counters
    assert _read(metric, stats) is None


def test_idle_gap_goes_to_the_engine_phase():
    """Engine records ([name, start, duration, step]; their first three
    fields are ``HostSpans`` rows) inside the benchmark's ``bench.step``:
    a device gap during ``serve.batch_in`` is charged to it, not to the
    step around it."""
    from repro.models.cnn import MODELS
    from repro.serve.cnn import CnnServeEngine

    spec = MODELS["lenet"]
    eng = CnnServeEngine(spec.init(jax.random.PRNGKey(0)), spec.apply,
                         None, slots=2)
    eng.spans.keep = True
    spans = T.HostSpans()
    eng.submit(image=np.zeros(spec.input_shape(), np.float32))
    with spans.span("bench.window"):
        with spans.span("bench.step"):
            eng.step()
    host = spans.spans + [r[:3] for r in eng.spans.records]
    (_, b0, bd), = [r for r in host if r[0] == "serve.batch_in"]
    (_, w0, wd), = [r for r in host if r[0] == "bench.window"]
    gap = (b0 + bd // 4, b0 + 3 * bd // 4)
    trace = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", w0, gap[0] - w0],
                ["fusion.1", gap[1], w0 + wd - gap[1]]],
        "modules": []}}, "host": host}
    s = T.summarize(trace, {"kernels": {}, "programs": []})
    assert list(s.gaps) == ["serve.batch_in"]
    assert s.gaps["serve.batch_in"][:2] == [
        pytest.approx((gap[1] - gap[0]) * 1e-9), 1]
