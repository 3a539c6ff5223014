"""Open-loop load harness (ISSUE 9): deterministic Poisson traces, the
virtual-clock driver, report accounting, and the continuous-vs-bucket
ordering on a virtual clock.
"""
import jax
import numpy as np
import pytest

from repro.core.policy import TPU_TILED
from repro.models.cnn import MODELS
from repro.serve.cnn import CnnServeEngine, ImageRequest
from repro.serve.load import (Arrival, VirtualClock, poisson_arrivals,
                              run_open_loop)

KEY = jax.random.PRNGKey(0)
POL = TPU_TILED.with_(block_k=None, straight_through=False)
MIX = [(0.5, "a", {}), (0.5, "b", {"deadline": 0.5})]


@pytest.fixture(scope="module")
def lenet():
    spec = MODELS["lenet"]
    params = spec.init(KEY)
    imgs = [jax.random.normal(jax.random.PRNGKey(5 + i),
                              spec.input_shape()) for i in range(4)]
    return spec, params, imgs


def test_poisson_arrivals_deterministic_and_shaped():
    a1 = poisson_arrivals(10.0, 50, MIX, seed=3)
    a2 = poisson_arrivals(10.0, 50, MIX, seed=3)
    assert a1 == a2                      # replayable trace
    assert a1 != poisson_arrivals(10.0, 50, MIX, seed=4)
    assert len(a1) == 50
    ts = [a.t for a in a1]
    assert ts == sorted(ts) and ts[0] > 0
    # mean gap ~ 1/rate (loose: 50 samples)
    assert 0.03 < np.mean(np.diff([0.0] + ts)) < 0.3
    kinds = {a.kind for a in a1}
    assert kinds == {"a", "b"}
    for a in a1:
        # the relative deadline is lifted off the payload
        assert a.deadline == (0.5 if a.kind == "b" else None)
        assert "deadline" not in a.payload
        assert isinstance(a.rid, int)


def test_poisson_arrivals_validation():
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(0.0, 5, MIX)
    with pytest.raises(ValueError, match="n must"):
        poisson_arrivals(1.0, 0, MIX)
    with pytest.raises(ValueError, match="mix"):
        poisson_arrivals(1.0, 5, [])


def test_virtual_clock():
    c = VirtualClock(2.0)
    assert c() == 2.0
    c.advance(0.5)
    assert c() == 2.5
    with pytest.raises(ValueError):
        c.advance(-1.0)


def _drive(lenet_fix, n=10, rate=200.0, seed=1, mix=MIX, **engine_kw):
    spec, params, imgs = lenet_fix
    arrivals = poisson_arrivals(rate, n, mix, seed=seed)
    clock = VirtualClock()
    eng = CnnServeEngine(params, spec.apply, POL, slots=4, jit=False,
                         clock=clock, **engine_kw)

    def mk(a):
        return ImageRequest(
            rid=a.rid, image=imgs[a.rid % len(imgs)],
            deadline=None if a.deadline is None else a.t + a.deadline)

    return run_open_loop(eng, arrivals, mk, clock=clock,
                         call_cost=0.002), eng


def test_open_loop_accounting(lenet):
    rep, eng = _drive(lenet)
    assert rep.offered == 10
    assert rep.completed + rep.shed + rep.expired + rep.failed == 10
    assert rep.completed == eng.stats["completed"] == 10
    assert rep.p99_ms >= rep.p50_ms > 0
    assert rep.mean_ms > 0 and rep.duration_s > 0
    assert rep.goodput_rps == pytest.approx(rep.completed /
                                            rep.duration_s)
    assert rep.calls == eng.ncalls > 0
    row = rep.row()
    assert row["completed"] == 10 and isinstance(row["p99_ms"], float)


def test_virtual_time_is_deterministic(lenet):
    r1, _ = _drive(lenet, n=20, seed=6)
    r2, _ = _drive(lenet, n=20, seed=6)
    assert r1 == r2                      # exact replay, any machine


def test_shedding_counted_once(lenet):
    rep, eng = _drive(lenet, n=30, rate=5000.0, max_queue=2)
    assert rep.shed > 0
    assert rep.shed == eng.stats["shed"]
    assert rep.completed + rep.shed + rep.expired + rep.failed == 30


def test_bucket_barrier_loses_on_p99(lenet):
    """The whole point: on the identical trace, the bucket barrier's
    idle waits turn into tail latency — and, once deadlines bind,
    expiries — that the continuous engine never pays."""
    # 10ms deadline on half the traffic: well above the continuous
    # engine's tail (~3ms here) but inside the bucket barrier's
    # max_wait idling, so only the barrier converts waits into expiry
    tight = [(0.5, "a", {}), (0.5, "b", {"deadline": 0.010})]
    cont, _ = _drive(lenet, n=40, rate=300.0, seed=9, mix=tight,
                     batching="continuous")
    buck, _ = _drive(lenet, n=40, rate=300.0, seed=9, mix=tight,
                     batching="bucket", max_wait=4)
    assert cont.p99_ms < buck.p99_ms
    assert cont.expired < buck.expired   # the barrier's waits expire work
    assert cont.goodput_rps > buck.goodput_rps


def test_idle_server_jumps_to_next_arrival(lenet):
    """Sparse arrivals: the driver must jump the clock across idle gaps
    instead of spinning, and latencies must not include idle time."""
    spec, params, imgs = lenet
    arrivals = [Arrival(t=float(t), rid=i, kind="a", payload={})
                for i, t in enumerate((1.0, 100.0, 200.0))]
    clock = VirtualClock()
    eng = CnnServeEngine(params, spec.apply, POL, slots=4, jit=False,
                         clock=clock)
    rep = run_open_loop(eng, arrivals,
                        lambda a: ImageRequest(rid=a.rid,
                                               image=imgs[0]),
                        clock=clock, call_cost=0.002)
    assert rep.completed == 3
    assert clock.t >= 200.0              # reached the last arrival
    assert rep.p99_ms < 1000.0           # idle gaps are not latency
