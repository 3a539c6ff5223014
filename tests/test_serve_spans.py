"""Host phase spans and counters of ``CnnServeEngine.step``
(``serve/spans.py``, docs/tracing.md): the counters match the steps run,
the phases tile each step, records are kept only on request, and the
readers of ``stats`` (``MultiTenantServer``, ``serve.load``) still sum."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import PALLAS_TILED
from repro.models.cnn import MODELS
from repro.serve.cnn import CnnServeEngine, ImageRequest
from repro.serve.load import Arrival, VirtualClock, run_open_loop
from repro.serve.spans import PREFIX
from repro.serve.tenants import MultiTenantServer

#: the phases of a step that runs one forward, in order
PHASES = ["serve.admit", "serve.batch_in", "serve.dispatch",
          "serve.logits_wait", "serve.finish"]


@pytest.fixture(scope="module")
def lenet():
    spec = MODELS["lenet"]
    params = spec.init(jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1),
                             (8, *spec.input_shape()))
    return spec, params, np.asarray(imgs)


def _engine(lenet, **kw):
    spec, params, _ = lenet
    return CnnServeEngine(params, spec.apply, None, slots=4, **kw)


def _host_ns(stats):
    return {k: v for k, v in stats.items() if k.startswith(PREFIX)}


def test_counters_match_steps(lenet):
    _, _, imgs = lenet
    eng = _engine(lenet)
    for img in imgs[:6]:
        eng.submit(image=img)
    t = time.perf_counter_ns()
    assert eng.step() == 2          # 4 admitted, bucket 4
    assert eng.step() == 0          # 2 admitted, bucket 2
    for img in imgs[:3]:
        eng.submit(image=img)
    assert eng.step() == 0          # 3 live rows in bucket 4
    wall = time.perf_counter_ns() - t
    st = eng.stats
    assert st["forwards"] == eng.ncalls == 3
    assert st["rows"] == 4 + 2 + 4 and st["rows_live"] == 4 + 2 + 3
    assert st["completed"] == 9
    host = _host_ns(st)
    assert sorted(host) == sorted(PREFIX + p for p in PHASES)
    assert all(v > 0 for v in host.values()), host
    assert sum(host.values()) <= wall
    assert st["queue_wait_ns"] > 0
    with pytest.raises(AttributeError):
        eng.ncalls = 0


def test_queue_wait_counts_time_in_queue(lenet):
    _, _, imgs = lenet
    eng = _engine(lenet)
    eng.step()                      # nothing queued: admission only
    assert eng.stats["queue_wait_ns"] == 0 and eng.stats["forwards"] == 0
    assert eng.stats[PREFIX + "serve.admit"] > 0
    eng.submit(image=imgs[0])
    time.sleep(0.02)
    eng.run()
    assert eng.stats["queue_wait_ns"] >= 20_000_000


def test_records_kept_only_on_request(lenet):
    _, _, imgs = lenet
    eng = _engine(lenet)
    eng.submit(image=imgs[0])
    eng.run()
    assert eng.spans.records == []
    eng.spans.keep = True
    for _ in range(2):
        for img in imgs[:2]:
            eng.submit(image=img)
        eng.step()
    recs = eng.spans.records
    # HostSpans' rows ([name, start_ns, duration_ns]) plus the step id
    assert all(isinstance(n, str) and isinstance(s, int)
               and isinstance(d, int) and d >= 0 and isinstance(k, int)
               for n, s, d, k in recs)
    steps = sorted((r for r in recs if r[0] == "serve.step"),
                   key=lambda r: r[1])
    assert [r[3] for r in steps] == [1, 2]
    for _, s0, d0, k in steps:
        phases = sorted((r for r in recs if r[0] != "serve.step"
                         and r[3] == k), key=lambda r: r[1])
        assert [r[0] for r in phases] == PHASES
        assert s0 <= phases[0][1]
        assert phases[-1][1] + phases[-1][2] <= s0 + d0
        for a, b in zip(phases, phases[1:]):
            assert a[1] + a[2] <= b[1]      # one after another
    eng.spans.keep = False
    n = len(recs)
    eng.submit(image=imgs[0])
    eng.run()
    assert len(eng.spans.records) == n


@pytest.mark.parametrize("jit", [True, False])
def test_patch_convs_counted_per_forward(lenet, jit):
    """``patch_convs`` counts each forward's conv launches on the
    narrow-channel patch path: lenet's c1 (C = 1, 5x5, Kp 128) takes it,
    c2 (C = 16, Kp 512) does not, and a float forward takes none."""
    spec, params, imgs = lenet
    bfp = CnnServeEngine(params, spec.apply,
                         PALLAS_TILED.with_(straight_through=False),
                         slots=4, jit=jit)
    flt = _engine(lenet, jit=jit)
    for eng in (bfp, flt):
        for img in imgs[:6]:
            eng.submit(image=img)
        eng.run()
        assert eng.stats["forwards"] == 2
    assert bfp.stats["patch_convs"] == 2
    assert flt.stats["patch_convs"] == 0


@pytest.mark.parametrize("jit", [True, False])
def test_conv_macs_issued_counted_per_forward(lenet, jit):
    """``conv_macs_issued`` adds, per forward, B * OHp * OW * Kp *
    ceil(OC / 128) * 128 over its conv launches, at the forward's own
    bucket: c1 over its 28x28 patch tensor (Kp 128, 28 rows a program,
    OC 16 -> 128), c2 at 14x14 (Kp 512, 9 rows a program so OHp 18)."""
    spec, params, imgs = lenet
    bfp = CnnServeEngine(params, spec.apply,
                         PALLAS_TILED.with_(straight_through=False),
                         slots=4, jit=jit)
    flt = _engine(lenet, jit=jit)
    for eng in (bfp, flt):
        for img in imgs[:6]:
            eng.submit(image=img)
        eng.run()
        assert eng.stats["rows"] == 4 + 2
    per_image = 28 * 28 * 128 * 128 + 18 * 14 * 512 * 128
    assert bfp.stats["conv_macs_issued"] == 6 * per_image
    assert flt.stats["conv_macs_issued"] == 0


def test_float_retry_is_a_phase(lenet):
    spec, params, imgs = lenet

    def flaky_apply(p, x, pol):
        y = spec.apply(p, x, pol)
        return y * jnp.nan if pol is not None else y

    eng = CnnServeEngine(params, flaky_apply, None, slots=2)
    eng.spans.keep = True
    r = eng.submit(image=imgs[0])
    eng.step()
    assert r.error is None and np.all(np.isfinite(r.logits))
    st = eng.stats
    assert st["float_retries"] == 1
    assert st["forwards"] == eng.ncalls == 2    # the retry is a forward
    assert st["rows"] == 2 and st["rows_live"] == 2
    assert st[PREFIX + "serve.float_retry"] > 0
    order = [n for n, *_ in sorted(eng.spans.records, key=lambda r: r[1])]
    assert order == ["serve.step"] + PHASES + ["serve.float_retry"]


def test_tenant_totals_sum_the_new_counters(lenet):
    spec, params, imgs = lenet
    srv = MultiTenantServer(slots=2)
    for name in ("a", "b"):
        srv.add_tenant(name, "lenet", params=params)
    for i in range(3):
        srv.submit("a", image=imgs[i])
    srv.submit("b", image=imgs[3])
    srv.run()
    st = srv.stats()
    for k in ("forwards", "rows", "rows_live", "completed",
              PREFIX + "serve.batch_in"):
        assert st["total"][k] == sum(t[k] for t in st["tenants"].values())
    assert st["total"]["completed"] == 4
    assert st["total"]["forwards"] == 3


def test_ncalls_is_the_load_harness_unit(lenet):
    """``serve.load`` reads ``ncalls`` as before: one virtual-time unit
    per forward, and a burst the slots hold takes one forward."""
    _, _, imgs = lenet
    clock = VirtualClock()
    eng = _engine(lenet, clock=clock)
    arrivals = [Arrival(t=0.0, rid=i, kind="img", payload={})
                for i in range(4)] + [Arrival(t=1.0, rid=4, kind="img",
                                              payload={})]
    rep = run_open_loop(eng, arrivals, lambda a: ImageRequest(
        rid=a.rid, image=imgs[a.rid]), clock=clock, call_cost=0.05)
    assert rep.completed == 5
    assert rep.calls == eng.ncalls == eng.stats["forwards"] == 2
    assert rep.duration_s == pytest.approx(1.0 + 0.05)


def test_launcher_phase_line(lenet):
    from repro.launch.serve_cnn import phase_line
    _, _, imgs = lenet
    eng = _engine(lenet)
    assert phase_line(eng.stats) == "no forwards"
    for img in imgs[:3]:
        eng.submit(image=img)
    eng.run()
    line = phase_line(eng.stats)
    assert line.startswith("host ms per forward (1 forwards): admit ")
    for p in PHASES[1:]:
        assert f" {p[len('serve.'):]} " in line
    assert eng.stats["admitted"] == 3
    assert line.endswith("rows filled 75.0%")
