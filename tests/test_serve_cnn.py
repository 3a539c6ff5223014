"""ISSUE 4: batched CNN inference service on sharded BFP plans.

Key contracts:
  * bit-exactness: a request served through ``CnnServeEngine`` produces
    EXACTLY the logits of a direct ``apply(plan.params, batch, plan)``
    on the same rows — verified through ``engine.taps`` events on both
    paths (same sites, same backends, same datapath outputs);
  * bucket padding with DUPLICATES of a live image never perturbs real
    rows (a duplicate row cannot raise a shared block max; a zero image
    would only be safe while zero biases keep zero rows zero);
  * plan reuse: engines bound to one plan share one jitted forward
    (``Plan.jit_forward``), and ``strict_backend`` rejects undeployable
    configs at construction;
  * the data-parallel sharding path (``dist.sharding.axis_rules`` +
    ``launch.mesh``) runs the same code 1-device, bit-identically;
  * continuous batching: more requests than slots drain fully, slots
    are reused.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as EG
from repro.core import BFPPolicy
from repro.dist.sharding import DEFAULT_RULES
from repro.engine.backends import BackendUnsupportedError
from repro.launch.mesh import make_mesh
from repro.models.cnn import MODELS, googlenet, small, vgg
from repro.serve.cnn import CnnServeEngine, ImageRequest, default_buckets
from repro.serve.slots import SlotTable

KEY = jax.random.PRNGKey(0)
EQ4 = BFPPolicy(straight_through=False)


def _images(n, shape=(28, 28, 1)):
    return [jax.random.normal(jax.random.PRNGKey(100 + i), shape)
            for i in range(n)]


# ---------------------------------------------------------------------------
# slot table
# ---------------------------------------------------------------------------

def test_slot_table_admission_and_reuse():
    t = SlotTable(2)
    for r in ("a", "b", "c"):
        t.submit(r)
    assert t.admit() == [0, 1]
    assert t.active() == [0, 1] and t.req[0] == "a"
    assert t.admit() == []          # full: "c" stays queued
    t.free(0)
    assert t.admit() == [0] and t.req[0] == "c"
    t.free(0)
    t.free(1)
    assert not t.pending()
    with pytest.raises(ValueError):
        SlotTable(0)


def test_default_buckets():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(6) == (1, 2, 4, 6)
    assert default_buckets(1) == (1,)


# ---------------------------------------------------------------------------
# bit-exactness vs direct apply (the regression the service is pinned by)
# ---------------------------------------------------------------------------

def test_serve_matches_direct_apply_bitexact():
    """Jitted bucketed serving == direct model apply with the same Plan."""
    params = small.lenet_init(KEY)
    plan = EG.bind(params, EQ4)
    imgs = _images(4)
    direct = small.lenet_apply(plan.params, jnp.stack(imgs), plan)
    eng = CnnServeEngine(None, small.lenet_apply, plan, slots=4,
                         buckets=(4,))
    reqs = [eng.submit(ImageRequest(rid=i, image=im))
            for i, im in enumerate(imgs)]
    eng.run()
    for i, r in enumerate(reqs):
        assert r.done and r.rid == i
        np.testing.assert_array_equal(r.logits, np.asarray(direct[i]))
        assert r.label == int(jnp.argmax(direct[i]))


def test_serve_taps_match_direct_path():
    """ISSUE 4 satellite: the engine runs the SAME datapath as a direct
    apply — engine.taps events on both paths agree on site identity,
    backend, and the exact datapath outputs.  (Taps observe eager
    execution, so the engine runs jit=False here.)"""
    params = small.lenet_init(KEY)
    plan = EG.bind(params, EQ4)
    imgs = _images(4)

    direct_evs = []
    with EG.taps(direct_evs.append):
        direct = small.lenet_apply(plan.params, jnp.stack(imgs), plan)

    serve_evs = []
    eng = CnnServeEngine(None, small.lenet_apply, plan, slots=4,
                         buckets=(4,), jit=False)
    reqs = [eng.submit(image=im) for im in imgs]
    with EG.taps(serve_evs.append):
        eng.run()

    assert [(e.path, e.kind, e.backend) for e in serve_evs] == \
           [(e.path, e.kind, e.backend) for e in direct_evs] == \
           [("c1", "conv", "emulated"), ("c2", "conv", "emulated"),
            ("fc1", "gemm", "emulated"), ("fc2", "gemm", "emulated")]
    for se, de in zip(serve_evs, direct_evs):
        np.testing.assert_array_equal(np.asarray(se.y), np.asarray(de.y))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.logits, np.asarray(direct[i]))


def test_bucket_padding_never_perturbs_real_rows():
    """3 requests into a 4-bucket: the pad row (a duplicate of a live
    image) must not change the live rows' quantization.  Duplicate rows
    are processed identically to their original, so they cannot raise a
    shared block max — unlike a zero image, which is only neutral while
    biases/BN shifts keep zero rows zero, the trained-model case below
    stresses exactly that."""
    params = small.lenet_init(KEY)
    # trained-model shape: nonzero biases make any pad row nonzero from
    # layer 2 on, where an EQ4 whole-matrix exponent could be perturbed
    for name in ("c1", "c2", "fc1", "fc2"):
        params[name]["b"] = jax.random.normal(
            jax.random.PRNGKey(len(name)), params[name]["b"].shape) * 0.5
    plan = EG.bind(params, EQ4)
    imgs = _images(3)
    direct = small.lenet_apply(plan.params, jnp.stack(imgs), plan)
    eng = CnnServeEngine(None, small.lenet_apply, plan, slots=4,
                         buckets=(1, 2, 4))
    reqs = [eng.submit(image=im) for im in imgs]
    eng.run()
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.logits, np.asarray(direct[i]))


def test_serve_sharded_mesh_bitexact():
    """The sharded deployment path (axis_rules + mesh, batch axis on
    "data") is the SAME code 1-device: outputs bit-identical."""
    params = small.lenet_init(KEY)
    plan = EG.bind(params, EQ4)
    imgs = _images(4)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = CnnServeEngine(None, small.lenet_apply, plan, slots=4,
                         buckets=(4,), mesh=mesh, rules=DEFAULT_RULES)
    reqs = [eng.submit(image=im) for im in imgs]
    eng.run()
    direct = small.lenet_apply(plan.params, jnp.stack(imgs), plan)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.logits, np.asarray(direct[i]))


# ---------------------------------------------------------------------------
# continuous batching / slot reuse
# ---------------------------------------------------------------------------

def test_more_requests_than_slots_drain():
    params = small.lenet_init(KEY)
    eng = CnnServeEngine(params, small.lenet_apply, EQ4, slots=2)
    reqs = [eng.submit(image=im) for im in _images(5)]
    done = eng.run()
    assert len(done) == 5
    assert all(r.done and r.logits.shape == (10,) for r in reqs)
    # single-request isolation: same image served alone gives same logits
    solo = CnnServeEngine(params, small.lenet_apply, EQ4, slots=1)
    r0 = solo.submit(image=reqs[0].image)
    solo.run()
    np.testing.assert_array_equal(r0.logits, reqs[0].logits)


def test_submit_validates_shapes():
    params = small.lenet_init(KEY)
    eng = CnnServeEngine(params, small.lenet_apply, EQ4, slots=2)
    eng.submit(image=jnp.zeros((28, 28, 1)))
    with pytest.raises(ValueError, match="shape"):
        eng.submit(image=jnp.zeros((32, 32, 1)))
    with pytest.raises(ValueError, match="image"):
        eng.submit(image=jnp.zeros((28, 28)))


# ---------------------------------------------------------------------------
# plan binding / reuse
# ---------------------------------------------------------------------------

def test_engines_share_jitted_forward_via_plan():
    """Bind once, serve many: two engines on one plan reuse ONE jitted
    callable (Plan.jit_forward cache) — no per-engine retracing."""
    params = small.lenet_init(KEY)
    plan = EG.bind(params, EQ4)
    e1 = CnnServeEngine(None, small.lenet_apply, plan, slots=2)
    e2 = CnnServeEngine(None, small.lenet_apply, plan, slots=8)
    assert e1._fwd is e2._fwd
    assert e1._fwd is plan.jit_forward(small.lenet_apply)
    # a different plan gets its own
    plan2 = EG.bind(params, EQ4)
    assert plan2.jit_forward(small.lenet_apply) is not e1._fwd


def test_strict_backend_rejects_at_admission():
    """An undeployable serving config (pallas backend, paper scheme it
    cannot honour) fails at engine CONSTRUCTION, not mid-traffic —
    whether the engine binds itself or receives a pre-bound plan."""
    import warnings as W
    from repro.engine.backends import BackendFallbackWarning
    params = small.lenet_init(KEY)
    with pytest.raises(BackendUnsupportedError):
        CnnServeEngine(params, small.lenet_apply,
                       EQ4.with_(backend="pallas"), strict_backend=True)
    # a pre-bound plan carrying downgraded sites is rejected too (the
    # Plan branch must not silently skip the strict check)
    with W.catch_warnings():
        W.simplefilter("ignore", BackendFallbackWarning)
        lax_plan = EG.bind(params, EQ4.with_(backend="pallas"))
    with pytest.raises(BackendUnsupportedError, match="downgraded"):
        CnnServeEngine(None, small.lenet_apply, lax_plan,
                       strict_backend=True)
    # a clean plan passes strict, and params alongside a plan is an error
    clean = EG.bind(params, EQ4)
    CnnServeEngine(None, small.lenet_apply, clean, strict_backend=True)
    with pytest.raises(ValueError, match="params=None"):
        CnnServeEngine(params, small.lenet_apply, clean)


def test_prequant_plan_serves_wire_format():
    params = small.lenet_init(KEY)
    eng = CnnServeEngine(params, small.lenet_apply, EQ4, slots=2,
                         prequant=True)
    assert EG.is_prequant(eng.plan.params["c1"]["w"])
    r = eng.submit(image=_images(1)[0])
    eng.run()
    assert r.done and np.isfinite(r.logits).all()


# ---------------------------------------------------------------------------
# model registry / multi-head models
# ---------------------------------------------------------------------------

def test_registry_covers_paper_models():
    assert {"vgg16", "resnet18", "resnet50", "googlenet"} <= set(MODELS)
    assert MODELS["vgg16"].apply is vgg.apply
    assert MODELS["lenet"].input_shape() == (28, 28, 1)


def test_googlenet_multi_head_serves_main_logits():
    """GoogLeNet serves its main (loss3) head: the registered apply
    builds and runs no aux head, and a tuple-returning apply (the three
    heads of ``googlenet.apply``) serves head 0, the same logits."""
    spec = MODELS["googlenet"]
    params = spec.init(KEY)
    assert "loss1" not in params and "loss2" not in params
    x = jax.random.normal(KEY, spec.input_shape())
    eng = CnnServeEngine(params, spec.apply, EQ4, slots=1)
    r = eng.submit(image=x)
    eng.run()
    direct = googlenet.apply(eng.plan.params, x[None], eng.plan,
                             with_aux=False)
    np.testing.assert_array_equal(r.logits, np.asarray(direct[0]))
    heads = googlenet.init(KEY, 10, width_mult=0.125)
    three = CnnServeEngine(heads, googlenet.apply, EQ4, slots=1)
    r3 = three.submit(image=x)
    three.run()
    main, _, _ = googlenet.apply(three.plan.params, x[None], three.plan)
    np.testing.assert_array_equal(r3.logits, np.asarray(main[0]))


def test_launcher_exits_nonzero_when_forward_raises(monkeypatch, capsys):
    """The engine completes a raising forward's requests with ``error``
    set (slots must not leak); the launcher must count those as failed,
    report 0 served, and exit non-zero."""
    from repro.launch import serve_cnn

    def boom(params, x, policy):
        raise RuntimeError("forward failed")

    lenet = dataclasses.replace(MODELS["lenet"], apply=boom)
    monkeypatch.setattr(serve_cnn, "MODELS", {"lenet": lenet})
    monkeypatch.setattr(serve_cnn, "enable_compile_cache", lambda: None)
    rc = serve_cnn.main(["--model", "lenet", "--requests", "3",
                         "--slots", "2"])
    out, err = capsys.readouterr()
    assert rc == 1
    assert "0 requests served" in out
    assert "3 of 3 requests failed" in err and "forward failed" in err


def test_vgg_reduced_through_engine():
    """A paper-model shape end to end through the serving stack."""
    spec = MODELS["vgg16"]
    params = spec.init(KEY)
    eng = CnnServeEngine(params, spec.apply, EQ4, slots=2)
    reqs = [eng.submit(image=jax.random.normal(jax.random.PRNGKey(i),
                                               spec.input_shape()))
            for i in range(3)]
    eng.run()
    assert all(r.done and r.logits.shape == (10,) for r in reqs)
