"""ISSUE 3: bound execution plans, engine taps, strict backend selection.

Key contracts:
  * ``engine.bind`` plans are BIT-IDENTICAL to the legacy per-call path
    on every backend (emulated, pallas, float), for GEMMs and convs;
  * backend downgrades are never silent: warn-once by default, raise
    with ``strict=True`` — surfaced at bind time and via ServeEngine;
  * policy rules naming unknown backends fail at bind time with the
    ``available_backends`` KeyError, not mid-forward;
  * policy-None convs consult the registered "float" backend (the same
    extension point GEMMs document);
  * taps observe the real datapath, are suppressed under jit tracing,
    and cost one list check when unregistered.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as EG
from repro.core import BFPPolicy, Scheme
from repro.engine import PolicyMap
from repro.engine.backends import (BackendFallbackWarning,
                                   BackendUnsupportedError)
from repro.models.cnn import resnet, small

KEY = jax.random.PRNGKey(0)
EQ4 = BFPPolicy(straight_through=False)
TILED = BFPPolicy(scheme=Scheme.TILED, block_k=128, straight_through=False)


# ---------------------------------------------------------------------------
# bind: bit-identical to the legacy per-call path
# ---------------------------------------------------------------------------

def test_bind_lenet_bitexact_vs_legacy():
    """Full bound pipeline (prequant + per-site dispatch) == legacy
    prequantize_cnn + per-call PolicyMap resolution, bit for bit."""
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    plan = EG.bind(params, EQ4)
    assert set(plan.sites) == {"c1", "c2", "fc1", "fc2"}
    assert plan.site("c1").kind == "conv" and plan.site("c1").prequantized
    assert plan.site("fc1").kind == "gemm"
    out_plan = small.lenet_apply(plan.params, x, plan)
    out_legacy = small.lenet_apply(EG.prequantize_cnn(params, EQ4), x, EQ4)
    np.testing.assert_array_equal(np.asarray(out_plan),
                                  np.asarray(out_legacy))


def test_bind_without_prequant_matches_inline():
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    plan = EG.bind(params, EQ4, prequantize=False)
    assert not plan.site("c1").prequantized
    np.testing.assert_array_equal(
        np.asarray(small.lenet_apply(plan.params, x, plan)),
        np.asarray(small.lenet_apply(params, x, EQ4)))


def test_bind_policymap_resnet_bitexact():
    """Mixed per-layer assignment (stem float, rest BFP) through a bound
    plan == the per-call PolicyMap path, across residual topology."""
    params = resnet.init(KEY, 18, 10, width_mult=0.25)
    x = jax.random.normal(KEY, (2, 32, 32, 3))
    pm = PolicyMap.of(("^stem", None), default=EQ4)
    plan = EG.bind(params, pm)
    assert plan.site("stem").policy is None
    assert not plan.site("stem").prequantized   # rule kept it float
    assert plan.site("blocks/0/c1").policy == EQ4
    out_plan = resnet.apply(plan.params, x, plan)
    out_legacy = resnet.apply(EG.prequantize_cnn(params, pm), x, pm)
    np.testing.assert_array_equal(np.asarray(out_plan),
                                  np.asarray(out_legacy))


def test_bind_gemm_pallas_bitexact():
    """The kernel path through a bound site == legacy pallas dispatch
    (kernel/oracle/core triangulation holds through plans)."""
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 32)) * 0.1
    x = jax.random.normal(KEY, (8, 256))
    pol = TILED.with_(backend="pallas")
    plan = EG.bind({"fc": {"w": w}}, pol)
    assert plan.site("fc").backend.name == "pallas"
    assert not plan.site("fc").fallback
    np.testing.assert_array_equal(
        np.asarray(plan.gemm(x, plan.params["fc"]["w"], path="fc")),
        np.asarray(EG.gemm(x, EG.prequantize_cnn({"fc": {"w": w}},
                                                 pol)["fc"]["w"], pol)))


def test_bind_conv_pallas_fused_bitexact():
    """Bound conv site keeps the fused implicit-im2col kernel."""
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 8, 16)) * 0.1
    x = jax.random.normal(KEY, (1, 6, 6, 8))
    pol = TILED.with_(backend="pallas")   # K = 4*4*8 = 128 = block_k
    plan = EG.bind({"conv1": {"w": w}}, pol)
    site = plan.site("conv1")
    assert site.kind == "conv" and site.backend.name == "pallas"
    out_plan = plan.conv2d(x, plan.params["conv1"]["w"], path="conv1",
                           stride=1, padding="SAME")
    wq = EG.prequantize_cnn({"conv1": {"w": w}}, pol)["conv1"]["w"]
    out_legacy = EG.conv2d(x, wq, pol, stride=1, padding="SAME")
    np.testing.assert_array_equal(np.asarray(out_plan),
                                  np.asarray(out_legacy))


def test_bind_compiles_one_program_per_weight_shape():
    """Prequantization runs one compiled program per weight shape, not
    one per operation: op by op, a full-width ResNet-50 or GoogLeNet bind
    compiled ~540 programs, minutes on a chip's empty compile cache."""
    params = resnet.init(KEY, 50, 10, width_mult=0.125,
                         stage_depths=(1, 1, 1, 1))
    pol = BFPPolicy(scheme=Scheme.TILED, block_k=None,
                    straight_through=False)
    shapes = {leaf.shape for path, leaf in
              jax.tree_util.tree_leaves_with_path(params)
              if jax.tree_util.keystr(path).endswith("['w']")}
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        plan = EG.bind(params, pol, tree="cnn", prequantize=True)
        jax.block_until_ready(plan.params)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert EG.is_prequant(plan.params["fc"]["w"])
    assert len(compiles) <= len(shapes), (len(compiles), len(shapes))


def test_plan_unbound_path_falls_back_per_call():
    """Paths bind never saw resolve against the original policy."""
    params = small.lenet_init(KEY)
    plan = EG.bind(params, PolicyMap.of(("^c1$", None), default=EQ4))
    x = jax.random.normal(KEY, (4, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 8)) * 0.1
    np.testing.assert_array_equal(
        np.asarray(plan.gemm(x, w, path="not/a/site")),
        np.asarray(EG.gemm(x, w, EQ4)))
    assert plan.resolve("c1") is None
    assert plan.resolve("not/a/site") == EQ4    # PolicyMap default


def test_plan_jit_closure_safe():
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    plan = EG.bind(params, EQ4)
    jitted = jax.jit(lambda p, xx: small.lenet_apply(p, xx, plan))
    np.testing.assert_array_equal(
        np.asarray(jitted(plan.params, x)),
        np.asarray(small.lenet_apply(plan.params, x, plan)))


def test_jit_forward_passes_params_as_arguments():
    """Plan.jit_forward hands the plan's arrays to the compiled program
    as arguments: bit-identical to jitting a closure over them, but the
    lowered program embeds no weight constant (a closure would put the
    whole model into every bucket's program and its cache key)."""
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    plan = EG.bind(params, EQ4)
    fwd = plan.jit_forward(small.lenet_apply)
    closure = jax.jit(lambda xx: small.lenet_apply(plan.params, xx, plan))
    np.testing.assert_array_equal(np.asarray(fwd(x)),
                                  np.asarray(closure(x)))
    lowered = fwd.lower(x)
    n_arrays = len(jax.tree_util.tree_leaves(plan.params))
    assert len(jax.tree_util.tree_leaves(lowered.args_info)) == n_arrays + 1
    assert 'dense<"0x' in closure.lower(x).as_text()   # embedded weights
    assert 'dense<"0x' not in lowered.as_text()


def test_jit_forward_refuses_batch_shared_exponent_on_mesh():
    """Under shard_map each device quantizes only its slice of the batch:
    exact for row-local blocks (TILED), but an EQ4 activation exponent
    spans the whole batch, so a multi-device batch axis is refused."""
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 1), ("data", "model"))
    plan = EG.bind(small.lenet_init(KEY), EQ4)
    with pytest.raises(ValueError, match="EQ2/EQ4"):
        plan.jit_forward(small.lenet_apply, mesh=mesh)


def test_plan_model_paths_restricts_and_extends():
    params = small.lenet_init(KEY)
    plan = EG.bind(params, EQ4, model_paths=["c1", ("extra/site", "gemm")])
    assert set(plan.sites) == {"c1", "extra/site"}
    assert plan.site("extra/site").policy == EQ4   # policy-only entry
    # the restriction scopes prequantization too: unbound sites keep
    # their float leaves
    assert EG.is_prequant(plan.params["c1"]["w"])
    assert not EG.is_prequant(plan.params["c2"]["w"])
    assert not EG.is_prequant(plan.params["fc1"]["w"])


# ---------------------------------------------------------------------------
# strict / warn-once backend selection (satellite 1)
# ---------------------------------------------------------------------------

def test_select_backend_strict_raises():
    w = jax.random.normal(KEY, (64, 8))
    with pytest.raises(BackendUnsupportedError, match="strict"):
        EG.select_backend(EQ4.with_(backend="pallas"), w, strict=True,
                          path="strict/site/a")


def test_select_backend_warns_once_per_site():
    w = jax.random.normal(KEY, (64, 8))
    pol = EQ4.with_(backend="pallas")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        be = EG.select_backend(pol, w, path="warn/site/unique1")
        assert be.name == "emulated"
        EG.select_backend(pol, w, path="warn/site/unique1")
    fallbacks = [r for r in rec
                 if issubclass(r.category, BackendFallbackWarning)]
    assert len(fallbacks) == 1   # once per site, not per call
    assert "pallas" in str(fallbacks[0].message)


def test_each_bind_warns_independently():
    """The warn-once dedup is per bind, not process-global: a later
    independently-constructed plan must surface its own downgrades."""
    params = small.lenet_init(KEY)
    pol = EQ4.with_(backend="pallas")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        EG.bind(params, pol)
        n1 = sum(issubclass(r.category, BackendFallbackWarning)
                 for r in rec)
        EG.bind(params, pol)
        n2 = sum(issubclass(r.category, BackendFallbackWarning)
                 for r in rec)
    assert n1 == 4          # one per site (c1, c2, fc1, fc2)
    assert n2 == 8          # the second bind warns again, not silently


def test_bind_strict_fails_loudly():
    """A serving config that requests a backend its policy can't run on
    must fail at bind, not drift onto the emulated path."""
    params = small.lenet_init(KEY)
    with pytest.raises(BackendUnsupportedError):
        EG.bind(params, EQ4.with_(backend="pallas"), strict=True)
    # non-strict: binds with the fallback recorded on the site
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendFallbackWarning)
        plan = EG.bind(params, EQ4.with_(backend="pallas"))
    assert plan.site("c1").fallback
    assert plan.site("c1").backend.name == "emulated"


def test_serve_engine_strict_backend():
    from repro.configs.base import reduced
    from repro.configs.registry import ARCHS
    from repro.models.lm import model as Mdl
    from repro.serve.engine import ServeEngine
    cfg = reduced(ARCHS["tinyllama-1.1b"])
    params = Mdl.init_params(cfg, KEY)
    with pytest.raises(BackendUnsupportedError):
        ServeEngine(params, cfg, slots=1, max_len=32,
                    policy=EQ4.with_(backend="pallas"),
                    strict_backend=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendFallbackWarning)
        eng = ServeEngine(params, cfg, slots=1, max_len=32, policy=EQ4)
    assert "attn/wq" in eng.plan.sites        # bound at admission time
    assert eng.plan.site("attn/wq").policy == EQ4


# ---------------------------------------------------------------------------
# PolicyMap edge cases + bind-time validation (satellite 3)
# ---------------------------------------------------------------------------

def test_policy_map_first_match_wins_on_overlap():
    p6 = BFPPolicy(l_w=6, l_i=6)
    p8 = BFPPolicy(l_w=8, l_i=8)
    pm = PolicyMap.of(("conv", p6), ("conv1", p8), default=None)
    assert pm.resolve("conv1_1") == p6        # both match; FIRST wins
    pm2 = PolicyMap.of(("conv1", p8), ("conv", p6), default=None)
    assert pm2.resolve("conv1_1") == p8       # order flipped, winner flips


def test_policy_map_none_path_resolution():
    p8 = BFPPolicy(l_w=8, l_i=8)
    pm = PolicyMap.of((".*", None), default=p8)
    # a None path never matches rules (even match-anything ones): default
    assert pm.resolve(None) == p8
    assert EG.resolve_policy(pm, None) == p8


def test_unknown_backend_in_rule_raises_at_bind_not_forward():
    """Even a rule that matches NO site must be validated at bind."""
    params = small.lenet_init(KEY)
    pm = PolicyMap.of(("^never_matches$", EQ4.with_(backend="cuda")),
                      default=EQ4)
    with pytest.raises(KeyError, match="unknown BFP backend"):
        EG.bind(params, pm)


# ---------------------------------------------------------------------------
# conv2d policy-None registry routing (satellite 2)
# ---------------------------------------------------------------------------

def test_conv_policy_none_consults_registered_float_backend():
    """A re-registered float backend with a fused conv must be used for
    policy-None convs (same extension point engine.gemm documents)."""
    x = jax.random.normal(KEY, (1, 8, 8, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.1
    calls = []
    orig = EG.get_backend("float")

    def my_conv(x, w, pol, stride, padding, key=None):
        calls.append((stride, padding))
        return EG.conv2d_im2col(x, w, pol, stride, padding, key)

    EG.register_backend("float", orig.matmul, orig.supports,
                        conv=my_conv,
                        conv_supports=lambda pol, w, s, p: True)
    try:
        out = EG.conv2d(x, w, None, stride=2, padding="VALID")
        assert calls == [(2, "VALID")], \
            "policy=None conv must dispatch via the float backend's conv"
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(EG.conv2d_im2col(x, w, None, 2, "VALID")),
            rtol=1e-6, atol=1e-6)
    finally:
        EG.register_backend("float", orig.matmul, orig.supports,
                            conv=orig.conv,
                            conv_supports=orig.conv_supports)


# ---------------------------------------------------------------------------
# taps
# ---------------------------------------------------------------------------

def test_taps_observe_every_site_in_order():
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    events = []
    with EG.taps(events.append):
        small.lenet_apply(params, x, EQ4)
    assert [(e.path, e.kind) for e in events] == \
        [("c1", "conv"), ("c2", "conv"), ("fc1", "gemm"), ("fc2", "gemm")]
    assert all(e.backend == "emulated" for e in events)
    assert events[0].stride == 1 and events[0].padding == "SAME"
    assert events[0].y.shape == (2, 28, 28, 16)
    assert all(e.y_float is None for e in events)   # not requested
    assert events[0].policy == EQ4


def test_taps_fire_through_bound_plans():
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    plan = EG.bind(params, EQ4, prequantize=False)
    events = []
    with EG.taps(events.append):
        small.lenet_apply(plan.params, x, plan)
    assert [e.path for e in events] == ["c1", "c2", "fc1", "fc2"]


def test_taps_suppressed_under_jit():
    params = small.lenet_init(KEY)
    x = jax.random.normal(KEY, (2, 28, 28, 1))
    events = []
    with EG.taps(events.append):
        jax.jit(lambda p, xx: small.lenet_apply(p, xx, EQ4))(params, x)
    assert events == []   # tracers never leak into taps


def test_taps_want_float_reference():
    x = jax.random.normal(KEY, (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.1
    events = []
    with EG.taps(events.append, want_float=True):
        EG.gemm(x, w, EQ4, path="g0")
    (ev,) = events
    np.testing.assert_array_equal(np.asarray(ev.y_float), np.asarray(x @ w))
    assert float(jnp.linalg.norm(ev.y - ev.y_float)) > 0   # BFP y differs


def test_taps_no_double_fire_on_im2col_route():
    """A conv lowered to im2col+GEMM emits ONE conv event, no gemm."""
    x = jax.random.normal(KEY, (1, 8, 8, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.1
    events = []
    with EG.taps(events.append):
        EG.conv2d(x, w, EQ4, path="conv0")   # emulated: im2col route
    assert [(e.path, e.kind) for e in events] == [("conv0", "conv")]
