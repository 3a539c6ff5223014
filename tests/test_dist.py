"""repro.dist: logical-axis sharding, spec trees, gradient compression."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.dist import compress, sharding, specs
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(0)


def test_shard_identity_without_context():
    x = jax.random.normal(KEY, (4, 8, 16))
    y = sharding.shard(x, "batch", "seq", "embed")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shard_applies_constraint_inside_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jax.random.normal(KEY, (4, 16))
    with sharding.axis_rules(sharding.DEFAULT_RULES, mesh):
        y = sharding.shard(x, "batch", "ffn")
        # indivisible dim drops to replicated instead of failing
        z = sharding.shard(jnp.ones((3, 5)), "batch", "ffn")
    assert sharding.current_rules() is None        # context restored
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert z.shape == (3, 5)


def test_indivisible_rule_warns_once_per_rule():
    """ISSUE 4 satellite: dropping a rule on a non-divisible dim is no
    longer silent — one ShardingRuleDropped per rule, not per call, so
    production misconfigs surface without flooding the serving loop.
    (Unit-tested against the lowering helper with synthetic axis sizes:
    real multi-device meshes are not constructible in the 1-CPU tier-1
    environment.)"""
    sizes = {"data": 4, "model": 2}
    rules = {"batch": "data", "ffn": "model", "experts": ("data", "model")}
    sharding._DROP_WARNED.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p1 = sharding.resolve_spec(rules, sizes, (6, 7), ("batch", "ffn"))
        p2 = sharding.resolve_spec(rules, sizes, (6, 7), ("batch", "ffn"))
        p3 = sharding.resolve_spec(rules, sizes, (9,), ("experts",))
    assert p1 == (None, None) == p2          # dropped -> replicated
    assert p3 == (None,)                     # tuple-axis rule (size 8)
    drops = [r for r in rec
             if issubclass(r.category, sharding.ShardingRuleDropped)]
    assert len(drops) == 3                   # once per RULE, not per call
    assert any("batch" in str(d.message) and "'data'" in str(d.message)
               for d in drops)
    # the dedup is per (rule, geometry): the SAME rule dropped at a
    # DIFFERENT dim (smoke warm-up then misconfigured prod mesh in one
    # process) must warn again, not stay muted
    with warnings.catch_warnings(record=True) as rec_geo:
        warnings.simplefilter("always")
        sharding.resolve_spec(rules, sizes, (1001,), ("batch",))
    assert [r for r in rec_geo
            if issubclass(r.category, sharding.ShardingRuleDropped)]
    # divisible dims still lower to their physical axes
    assert sharding.resolve_spec(rules, sizes, (8, 4),
                                 ("batch", "ffn")) == ("data", "model")
    # unknown / unnamed axes replicate silently (no rule -> no warning)
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        assert sharding.resolve_spec(rules, sizes, (5, 5),
                                     ("nope", None)) == (None, None)
    assert not [r for r in rec2
                if issubclass(r.category, sharding.ShardingRuleDropped)]


def test_param_and_cache_specs_structure():
    from repro.models.lm import model as Mdl
    cfg = reduced(ARCHS["tinyllama-1.1b"])
    mesh = make_mesh((1, 1), ("data", "model"))
    params_sds = jax.eval_shape(lambda: Mdl.init_params(cfg, KEY))
    pspecs = specs.param_specs(cfg, params_sds, mesh)
    assert jax.tree_util.tree_structure(pspecs) == \
        jax.tree_util.tree_structure(params_sds)
    assert all(isinstance(s, P)
               for s in jax.tree_util.tree_leaves(pspecs))
    cache_sds = jax.eval_shape(lambda: Mdl.init_cache(cfg, 4, 64))
    cspecs = specs.cache_specs(cfg, cache_sds, mesh)
    assert jax.tree_util.tree_structure(cspecs) == \
        jax.tree_util.tree_structure(cache_sds)


def test_specs_lower_under_jit():
    """The spec trees are shardings ``jax.jit`` accepts: a reduced
    tinyllama train step (parameters and AdamW moments under
    ``param_specs``) and a decode step (the cache under ``cache_specs``)
    lower on a (1, 1) mesh under ``axis_rules``, without device work."""
    from jax.sharding import NamedSharding
    from repro.models.lm import model as Mdl
    from repro.optim import optimizers as opt
    from repro.train.step import TrainState, make_train_step
    cfg = reduced(ARCHS["tinyllama-1.1b"])
    mesh = make_mesh((1, 1), ("data", "model"))

    def named(tree):
        return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), tree,
                                      is_leaf=lambda x: isinstance(x, P))

    params = jax.eval_shape(lambda: Mdl.init_params(cfg, KEY))
    pspecs = specs.param_specs(cfg, params, mesh)
    state = TrainState(params=params,
                       opt_state=jax.eval_shape(opt.adamw_init, params),
                       step=jax.ShapeDtypeStruct((), jnp.int32))
    sspecs = TrainState(params=pspecs,
                        opt_state=opt.OptState(step=P(), mu=pspecs,
                                               nu=pspecs),
                        step=P())
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    cache = jax.eval_shape(lambda: Mdl.init_cache(cfg, 4, 64))
    cspecs = specs.cache_specs(cfg, cache, mesh)
    rows = P("data", None)
    with sharding.axis_rules(sharding.DEFAULT_RULES, mesh):
        train = jax.jit(make_train_step(cfg, opt.constant_schedule(1e-4)),
                        in_shardings=named((sspecs, (rows, rows))),
                        out_shardings=(named(sspecs), None)
                        ).lower(state, (tokens, tokens))
        decode = jax.jit(
            lambda p, c, t, pos: Mdl.decode_step(p, cfg, c, t, pos),
            in_shardings=named((pspecs, cspecs, rows, P())),
            out_shardings=(None, named(cspecs)),
        ).lower(params, cache, jax.ShapeDtypeStruct((4, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    for low, tree in ((train, (state, (tokens, tokens))),
                      (decode, (params, cache))):
        assert "sharding" in low.as_text()
        n_in = len(jax.tree_util.tree_leaves(low.args_info))
        assert n_in >= len(jax.tree_util.tree_leaves(tree))


def test_quantize_leaf_shapes_and_snr():
    g = jax.random.normal(KEY, (1000,))            # non-multiple of block
    q = compress.quantize_leaf(g, 8)
    assert q.shape == g.shape and q.dtype == g.dtype
    snr = 10 * np.log10(float(jnp.sum(g ** 2) / jnp.sum((q - g) ** 2)))
    assert snr > 30
    ints = jnp.arange(5)
    np.testing.assert_array_equal(np.asarray(compress.quantize_leaf(ints, 8)),
                                  np.asarray(ints))   # non-float passthrough


def test_packed_allreduce_matches_in_graph_model():
    """ISSUE 8 satellite: the REAL packed-bytes all-reduce is BIT-EXACT
    to the jit-safe in-graph compressor model, per worker, residuals
    included — the wire protocol IS the training step's arithmetic."""
    W, bits = 3, 6
    keys = jax.random.split(KEY, 4)
    grads = {"a": jax.random.normal(keys[0], (W, 600)) * 0.1,
             "b": {"c": jax.random.normal(keys[1], (W, 32, 8))},
             "n": jnp.arange(3)}                  # non-float passthrough
    residual = {"a": jax.random.normal(keys[2], (W, 600)) * 0.01,
                "b": {"c": jnp.zeros((W, 32, 8))},
                "n": jnp.arange(3)}

    mean, res, n_bytes = compress.packed_allreduce(grads, residual, bits)

    _, transform = compress.make_compressor(bits)
    q_ref, r_ref = jax.vmap(transform)(
        {"a": grads["a"], "b": grads["b"]},
        {"a": residual["a"], "b": residual["b"]})
    np.testing.assert_array_equal(np.asarray(mean["a"]),
                                  np.asarray(jnp.mean(q_ref["a"], 0)))
    np.testing.assert_array_equal(np.asarray(mean["b"]["c"]),
                                  np.asarray(jnp.mean(q_ref["b"]["c"], 0)))
    np.testing.assert_array_equal(np.asarray(res["a"]),
                                  np.asarray(r_ref["a"]))
    np.testing.assert_array_equal(np.asarray(mean["n"]),
                                  np.asarray(grads["n"]))
    # byte accounting: serialized container sizes, all workers and leaves
    expect = W * (compress.pack_leaf(grads["a"][0], bits).nbytes
                  + compress.pack_leaf(grads["b"]["c"][0], bits).nbytes)
    assert n_bytes == expect


def test_packed_allreduce_error_feedback_converges():
    """compress -> all-reduce -> decompress with residual carry: the
    accumulated compressed mean matches the uncompressed mean within the
    wire quantization bound (EF makes the bias vanish across steps)."""
    W, steps = 2, 30
    g = {"w": jax.random.normal(KEY, (W, 512)) * 0.1}
    residual = jax.tree_util.tree_map(jnp.zeros_like, g)
    true_mean = jnp.mean(g["w"], 0)
    acc = jnp.zeros_like(true_mean)
    for _ in range(steps):
        mean, residual, _ = compress.packed_allreduce(g, residual, bits=4)
        acc = acc + mean["w"]
    rel = float(jnp.linalg.norm(acc - steps * true_mean) /
                jnp.linalg.norm(steps * true_mean))
    assert rel < 0.05, rel
    # single-step contract: each worker's residual is exactly the wire
    # quantization error of its EF input, so a step's deviation from the
    # true mean is bounded by the mean wire quantization error
    r0 = jax.tree_util.tree_map(jnp.zeros_like, g)
    mean1, r1, _ = compress.packed_allreduce(g, r0, bits=4)
    qerr = jnp.stack([g["w"][wi] - compress.quantize_leaf(g["w"][wi], 4)
                      for wi in range(W)])
    np.testing.assert_array_equal(np.asarray(r1["w"]), np.asarray(qerr))
    np.testing.assert_allclose(np.asarray(mean1["w"] - true_mean),
                               np.asarray(-jnp.mean(qerr, 0)), atol=1e-7)


def test_error_feedback_tree():
    init_fn, transform = compress.make_compressor(bits=4)
    tree = {"a": jax.random.normal(KEY, (256,)) * 0.1,
            "b": {"c": jax.random.normal(jax.random.PRNGKey(1), (64, 4))}}
    res = init_fn(tree)
    acc = jax.tree_util.tree_map(jnp.zeros_like, tree)
    for _ in range(30):
        q, res = transform(tree, res)
        acc = jax.tree_util.tree_map(jnp.add, acc, q)
    for leaf, ref in zip(jax.tree_util.tree_leaves(acc),
                         jax.tree_util.tree_leaves(tree)):
        rel = float(jnp.linalg.norm(leaf - 30 * ref) /
                    (jnp.linalg.norm(30 * ref) + 1e-9))
        assert rel < 0.05, rel
