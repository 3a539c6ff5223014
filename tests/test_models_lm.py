"""LM zoo: per-arch reduced-config smoke tests (deliverable f) +
forward/decode consistency + family-specific invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.core.policy import PAPER_DEFAULT
from repro.models.lm import common as C, model as Mdl

KEY = jax.random.PRNGKey(0)
ALL_ARCHS = sorted(ARCHS)


def _setup(name, **kw):
    cfg = reduced(ARCHS[name], **kw)
    params = Mdl.init_params(cfg, KEY)
    return cfg, params


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_smoke_forward_and_train_step(name):
    """One forward + one train step on CPU: output shapes + no NaNs."""
    cfg, params = _setup(name)
    toks = jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size)
    enc = (jax.random.normal(KEY, (2, cfg.enc_seq_stub, cfg.d_model))
           if cfg.is_encdec else None)
    logits, aux = Mdl.forward(params, cfg, toks, enc_feats=enc)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    from repro.train.step import init_state, make_train_step
    state = init_state(cfg, KEY)
    step = jax.jit(make_train_step(cfg))
    targets = jnp.roll(toks, -1, 1)
    state2, metrics = step(state, (toks, targets))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(state2.step) == 1


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_decode_matches_forward(name):
    """KV-cache / recurrent-state decode == full forward, token by token."""
    kw = {}
    cfg = reduced(ARCHS[name])
    if cfg.is_moe:   # capacity drops are fwd-only; disable for equivalence
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    params = Mdl.init_params(cfg, KEY)
    B, S = 2, 16
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    enc = (jax.random.normal(KEY, (B, cfg.enc_seq_stub, cfg.d_model))
           if cfg.is_encdec else None)
    logits_f, _ = Mdl.forward(params, cfg, toks, enc_feats=enc)
    cache = Mdl.init_cache(cfg, B, max_len=64, dtype=jnp.float32)
    if cfg.is_encdec:
        cache["enc_out"] = Mdl.prefill_encoder(params, cfg, enc)
    step = jax.jit(lambda c, t, p: Mdl.decode_step(params, cfg, c, t, p))
    outs = []
    for t in range(S):
        lg, cache = step(cache, toks[:, t:t + 1], jnp.asarray(t, jnp.int32))
        outs.append(lg)
    logits_d = jnp.concatenate(outs, axis=1)
    scale = float(jnp.max(jnp.abs(logits_f))) + 1e-9
    assert float(jnp.max(jnp.abs(logits_f - logits_d))) / scale < 1e-4


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_bfp_forward(name):
    """Every arch runs with the paper's BFP datapath in all linears."""
    cfg, params = _setup(name)
    toks = jax.random.randint(KEY, (1, 32), 0, cfg.vocab_size)
    enc = (jax.random.normal(KEY, (1, cfg.enc_seq_stub, cfg.d_model))
           if cfg.is_encdec else None)
    lf, _ = Mdl.forward(params, cfg, toks, enc_feats=enc)
    lq, _ = Mdl.forward(params, cfg, toks, enc_feats=enc,
                        policy=PAPER_DEFAULT.with_(straight_through=False))
    assert bool(jnp.all(jnp.isfinite(lq)))
    rel = float(jnp.linalg.norm(lq - lf) / (jnp.linalg.norm(lf) + 1e-9))
    # 8-bit BFP stays close to float end-to-end.  MoE archs get a looser
    # bound: quantization can flip discrete top-k routing decisions, which
    # perturbs logits beyond the pure datapath error (~0.16 measured).
    bound = 0.2 if cfg.is_moe else 0.15
    assert rel < bound, rel


def test_causality():
    """Changing a future token must not affect past logits (dense arch)."""
    cfg, params = _setup("tinyllama-1.1b")
    toks = jax.random.randint(KEY, (1, 32), 0, cfg.vocab_size)
    l1, _ = Mdl.forward(params, cfg, toks)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
    l2, _ = Mdl.forward(params, cfg, toks2)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                               np.asarray(l2[:, :-1]), atol=1e-5)


def test_swa_matches_masked_attention():
    """Chunked sliding-window attention == full attention with band mask."""
    cfg = reduced(ARCHS["mixtral-8x7b"])
    cfg = dataclasses.replace(cfg, sliding_window=32)
    p = C.attention_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 128, cfg.d_model))  # S = 4*W -> chunked
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    out_chunked = C.attention(p, cfg, x, pos, None)
    cfg_small = dataclasses.replace(cfg, sliding_window=32)
    # force the masked-dense path by lying about the threshold
    q, k, v = C._qkv(p, cfg_small, x, x, None)
    q = C._apply_rope(cfg_small, q, pos)
    k = C._apply_rope(cfg_small, k, pos)
    mask = C._causal_mask(128, 32)[None, None, None]
    out_dense = C._sdpa(q, k, v, cfg_small, mask)
    out_dense = C.linear(p["wo"], out_dense.reshape(2, 128, -1), None)
    np.testing.assert_allclose(np.asarray(out_chunked),
                               np.asarray(out_dense), atol=2e-4)


def test_flash_matches_dense():
    cfg = reduced(ARCHS["mistral-nemo-12b"])
    p = C.attention_init(KEY, cfg)
    x = jax.random.normal(KEY, (1, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (1, 64))
    q, k, v = C._qkv(p, cfg, x, x, None)
    out_flash = C._flash_sdpa(q, k, v, cfg, causal=True, chunk=16)
    mask = C._causal_mask(64, None)[None, None, None]
    out_dense = C._sdpa(q, k, v, cfg, mask)
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_dense), atol=2e-4)


def test_mrope_text_equals_rope():
    """qwen2-vl M-RoPE with equal (t,h,w) ids == standard RoPE."""
    x = jax.random.normal(KEY, (2, 16, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    r1 = C.rope(x, pos, 10000.0)
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 16))
    r2 = C.mrope(x, pos3, 10000.0, (6, 5, 5))
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-5)


def test_rwkv_state_decay():
    """RWKV-6: with zero input-keys the WKV state must decay toward 0."""
    from repro.models.lm import rwkv6 as R
    cfg = reduced(ARCHS["rwkv6-3b"])
    p = R.time_mix_init(KEY, cfg)
    B = 1
    S0 = jnp.ones((B, cfg.n_heads, cfg.dh, cfg.dh))
    x = jnp.zeros((B, 1, cfg.d_model))
    _, (_, S1) = R.time_mix_decode(p, cfg, x, (jnp.zeros((B, cfg.d_model)),
                                               S0))
    assert float(jnp.max(jnp.abs(S1))) <= float(jnp.max(jnp.abs(S0))) + 1e-3


def test_moe_capacity_drops_counted():
    """Oversubscribed experts drop tokens (capacity factor semantics)."""
    from repro.models.lm import moe as M
    cfg = dataclasses.replace(reduced(ARCHS["olmoe-1b-7b"]),
                              capacity_factor=0.25)
    p = M.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    out, aux = M.moe_apply(p, cfg, x)
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))
    assert float(aux) > 0.5  # aux loss active


def test_param_count_matches_analytic():
    """Analytic 6ND count matches actual leaves within 5% (dense arch)."""
    cfg, params = _setup("tinyllama-1.1b")
    analytic = cfg.param_count()
    actual = Mdl.param_count(params)
    assert abs(analytic - actual) / actual < 0.05
