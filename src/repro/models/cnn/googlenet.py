"""GoogLeNet (Szegedy et al. 2015, Table 1 and Figure 3) with the two
local response norms of the published net and, for training and the
paper's Table 3 analysis, the three classifier heads (loss1/loss2/loss3).
Inception branch convs (1x1 / 3x3 / 5x5, mixed per-branch shapes) all
route through ``engine.conv2d``; the norms, the pools and the concats
run in XLA, each under a ``jax.named_scope`` of its own (``norm1``,
``inc3a/pool``, ``inc3a/concat``, ...), so the compiled program's
``op_name`` ties every fusion to its block.  :func:`serve_apply` is the
served forward: the main head alone, as in a deployment."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.engine import PolicyLike, join_path
from repro.models.cnn import layers as L

# (name, out_1x1, red_3x3, out_3x3, red_5x5, out_5x5, pool_proj)
_INCEPTION = [
    ("3a", 64, 96, 128, 16, 32, 32),
    ("3b", 128, 128, 192, 32, 96, 64),
    ("pool", 0, 0, 0, 0, 0, 0),
    ("4a", 192, 96, 208, 16, 48, 64),
    ("4b", 160, 112, 224, 24, 64, 64),
    ("4c", 128, 128, 256, 24, 64, 64),
    ("4d", 112, 144, 288, 32, 64, 64),
    ("4e", 256, 160, 320, 32, 128, 128),
    ("pool", 0, 0, 0, 0, 0, 0),
    ("5a", 256, 160, 320, 32, 128, 128),
    ("5b", 384, 192, 384, 48, 128, 128),
]
_AUX_AFTER = {"4a": "loss1", "4d": "loss2"}


def _inception_init(key, in_ch, cfg, width_mult):
    _, o1, r3, o3, r5, o5, pp = cfg
    scale = lambda c: max(4, int(c * width_mult))
    k = jax.random.split(key, 6)
    return {
        "b1": L.conv2d_init(k[0], in_ch, scale(o1), 1, 1),
        "b3r": L.conv2d_init(k[1], in_ch, scale(r3), 1, 1),
        "b3": L.conv2d_init(k[2], scale(r3), scale(o3), 3, 3),
        "b5r": L.conv2d_init(k[3], in_ch, scale(r5), 1, 1),
        "b5": L.conv2d_init(k[4], scale(r5), scale(o5), 5, 5),
        "bp": L.conv2d_init(k[5], in_ch, scale(pp), 1, 1),
    }, scale(o1) + scale(o3) + scale(o5) + scale(pp)


def _inception(p, x, policy, path):
    cv = lambda name, inp: L.relu(L.conv2d(p[name], inp, 1, "SAME", policy,
                                           path=join_path(path, name)))
    b1 = cv("b1", x)
    b3 = cv("b3", cv("b3r", x))
    b5 = cv("b5", cv("b5r", x))
    with jax.named_scope(f"{path}/pool"):
        pooled = L.max_pool(x, 3, 1, "SAME")
    bp = cv("bp", pooled)
    with jax.named_scope(f"{path}/concat"):
        return jnp.concatenate([b1, b3, b5, bp], axis=-1)


def _aux_init(key, in_ch, num_classes, width_mult):
    k1, k2, k3 = jax.random.split(key, 3)
    mid = max(16, int(128 * width_mult))
    fc = max(32, int(1024 * width_mult))
    return {"conv": L.conv2d_init(k1, in_ch, mid, 1, 1),
            "fc1_in": mid * 16, "mid": mid,
            "fc1": L.dense_init(k2, mid * 16, fc),
            "fc2": L.dense_init(k3, fc, num_classes)}


def _aux(p, x, policy, path=None):
    # adaptive 4x4 average pool
    h, w = x.shape[1], x.shape[2]
    x = L.avg_pool(x, h // 4, h // 4) if h >= 4 else x
    x = L.relu(L.conv2d(p["conv"], x, 1, "SAME", policy,
                        path=join_path(path, "conv")))
    x = x.reshape(x.shape[0], -1)[:, :p["fc1_in"]]
    x = L.relu(L.dense(p["fc1"], x, policy, path=join_path(path, "fc1")))
    return L.dense(p["fc2"], x, policy, path=join_path(path, "fc2"))


def init(key, num_classes: int = 1000, in_ch: int = 3,
         width_mult: float = 1.0, with_aux: bool = True):
    """The conv and fc weights; the aux heads (loss1, loss2) only
    ``with_aux``."""
    scale = lambda c: max(8, int(c * width_mult))
    key, k1, k2, k3 = jax.random.split(key, 4)
    params = {"stem1": L.conv2d_init(k1, in_ch, scale(64), 7, 7),
              "stem2r": L.conv2d_init(k2, scale(64), scale(64), 1, 1),
              "stem2": L.conv2d_init(k3, scale(64), scale(192), 3, 3)}
    ch = scale(192)
    for cfg in _INCEPTION:
        if cfg[0] == "pool":
            continue
        key, sub = jax.random.split(key)
        params[f"inc{cfg[0]}"], ch_out = _inception_init(sub, ch, cfg,
                                                         width_mult)
        if with_aux and cfg[0] in _AUX_AFTER:
            key, sub = jax.random.split(key)
            params[_AUX_AFTER[cfg[0]]] = _aux_init(sub, ch_out, num_classes,
                                                   width_mult)
        ch = ch_out
    key, sub = jax.random.split(key)
    params["fc"] = L.dense_init(sub, ch, num_classes)
    return params


def apply(params, x: jax.Array, policy: PolicyLike = None,
          with_aux: bool = True):
    """Returns (loss3_logits, loss1_logits, loss2_logits) — the paper's
    three GoogLeNet columns — or, ``with_aux=False``, the loss3 logits
    alone (the tree then needs no aux heads).  Layer paths:
    "stem1|stem2r|stem2", "inc<name>/b1|b3r|b3|b5r|b5|bp",
    "loss1|loss2/conv|fc1|fc2", "fc"; ``policy`` is a PolicyLike (incl. a
    bound ``engine.Plan``)."""
    x = L.relu(L.conv2d(params["stem1"], x, 2, "SAME", policy,
                        path="stem1"))
    with jax.named_scope("pool1"):
        x = L.max_pool(x, 3, 2, "SAME")
    with jax.named_scope("norm1"):
        x = L.local_response_norm(x)
    x = L.relu(L.conv2d(params["stem2r"], x, 1, "SAME", policy,
                        path="stem2r"))
    x = L.relu(L.conv2d(params["stem2"], x, 1, "SAME", policy,
                        path="stem2"))
    with jax.named_scope("norm2"):
        x = L.local_response_norm(x)
    with jax.named_scope("pool2"):
        x = L.max_pool(x, 3, 2, "SAME")
    aux = {}
    pools = iter(("pool3", "pool4"))
    for cfg in _INCEPTION:
        if cfg[0] == "pool":
            with jax.named_scope(next(pools)):
                x = L.max_pool(x, 3, 2, "SAME")
            continue
        x = _inception(params[f"inc{cfg[0]}"], x, policy,
                       path=f"inc{cfg[0]}")
        if with_aux and cfg[0] in _AUX_AFTER:
            head = _AUX_AFTER[cfg[0]]
            aux[head] = _aux(params[head], x, policy, path=head)
    with jax.named_scope("pool5"):
        x = L.global_avg_pool(x)
    main = L.dense(params["fc"], x, policy, path="fc")
    return (main, aux["loss1"], aux["loss2"]) if with_aux else main


def serve_apply(params, x: jax.Array, policy: PolicyLike = None):
    """The served forward: the loss3 logits, with no aux head built or
    run (a deployed GoogLeNet, like Caffe's deploy net, has none)."""
    return apply(params, x, policy, with_aux=False)
