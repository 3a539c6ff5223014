"""VGG-16 (Simonyan & Zisserman 2014) — the paper's main analysis vehicle.

Declarative sequential spec so the NSR analysis driver (paper Table 4) can
walk layer-by-layer.  ``width_mult``/``input_hw`` let tests run a reduced
config of the same family.  Convs execute through ``engine.conv2d`` —
the fused implicit-im2col Pallas kernel on the pallas backend (no
materialized patch matrix).
"""
from __future__ import annotations

from typing import List, Tuple

import jax

from repro.engine import PolicyLike
from repro.models.cnn import layers as L

# (type, *args): ("conv", name, out_ch) stride-1 SAME 3x3 / ("pool",) 2x2
# max / ("dense", name, out_dim) / ("flatten",) — ReLU after every conv and
# the first two dense layers, exactly VGG-16.
VGG16_CONV_PLAN: List[Tuple[str, int]] = [
    ("conv1_1", 64), ("conv1_2", 64), ("pool", 0),
    ("conv2_1", 128), ("conv2_2", 128), ("pool", 0),
    ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256), ("pool", 0),
    ("conv4_1", 512), ("conv4_2", 512), ("conv4_3", 512), ("pool", 0),
    ("conv5_1", 512), ("conv5_2", 512), ("conv5_3", 512), ("pool", 0),
]


def init(key, num_classes: int = 1000, in_ch: int = 3,
         width_mult: float = 1.0, input_hw: int = 224,
         fc_dim: int = 4096):
    params = {}
    ch = in_ch
    hw = input_hw
    for name, out in VGG16_CONV_PLAN:
        if name == "pool":
            hw //= 2
            continue
        out = max(8, int(out * width_mult))
        key, sub = jax.random.split(key)
        params[name] = L.conv2d_init(sub, ch, out, 3, 3)
        ch = out
    flat = ch * hw * hw
    key, k1, k2, k3 = jax.random.split(key, 4)
    params["fc6"] = L.dense_init(k1, flat, fc_dim)
    params["fc7"] = L.dense_init(k2, fc_dim, fc_dim)
    params["fc8"] = L.dense_init(k3, fc_dim, num_classes)
    return params


def apply(params, x: jax.Array, policy: PolicyLike = None) -> jax.Array:
    """Layer paths are the plan names ("conv1_1" ... "fc8"), so a
    PolicyMap rule like ("^conv1_1$", None) pins the first conv to float
    (paper Table-3 layer-wise experiments); ``engine.bind(params, pm)``
    binds the same paths once and rides this argument as a Plan."""
    for name, _ in VGG16_CONV_PLAN:
        if name == "pool":
            x = L.max_pool(x)
        else:
            x = L.relu(L.conv2d(params[name], x, 1, "SAME", policy,
                                path=name))
    x = x.reshape(x.shape[0], -1)
    x = L.relu(L.dense(params["fc6"], x, policy, path="fc6"))
    x = L.relu(L.dense(params["fc7"], x, policy, path="fc7"))
    return L.dense(params["fc8"], x, policy, path="fc8")


def conv_names() -> List[str]:
    return [n for n, _ in VGG16_CONV_PLAN if n != "pool"]
