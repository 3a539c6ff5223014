"""Plain float32 reference of VGG-16 (configuration D), and its weights.

Written from the paper, independent of the program under test: NHWC
images, 3x3 stride-1 SAME convs with bias and ReLU, 2x2 stride-2 max
pools, flatten in (H, W, C) order, fc-ReLU, fc-ReLU, fc.  ``init`` lays
the weights out as the program's ``models.cnn`` tree expects them (conv
weights HWIO, dense weights [in, out]), so the same arrays go to both.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from bench.draw import draw


def init(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """He-normal weights, N(0, 0.01) biases.  Call under ``jax.jit``."""
    leaves = []
    for s in sites(cfg):
        shape = ((3, 3, s["cin"], s["cout"]) if s["kind"] == "conv"
                 else (s["cin"], s["cout"]))
        fan_in = shape[0] * (shape[1] * shape[2] if len(shape) == 4 else 1)
        leaves += [(shape, "normal", (2.0 / fan_in) ** .5, 0.0),
                   ((s["cout"],), "normal", 0.01, 0.0)]
    arrays = iter(draw(key, leaves))
    return {s["name"]: {"w": next(arrays), "b": next(arrays)}
            for s in sites(cfg)}


def program_params(params: Dict[str, Any], cfg: Dict[str, Any]):
    """The tree the program is handed: the same arrays."""
    return params


def forward(params: Dict[str, Any], x: jax.Array,
            cfg: Dict[str, Any]) -> jax.Array:
    """Logits [B, classes] in float32.  Run it under
    ``jax.default_matmul_precision("highest")``."""
    for name, _ in cfg["conv_plan"]:
        if name == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        p = params[name]
        x = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jnp.maximum(x + p["b"], 0.0)
    x = x.reshape(x.shape[0], -1)
    for name in ("fc6", "fc7"):
        x = jnp.maximum(x @ params[name]["w"] + params[name]["b"], 0.0)
    return x @ params["fc8"]["w"] + params["fc8"]["b"]


def sites(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv and fc of one image, in order, with its shapes."""
    out: List[Dict[str, Any]] = []
    ch, hw = cfg["in_ch"], cfg["input_hw"]
    for name, c in cfg["conv_plan"]:
        if name == "pool":
            hw //= 2
            continue
        out.append(dict(name=name, kind="conv", h=hw, w=hw, cin=ch,
                        cout=c, k=3, stride=1, ho=hw, wo=hw))
        ch = c
    dims = [ch * hw * hw, *cfg["fc_dims"], cfg["num_classes"]]
    for name, din, dout in zip(("fc6", "fc7", "fc8"), dims, dims[1:]):
        out.append(dict(name=name, kind="fc", cin=din, cout=dout))
    return out


def param_count(cfg: Dict[str, Any]) -> int:
    n = 0
    for s in sites(cfg):
        k2 = s["k"] ** 2 if s["kind"] == "conv" else 1
        n += k2 * s["cin"] * s["cout"] + s["cout"]
    return n
