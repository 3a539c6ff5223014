"""Plain float32 reference of GoogLeNet (Inception v1), and its weights.

Written from Szegedy et al. (Table 1 for the shapes, Figure 3 for the
layer order) and the BVLC Caffe deploy net (the LRN parameters and the
pooling modes), with the departures the configuration file lists under
``assumed``: SAME padding on the 7x7/2 stem, no aux heads.  Every conv
carries a bias and a ReLU.  The two local response norms sum the squares
of ``size`` neighbouring channels, zero beyond the edges, and divide by
``(k + alpha/size * sum)^beta``; here the sum is taken over shifted
slices of a zero-padded copy.  ``init`` lays the weights out as the
program's ``models.cnn.googlenet`` tree expects them (conv weights HWIO,
the fc [in, out]), so the same arrays go to both.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from bench.draw import draw


def init(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """He-normal conv and fc weights, N(0, 0.01) biases; ``stem1``'s
    weights are scaled up by ``pixel_scale`` (see ``assumed``).  Call
    under ``jax.jit``."""
    leaves = []
    for s in sites(cfg):
        if s["kind"] == "conv":
            shape = (s["k"], s["k"], s["cin"], s["cout"])
            fan_in = s["k"] ** 2 * s["cin"]
        else:
            shape, fan_in = (s["cin"], s["cout"]), s["cin"]
        scale = cfg["pixel_scale"] if s["name"] == "stem1" else 1.0
        leaves += [(shape, "normal", scale * (2.0 / fan_in) ** .5, 0.0),
                   ((s["cout"],), "normal", 0.01, 0.0)]
    arrays = iter(draw(key, leaves))
    params: Dict[str, Any] = {}
    for s in sites(cfg):
        node = params
        *groups, leaf = s["name"].split("/")
        for g in groups:
            node = node.setdefault(g, {})
        node[leaf] = {"w": next(arrays), "b": next(arrays)}
    return params


def program_params(params: Dict[str, Any], cfg: Dict[str, Any]):
    """The tree the program is handed: the same arrays.  The program's
    served forward is first traced on it, on shapes alone and in float,
    so that a program which cannot serve this tree (one whose forward
    reads aux heads the tree does not hold) fails here, in seconds, and
    not in the warm-up after every weight has been bound."""
    from repro.models.cnn import MODELS
    apply = MODELS[cfg["program_model"]].apply
    x = jax.ShapeDtypeStruct((1, cfg["input_hw"], cfg["input_hw"],
                              cfg["in_ch"]), jnp.float32)
    jax.eval_shape(lambda p, x: apply(p, x, None), params, x)
    return params


def _conv(p, x, stride=1):
    x = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.maximum(x + p["b"], 0.0)


def _max_pool(x, window, stride):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, stride, stride, 1), "SAME")


def lrn(x, cfg: Dict[str, Any]):
    """Caffe's across-channel LRN."""
    n, alpha = cfg["lrn"]["size"], cfg["lrn"]["alpha"]
    half, c = n // 2, x.shape[-1]
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, n - 1 - half)))
    total = sum(sq[..., i:i + c] for i in range(n))
    return x / (cfg["lrn"]["k"] + alpha / n * total) ** cfg["lrn"]["beta"]


def stem(params: Dict[str, Any], x: jax.Array, cfg: Dict[str, Any]):
    """conv1 7x7/2 and max pool 3x3/2: norm1's input."""
    pool = cfg["max_pool"]
    x = _conv(params["stem1"], x, cfg["stem1"]["stride"])
    return _max_pool(x, pool["window"], pool["stride"])


def forward(params: Dict[str, Any], x: jax.Array,
            cfg: Dict[str, Any]) -> jax.Array:
    """Logits [B, classes] in float32.  Run it under
    ``jax.default_matmul_precision("highest")``."""
    pool = cfg["max_pool"]
    x = lrn(stem(params, x, cfg), cfg)
    x = _conv(params["stem2"], _conv(params["stem2r"], x))
    x = _max_pool(lrn(x, cfg), pool["window"], pool["stride"])
    for row in cfg["inception"]:
        if row[0] == "pool":
            x = _max_pool(x, pool["window"], pool["stride"])
            continue
        p = params[f"inc{row[0]}"]
        x = jnp.concatenate([
            _conv(p["b1"], x),
            _conv(p["b3"], _conv(p["b3r"], x)),
            _conv(p["b5"], _conv(p["b5r"], x)),
            _conv(p["bp"], _max_pool(x, 3, 1))], axis=-1)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]


def _out(hw, stride):
    return -(-hw // stride)     # SAME padding


def sites(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv and the fc of one image, in order, with its shapes."""
    def conv(name, hw, cin, cout, k, stride=1):
        ho = _out(hw, stride)
        return dict(name=name, kind="conv", h=hw, w=hw, cin=cin, cout=cout,
                    k=k, stride=stride, ho=ho, wo=ho)

    pool_s = cfg["max_pool"]["stride"]
    s1, s2r, s2 = cfg["stem1"], cfg["stem2r"], cfg["stem2"]
    hw = cfg["input_hw"]
    out = [conv("stem1", hw, cfg["in_ch"], s1["out"], s1["kernel"],
                s1["stride"])]
    hw = _out(_out(hw, s1["stride"]), pool_s)
    out.append(conv("stem2r", hw, s1["out"], s2r["out"], s2r["kernel"]))
    out.append(conv("stem2", hw, s2r["out"], s2["out"], s2["kernel"]))
    hw, ch = _out(hw, pool_s), s2["out"]
    for row in cfg["inception"]:
        if row[0] == "pool":
            hw = _out(hw, pool_s)
            continue
        name, o1, r3, o3, r5, o5, pp = row
        for b, cin, cout, k in (("b1", ch, o1, 1), ("b3r", ch, r3, 1),
                                ("b3", r3, o3, 3), ("b5r", ch, r5, 1),
                                ("b5", r5, o5, 5), ("bp", ch, pp, 1)):
            out.append(conv(f"inc{name}/{b}", hw, cin, cout, k))
        ch = o1 + o3 + o5 + pp
    out.append(dict(name="fc", kind="fc", cin=ch, cout=cfg["num_classes"]))
    return out


def param_count(cfg: Dict[str, Any]) -> int:
    """Conv and fc weights and biases."""
    n = 0
    for s in sites(cfg):
        k2 = s["k"] ** 2 if s["kind"] == "conv" else 1
        n += k2 * s["cin"] * s["cout"] + s["cout"]
    return n
