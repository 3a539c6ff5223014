"""Plain float32 reference of ResNet-50, and its weights.

Written from He et al. (Table 1, 50-layer column) with the departures the
configuration file lists under ``assumed``: stride 2 on the bottleneck's
3x3 conv, SAME padding.  Each conv carries a bias and is followed by
inference-form batch norm (running mean and variance, eps 1e-5); ReLU
after every conv-BN but the last of a bottleneck and its projection,
which are summed and then ReLU'd.  ``init`` lays the weights out as the
program's ``models.cnn.resnet`` tree expects them.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.draw import draw

EPS = 1e-5


def _blocks(cfg) -> List[Tuple[int, int, int, bool]]:
    """(in_ch, mid_ch, stride, has_projection) of every bottleneck."""
    out, ch = [], cfg["base_width"]
    for si, n in enumerate(cfg["stage_depths"]):
        mid = cfg["base_width"] * 2 ** si
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            cout = mid * cfg["expansion"]
            out.append((ch, mid, stride, stride != 1 or ch != cout))
            ch = cout
    return out


def init(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """He-normal conv and fc weights, N(0, 0.01) biases, batch norm as the
    configuration's ``assumed`` says.  Array leaves only; call under
    ``jax.jit``."""
    leaves = []
    for s in sites(cfg):
        n = s["cout"]
        if s["kind"] == "conv":
            fan_in = s["k"] ** 2 * s["cin"]
            leaves += [((s["k"], s["k"], s["cin"], n), "normal",
                        (2.0 / fan_in) ** .5, 0.0),
                       ((n,), "normal", 0.01, 0.0),     # conv bias
                       ((n,), "uniform", 1.0, 0.5),     # gamma
                       ((n,), "normal", 0.1, 0.0),      # beta
                       ((n,), "normal", 0.1, 0.0),      # running mean
                       ((n,), "uniform", 1.0, 0.5)]     # running var
        else:
            leaves += [((s["cin"], n), "normal", (2.0 / s["cin"]) ** .5, 0.0),
                       ((n,), "normal", 0.01, 0.0)]
    arrays = iter(draw(key, leaves))
    params: Dict[str, Any] = {"blocks": [{} for _ in _blocks(cfg)]}
    for s in sites(cfg):
        w, b = next(arrays), next(arrays)
        if s["kind"] != "conv":
            params[s["name"]] = {"w": w, "b": b}
            continue
        p = {"conv": {"w": w, "b": b},
             "bn": {k: next(arrays) for k in ("gamma", "beta", "mean",
                                              "var")}}
        path = s["name"].split("/")
        if len(path) == 1:
            params[path[0]] = p
        else:
            params["blocks"][int(path[1])][path[2]] = p
    return params


def program_params(params: Dict[str, Any], cfg: Dict[str, Any]):
    """The tree the program is handed: the same arrays, plus the static
    ``meta`` entry (depth, stage depths, bottleneck) its apply reads."""
    return {**params, "meta": (50, tuple(cfg["stage_depths"]), True)}


def _conv_bn(p, x, stride, relu):
    x = jax.lax.conv_general_dilated(
        x, p["conv"]["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["conv"]["b"]
    bn = p["bn"]
    x = (x - bn["mean"]) / jnp.sqrt(bn["var"] + EPS) * bn["gamma"] \
        + bn["beta"]
    return jnp.maximum(x, 0.0) if relu else x


def forward(params: Dict[str, Any], x: jax.Array,
            cfg: Dict[str, Any]) -> jax.Array:
    """Logits [B, classes] in float32.  Run it under
    ``jax.default_matmul_precision("highest")``."""
    x = _conv_bn(params["stem"], x, cfg["stem"]["stride"], True)
    w, s = cfg["max_pool"]["window"], cfg["max_pool"]["stride"]
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, w, w, 1),
                              (1, s, s, 1), "SAME")
    for p, (_, _, stride, proj) in zip(params["blocks"], _blocks(cfg)):
        h = _conv_bn(p["c1"], x, 1, True)
        h = _conv_bn(p["c2"], h, stride, True)
        h = _conv_bn(p["c3"], h, 1, False)
        sc = _conv_bn(p["proj"], x, stride, False) if proj else x
        x = jnp.maximum(h + sc, 0.0)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]


def _out(hw, stride):
    return -(-hw // stride)     # SAME padding


def sites(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv and the fc of one image, in order, with its shapes."""
    def conv(name, hw, cin, cout, k, stride):
        ho = _out(hw, stride)
        return dict(name=name, kind="conv", h=hw, w=hw, cin=cin, cout=cout,
                    k=k, stride=stride, ho=ho, wo=ho)

    hw = cfg["input_hw"]
    st = cfg["stem"]
    out = [conv("stem", hw, cfg["in_ch"], cfg["base_width"], st["kernel"],
                st["stride"])]
    hw = _out(_out(hw, st["stride"]), cfg["max_pool"]["stride"])
    for i, (cin, mid, stride, proj) in enumerate(_blocks(cfg)):
        cout = mid * cfg["expansion"]
        out.append(conv(f"blocks/{i}/c1", hw, cin, mid, 1, 1))
        out.append(conv(f"blocks/{i}/c2", hw, mid, mid, 3, stride))
        ho = _out(hw, stride)
        out.append(conv(f"blocks/{i}/c3", ho, mid, cout, 1, 1))
        if proj:
            out.append(conv(f"blocks/{i}/proj", hw, cin, cout, 1, stride))
        hw = ho
    ch = _blocks(cfg)[-1][1] * cfg["expansion"]
    out.append(dict(name="fc", kind="fc", cin=ch, cout=cfg["num_classes"]))
    return out


def param_count(cfg: Dict[str, Any]) -> int:
    """Trainable parameters: conv and fc weights, fc bias, BN scale and
    shift (the published count; conv biases before BN are left out)."""
    n = 0
    for s in sites(cfg):
        if s["kind"] == "conv":
            n += s["k"] ** 2 * s["cin"] * s["cout"] + 2 * s["cout"]
        else:
            n += s["cin"] * s["cout"] + s["cout"]
    return n
