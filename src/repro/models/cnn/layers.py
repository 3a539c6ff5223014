"""CNN layers with the BFP datapath (paper §3.2-3.4).

Convolution is the paper's matrix form ``O = I @ W`` (Fig. 1), executed
by :func:`repro.engine.conv2d`: on the pallas backend that is the fused
implicit-im2col kernel — receptive-field rows are formed in VMEM, the
patch matrix never hits HBM — and on every other backend/scheme the
engine falls back to materialized :func:`im2col` + ``engine.gemm``
(identical numerics; tests assert the two routes agree bit-exactly for
Scheme.TILED).  ``policy=None`` gives the float reference path; a
``repro.engine.PolicyMap`` resolves a per-layer policy against the
layer's ``path`` (paper Table-3 layer-wise assignments); a bound
``repro.engine.Plan`` (from ``engine.bind(params, policy)``) rides the
same argument with resolution + backend selection already done — apply
the model to ``plan.params`` and pass the plan as ``policy``.  Weights
may be pre-quantized to the ``{"m", "s"}`` wire format
(``repro.engine.prequantize_cnn``, or ``bind`` does it): every backend —
including the sidecar-consuming fused conv kernel — consumes it
directly, so inference skips per-forward weight re-quantization.

Parameters are plain pytrees (dicts); every layer is a pure function.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import engine as EG
from repro.core.conv_utils import im2col  # re-export: the shared helper
from repro.engine import PolicyLike

__all__ = ["conv2d_init", "conv2d", "im2col", "dense_init", "dense",
           "batchnorm_init", "batchnorm", "max_pool", "avg_pool",
           "global_avg_pool", "local_response_norm", "relu"]


def _he_init(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)


# ---------------------------------------------------------------------------
# Convolution as matrix multiplication (paper Fig. 1)
# ---------------------------------------------------------------------------

def conv2d_init(key, in_ch: int, out_ch: int, kh: int, kw: int):
    """Weights stored HWIO [kh, kw, in_ch, out_ch]; the GEMM view (paper
    W^T: each column is one filter == one paper W row) is taken inside
    conv2d.  Shape info lives in the array shape (jit-static)."""
    k = kh * kw * in_ch
    wkey, bkey = jax.random.split(key)
    return {
        "w": _he_init(wkey, (kh, kw, in_ch, out_ch), k),
        "b": jnp.zeros((out_ch,), jnp.float32),
    }


def conv2d(params, x: jax.Array, stride: int = 1, padding: str = "SAME",
           policy: PolicyLike = None,
           path: Optional[str] = None) -> jax.Array:
    """BFP convolution through :func:`repro.engine.conv2d`.  x: NHWC.

    ``params["w"]`` is an HWIO float kernel or its prequant form (int8
    HWIO mantissa + GEMM-view scale sidecar); the engine picks the fused
    implicit-im2col kernel or the materialized-im2col GEMM route per
    backend/policy.
    """
    return EG.conv2d(x, params["w"], policy, stride=stride,
                     padding=padding, path=path) + params["b"]


# ---------------------------------------------------------------------------
# Dense / norm / pooling
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int):
    wkey, _ = jax.random.split(key)
    return {"w": _he_init(wkey, (in_dim, out_dim), in_dim),
            "b": jnp.zeros((out_dim,), jnp.float32)}


def dense(params, x: jax.Array, policy: PolicyLike = None,
          path: Optional[str] = None) -> jax.Array:
    return EG.gemm(x, params["w"], policy, path=path) + params["b"]


def batchnorm_init(ch: int):
    return {"gamma": jnp.ones((ch,), jnp.float32),
            "beta": jnp.zeros((ch,), jnp.float32),
            "mean": jnp.zeros((ch,), jnp.float32),
            "var": jnp.ones((ch,), jnp.float32)}


def batchnorm(params, x: jax.Array, training: bool = False,
              eps: float = 1e-5):
    """Inference-mode BN (paper setting: deployed models, no retraining).

    In training mode uses batch statistics (no running-average state
    threading — the small CNNs trained in-repo use this path).
    """
    if training:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
    else:
        mean, var = params["mean"], params["var"]
    inv = jax.lax.rsqrt(var + eps) * params["gamma"]
    return x * inv + (params["beta"] - mean * inv)


def max_pool(x: jax.Array, window: int = 2, stride: int = 2,
             padding: str = "VALID") -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), padding)


def avg_pool(x: jax.Array, window: int, stride: int,
             padding: str = "VALID") -> jax.Array:
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), padding)
    return s / (window * window)


def global_avg_pool(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2))


def local_response_norm(x: jax.Array, size: int = 5, alpha: float = 1e-4,
                        beta: float = 0.75, k: float = 1.0) -> jax.Array:
    """Local response normalization across channels, in Caffe's form
    (GoogLeNet's norm1/norm2): ``x / (k + alpha/size * sum x^2)^beta``,
    the sum over a window of ``size`` channels centred on each channel,
    zero beyond the edges.  Float32, in XLA, like ReLU and the pools."""
    x = x.astype(jnp.float32)
    half = size // 2
    sq = jax.lax.reduce_window(
        x * x, 0.0, jax.lax.add, (1,) * (x.ndim - 1) + (size,),
        (1,) * x.ndim,
        ((0, 0),) * (x.ndim - 1) + ((half, size - 1 - half),))
    return x / (k + alpha / size * sq) ** beta


relu = jax.nn.relu
