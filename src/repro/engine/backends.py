"""Backend registry for the BFP GEMM engine (DESIGN.md §7).

One datapath, three executions:

  float     disabled-quant baseline: plain ``x @ w`` (prequant weights are
            dequantized first) — the paper's floating-point reference.
  emulated  pure-jnp integer datapath (repro.core.bfp_dot): exact
            fixed-point MACs in int32, works for every scheme/rounding,
            differentiable via STE.
  pallas    fused TPU kernel (repro.kernels): Scheme.TILED only, runs
            in the Pallas interpreter on the CPU backend.  With prequant
            weights it dispatches the sidecar-consuming kernel variant
            that skips in-kernel weight quantization entirely.

``select_backend`` honours ``policy.backend`` (or the legacy
``use_kernel`` flag) but falls back to ``emulated`` when the requested
backend cannot execute the policy faithfully — e.g. pallas with a paper
scheme, stochastic rounding, or an int16 prequant mantissa (with
``strict=True`` it refuses instead).

External backends (future: GPU Triton, int8 XLA dot) register with
:func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.core.bfp_dot import bfp_matmul_2d, bfp_matmul_2d_prequant
from repro.core.bfp import Rounding, Scheme
from repro.core.policy import BFPPolicy
from repro.core.prequant import dequantize_prequant, is_prequant

__all__ = ["Backend", "register_backend", "get_backend",
           "available_backends", "select_backend",
           "BackendFallbackWarning", "BackendUnsupportedError"]

#: (x2d, w_or_prequant, policy, key) -> out [B, N]
MatmulFn = Callable[[jax.Array, object, Optional[BFPPolicy],
                     Optional[jax.Array]], jax.Array]

#: (x_nhwc, w_hwio_or_prequant, policy, stride, padding, key) -> out NHWC
ConvFn = Callable[..., jax.Array]


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    matmul: MatmulFn
    supports: Callable[[BFPPolicy, object], bool]
    #: optional fused convolution; ``None`` means engine.conv2d routes
    #: this backend through the materialized-im2col + matmul fallback
    conv: Optional[ConvFn] = None
    #: (policy, w, stride, padding) -> can ``conv`` honour this faithfully?
    conv_supports: Callable[..., bool] = lambda pol, w, stride, pad: False
    #: can ``matmul``/``conv`` consume activation-prequant ``{"m", "s"}``
    #: inputs natively (pallas: the x-prequant kernel variants)?  False
    #: means the engine dequantizes the dict first — bit-identical via
    #: quantization idempotence, just one more HBM round-trip.
    act_prequant: bool = False
    #: do ``matmul``/``conv`` accept an ``out_policy=`` kwarg emitting the
    #: activation wire format straight from the accumulator (fused
    #: requantize epilogue)?  False means the engine requantizes the
    #: float output in a second step (bit-identical, slower).
    out_quant: bool = False


_REGISTRY: Dict[str, Backend] = {}


def register_backend(name: str, matmul: MatmulFn,
                     supports: Optional[Callable] = None,
                     conv: Optional[ConvFn] = None,
                     conv_supports: Optional[Callable] = None,
                     act_prequant: bool = False,
                     out_quant: bool = False) -> None:
    _REGISTRY[name] = Backend(
        name, matmul, supports or (lambda pol, w: True), conv,
        conv_supports or (lambda pol, w, stride, pad: conv is not None),
        act_prequant, out_quant)


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown BFP backend {name!r}; available: "
                       f"{available_backends()}") from None


def available_backends():
    return sorted(_REGISTRY)


class BackendFallbackWarning(UserWarning):
    """A requested backend could not honour a policy and was downgraded."""


class BackendUnsupportedError(ValueError):
    """strict mode: the requested backend cannot honour the policy."""


#: (backend, path) pairs already warned about on the bare per-call path —
#: the downgrade is warned ONCE per site, not per forward (eager loops
#: would otherwise spam).  ``engine.bind`` passes its own fresh registry
#: per bind, so every independently-constructed Plan/ServeEngine surfaces
#: its own downgrades instead of being muted by an earlier one's.
_WARNED: Set[Tuple[str, Optional[str]]] = set()


def select_backend(policy: BFPPolicy, w, *, strict: bool = False,
                   path: Optional[str] = None,
                   warned: Optional[Set] = None) -> Backend:
    """Requested backend if it supports (policy, w); else emulated.

    The downgrade is never silent: by default it emits a
    :class:`BackendFallbackWarning`, deduplicated per (backend, site)
    against ``warned`` (callers like ``engine.bind`` pass a fresh set
    per bind; bare per-call dispatch shares a process-wide one); with
    ``strict=True`` (surfaced through ``engine.bind(strict=...)`` for
    serving configs) it raises :class:`BackendUnsupportedError` instead,
    so a deployment that asked for the fused kernel fails loudly rather
    than drifting onto the emulated path.
    """
    be = get_backend(policy.backend_name)
    if not be.supports(policy, w):
        msg = (f"backend {be.name!r} cannot honour policy "
               f"(scheme={policy.scheme}, rounding={policy.rounding}, "
               f"l_w={policy.l_w})"
               + (f" at site {path!r}" if path else ""))
        if strict:
            raise BackendUnsupportedError(
                msg + "; refusing the emulated fallback (strict mode)")
        reg = _WARNED if warned is None else warned
        if (be.name, path) not in reg:
            reg.add((be.name, path))
            warnings.warn(msg + "; falling back to 'emulated'",
                          BackendFallbackWarning, stacklevel=2)
        be = _REGISTRY["emulated"]
    return be


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _float_matmul(x2d, w, policy=None, key=None):
    if is_prequant(w):
        w = dequantize_prequant(w, x2d.dtype)
    return x2d @ w


def _emulated_matmul(x2d, w, policy, key=None):
    if is_prequant(w):
        out = bfp_matmul_2d_prequant(x2d, w["m"], w["s"], policy, key)
        return out.astype(x2d.dtype)
    out = bfp_matmul_2d(x2d, w, policy, key)
    return out.astype(jnp.result_type(x2d.dtype, w.dtype))


def _pallas_matmul(x2d, w, policy, key=None, out_policy=None):
    # x2d may be an activation-prequant {"m", "s"} dict (the fused
    # epilogue's output chained into the next layer) — ops dispatches the
    # x-prequant kernel variants; out_policy asks for the fused
    # requantize epilogue (activation wire format straight from VMEM).
    from repro.kernels import ops  # local import: kernels are optional
    if is_prequant(w):
        return ops.bfp_matmul_prequant(x2d, w["m"], w["s"], policy,
                                       out_policy=out_policy)
    return ops.bfp_matmul(x2d, w, policy, out_policy=out_policy)


def _pallas_supports(policy: BFPPolicy, w) -> bool:
    # The fused kernel implements exactly Scheme.TILED with block == K
    # tile, round-to-nearest, both operands quantized.  Anything else is
    # the emulated path's job (silent semantic drift is worse than a
    # fallback; the old use_kernel flag ran TILED math for ANY scheme).
    if policy.scheme is not Scheme.TILED or policy.block_k is None:
        return False
    if policy.rounding is not Rounding.ROUND:
        return False
    if not (policy.quantize_weights and policy.quantize_inputs):
        return False
    if is_prequant(w) and w["m"].dtype != jnp.int8:
        return False  # prequant kernel streams int8 mantissas (L_W <= 8)
    return True


def _pallas_conv(x, w, policy, stride, padding, key=None, out_policy=None):
    from repro.kernels import ops  # local import: kernels are optional
    if is_prequant(w):
        return ops.bfp_conv2d_prequant(x, w["m"], w["s"], policy, stride,
                                       padding, out_policy=out_policy)
    return ops.bfp_conv2d(x, w, policy, stride, padding,
                          out_policy=out_policy)


def _pallas_conv_supports(policy: BFPPolicy, w, stride, padding) -> bool:
    # Same faithfulness contract as the GEMM kernel, plus the implicit
    # kernel's geometry: string SAME/VALID padding and a positive int
    # stride.  Everything else takes the honest im2col fallback.
    if padding not in ("SAME", "VALID"):
        return False
    if not isinstance(stride, int) or stride < 1:
        return False
    return _pallas_supports(policy, w)


register_backend("float", _float_matmul)
register_backend("emulated", _emulated_matmul)
register_backend("pallas", _pallas_matmul, _pallas_supports,
                 conv=_pallas_conv, conv_supports=_pallas_conv_supports,
                 act_prequant=True, out_quant=True)
