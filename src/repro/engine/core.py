"""The unified BFP GEMM execution layer (DESIGN.md §7).

Every model GEMM in the repo — LM linears, MoE expert GEMMs, the tied
lm_head, dense layers — lands on :func:`gemm`; CNN convolutions land on
the conv-aware :func:`conv2d`, which dispatches to a backend's fused
conv (pallas: implicit im2col, no patch matrix in HBM) or falls back to
materialized im2col + :func:`gemm`:

    gemm(x, w, policy, path="fc6")
    conv2d(x, w_hwio, policy, stride=2, padding="SAME", path="stem")

* ``w`` is a float matrix OR the prequant ``{"m", "s"}`` wire format
  (int8 mantissas + power-of-two scale sidecar); pre-quantized weights
  are first-class on every backend, so inference quantizes weights ONCE
  (see ``prequantize`` / ``prequantize_cnn``).
* ``policy`` is None (float), a BFPPolicy (uniform), a PolicyMap
  (per-layer rules resolved against ``path`` — the paper's Table-3
  layer-wise assignments as config), or a bound ``Plan``
  (``engine.bind``): per-site policy resolution AND backend selection
  done once up front, per-call dispatch is a dict hit.
* the backend registry (float / emulated / pallas) picks the execution,
  folding in the legacy ``use_kernel`` flag and the CPU-interpret
  dispatch that used to be scattered across call sites.

:func:`gemm` / :func:`conv2d` are thin shims: with a Plan they delegate
to the bound site entry; otherwise they resolve per call (an implicit
one-site plan), so every existing call site keeps working.  Both emit
``engine.taps`` events from the real datapath (repro.engine.taps).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.bfp import Rounding, Scheme
from repro.core.conv_utils import conv_weight_matrix, im2col
from repro.core.prequant import (act_block, dequantize_act, is_prequant,
                                 prequant_act, quantize_cnn_param_tree,
                                 quantize_param_tree)
from repro.engine import backends as BK
from repro.engine import taps as TAPS
from repro.engine.policy_map import PolicyLike, resolve_policy

__all__ = ["gemm", "conv2d", "conv2d_im2col", "prequantize",
           "prequantize_cnn"]


# ---------------------------------------------------------------------------
# Activation wire format plumbing (ISSUE 6 fused requantize epilogue).
#
# ``out_policy`` asks an execution to emit the CONSUMING layer's
# quantized-input wire format {"m": int8 [.., N], "s": f32 [.., N//bk]}
# instead of dense float — on a backend advertising ``out_quant`` the
# requantization fuses into the kernel epilogue (the f32 activation
# never touches HBM); anywhere else the engine requantizes the float
# output in a second step, bit-identically (core.prequant.prequant_act
# is the pinned reference).  Symmetrically, an execution whose ``x`` is
# already that wire format feeds it straight to an ``act_prequant``
# backend, and is dequantized first (bit-identical by quantization
# idempotence) for every other route.
# ---------------------------------------------------------------------------

def _check_out_policy(out_policy) -> None:
    """Epilogue requantization is defined for exactly the activation wire
    format: TILED blocks along the last axis, round-to-nearest, int8
    mantissas.  (block_k | N and l_i <= 8 are checked where the sizes
    are known: ops epilogue config / prequant_act.)"""
    if out_policy.scheme is not Scheme.TILED or not out_policy.block_k:
        raise ValueError(
            "out_policy must be Scheme.TILED with an explicit block_k "
            f"(activation wire format); got scheme={out_policy.scheme}, "
            f"block_k={out_policy.block_k}")
    if out_policy.rounding is not Rounding.ROUND:
        raise ValueError("out_policy requantization is round-to-nearest "
                         f"only; got {out_policy.rounding}")


def _act_ok_gemm(be: BK.Backend, pol, w, x2d) -> bool:
    """Can ``be`` consume this activation-prequant dict natively?"""
    if not be.act_prequant or pol is None:
        return False
    if x2d["m"].dtype != jnp.int8:
        return False
    bk = act_block(x2d)
    if pol.block_k not in (None, bk):
        return False
    if is_prequant(w):  # weight sidecar block must match the act block
        if w["m"].shape[-2] // w["s"].shape[-2] != bk:
            return False
    return True


def _reshape_out(out, lead, n):
    """Restore leading dims on a dense or wire-format output."""
    if is_prequant(out):
        bq = out["m"].shape[-1] // out["s"].shape[-1]
        return {"m": out["m"].reshape(*lead, n),
                "s": out["s"].reshape(*lead, n // bq)}
    return out.reshape(*lead, n)


def _tap_view(y):
    """Dense float view of an execution output for tap observers (taps
    compare against float references; the wire-format dict is
    dequantized for observation only — the model still sees the dict)."""
    return dequantize_act(y) if is_prequant(y) else y


# ---------------------------------------------------------------------------
# Execution primitives (shared by the per-call shims and bound Plans).
# PolicyMap resolution and tap emission never happen here; backend
# selection (registry + support checks, the per-call path) runs only
# when no pre-selected ``backend`` is passed — bound Plans pass theirs.
# ---------------------------------------------------------------------------

def _gemm_exec(x: Any, w: Any, pol, key=None,
               backend: Optional[BK.Backend] = None,
               strict: bool = False, path: Optional[str] = None,
               warned=None, out_policy=None) -> Tuple[Any, BK.Backend]:
    """Flatten leading dims, run the (given or selected) backend matmul.

    ``x`` may be the activation wire format; ``out_policy`` requests it
    on the output (see the module comment above)."""
    n = (w["m"] if is_prequant(w) else w).shape[-1]
    if out_policy is not None:
        _check_out_policy(out_policy)
    x_pq = is_prequant(x)
    xm = x["m"] if x_pq else x
    lead = xm.shape[:-1]
    if x_pq:
        x2d = {"m": x["m"].reshape(-1, xm.shape[-1]),
               "s": x["s"].reshape(-1, x["s"].shape[-1])}
    else:
        x2d = x.reshape(-1, xm.shape[-1])
    be = backend
    if be is None:
        be = (BK.get_backend("float") if pol is None
              else BK.select_backend(pol, w, strict=strict, path=path,
                                     warned=warned))
    if x_pq and not _act_ok_gemm(be, pol, w, x2d):
        x2d = dequantize_act(x2d)
    if out_policy is not None and be.out_quant and pol is not None:
        out = be.matmul(x2d, w, pol, key, out_policy=out_policy)
    else:
        out = be.matmul(x2d, w, pol, key)
        if out_policy is not None:
            out = prequant_act(out, out_policy)
    return _reshape_out(out, lead, n), be


def _act_ok_conv(be: BK.Backend, pol, w, x) -> bool:
    """Conv counterpart of :func:`_act_ok_gemm` — blocks are per
    (pixel, channel-chunk), so the act block must also match a
    weight-prequant sidecar's HWIO-major K block."""
    if not be.act_prequant or pol is None:
        return False
    if x["m"].dtype != jnp.int8:
        return False
    bk = act_block(x)
    if pol.block_k not in (None, bk):
        return False
    if is_prequant(w):
        kh, kw, c, _ = w["m"].shape
        if (kh * kw * c) // w["s"].shape[-2] != bk:
            return False
    return True


def _conv_exec(x: Any, w: Any, pol, stride: int, padding: str,
               key=None, backend: Optional[BK.Backend] = None,
               strict: bool = False, path: Optional[str] = None,
               warned=None, out_policy=None) -> Tuple[Any, BK.Backend]:
    """Fused conv when the backend has one and can honour (policy,
    geometry); honest materialized-im2col + matmul fallback otherwise.

    With ``backend=None`` the conv slot of the REQUESTED backend is
    consulted (policy None consults the registered "float" backend — the
    same extension point :func:`gemm` documents), and the im2col GEMM
    re-selects with support checks, exactly the legacy per-call
    semantics.  A bound Plan passes its pre-selected ``backend``.
    """
    if out_policy is not None:
        _check_out_policy(out_policy)
    be = backend
    if be is None:
        be = BK.get_backend("float" if pol is None else pol.backend_name)
    fused = be.conv is not None and be.conv_supports(pol, w, stride, padding)
    if is_prequant(x) and not (fused and _act_ok_conv(be, pol, w, x)):
        x = dequantize_act(x)
    if fused:
        if out_policy is not None and be.out_quant and pol is not None:
            return be.conv(x, w, pol, stride, padding, key,
                           out_policy=out_policy), be
        out = be.conv(x, w, pol, stride, padding, key)
        if out_policy is not None:
            out = prequant_act(out, out_policy)
        return out, be
    # backend given (Plan): reuse its matmul for the im2col GEMM;
    # otherwise select per call (pallas-with-paper-scheme lands emulated).
    return _conv_im2col_exec(x, w, pol, stride, padding, key,
                             backend=backend, strict=strict, path=path,
                             warned=warned, out_policy=out_policy)


def _conv_im2col_exec(x, w, pol, stride, padding, key=None, backend=None,
                      strict=False, path=None, warned=None,
                      out_policy=None) -> Tuple[Any, BK.Backend]:
    if is_prequant(x):  # im2col gathers float patches
        x = dequantize_act(x)
    prequant = is_prequant(w)
    kh, kw, c, oc = (w["m"] if prequant else w).shape
    cols, (b, oh, ow) = im2col(x, kh, kw, stride, padding)
    wmat = ({"m": conv_weight_matrix(w["m"]), "s": w["s"]} if prequant
            else conv_weight_matrix(w))
    out, be = _gemm_exec(cols, wmat, pol, key, backend=backend,
                         strict=strict, path=path, warned=warned,
                         out_policy=out_policy)
    if is_prequant(out):
        bq = out["m"].shape[-1] // out["s"].shape[-1]
        return {"m": out["m"].reshape(b, oh, ow, oc),
                "s": out["s"].reshape(b, oh, ow, oc // bq)}, be
    return out.reshape(b, oh, ow, oc), be


# ---------------------------------------------------------------------------
# Execute-then-tap (one implementation shared by the per-call shims and
# the bound Plan entries, so tap events cannot diverge between the two)
# ---------------------------------------------------------------------------

def _adopt_transform(out, view, new, out_policy):
    """Fold a transforming tap's replacement back into the datapath.

    Taps observe the dense float view; when the execution produced the
    activation wire format, the replacement is re-quantized under the
    same ``out_policy`` — i.e. the fault lands on the f32 accumulator
    BEFORE the epilogue requantization, which is where an SEU in an
    accumulator register would physically sit."""
    if new is view:
        return out
    if is_prequant(out):
        return prequant_act(new, out_policy)
    return new


def gemm_and_tap(x, w, pol, key=None, backend=None, strict=False,
                 path=None, warned=None, out_policy=None) -> Any:
    out, be = _gemm_exec(x, w, pol, key, backend=backend, strict=strict,
                         path=path, warned=warned, out_policy=out_policy)
    if TAPS.active():
        # wire-format outputs are dequantized for observation only (taps
        # compare against the float reference); the model sees ``out``
        # unless a transforming tap replaced the observed view
        view = _tap_view(out)
        new = TAPS.emit("gemm", path, pol, be.name, x, w, view,
                        float_fn=lambda: _gemm_exec(x, w, None, None)[0])
        out = _adopt_transform(out, view, new, out_policy)
    return out


def conv_and_tap(x, w, pol, stride, padding, key=None, backend=None,
                 strict=False, path=None, warned=None,
                 out_policy=None) -> Any:
    out, be = _conv_exec(x, w, pol, stride, padding, key, backend=backend,
                         strict=strict, path=path, warned=warned,
                         out_policy=out_policy)
    if TAPS.active():
        view = _tap_view(out)
        new = TAPS.emit("conv", path, pol, be.name, x, w, view,
                        float_fn=lambda: _conv_im2col_exec(
                            x, w, None, stride, padding)[0],
                        stride=stride, padding=padding)
        out = _adopt_transform(out, view, new, out_policy)
    return out


# ---------------------------------------------------------------------------
# Public shims
# ---------------------------------------------------------------------------

#: lazily-cached Plan class — resolves the core<->plan import cycle once
#: instead of paying a sys.modules lookup on every per-call dispatch
_PLAN_CLS = None


def _plan_cls():
    global _PLAN_CLS
    if _PLAN_CLS is None:
        from repro.engine.plan import Plan
        _PLAN_CLS = Plan
    return _PLAN_CLS


#: lazily-cached grad subsystem (same cycle-breaking pattern):
#: repro.grad.vjp builds the custom VJPs ON TOP of gemm_and_tap /
#: conv_and_tap, so it must import this module, not the reverse
_GRAD_VJP = None


def _grad_vjp():
    global _GRAD_VJP
    if _GRAD_VJP is None:
        from repro.grad import vjp
        _GRAD_VJP = vjp
    return _GRAD_VJP


def gemm(x: Any, w: Any, policy: PolicyLike = None, *,
         path: Optional[str] = None,
         key: Optional[jax.Array] = None,
         out_policy: Optional[Any] = None) -> Any:
    """``x[..., K] @ w[K, N]`` through the policy-selected BFP backend.

    ``w``: float [K, N] or prequant ``{"m": [K, N], "s": [K//bk, N]}``.
    Leading dims of ``x`` are flattened for the 2-D backends and restored.
    ``policy`` may be a bound ``engine.Plan`` — the site entry for
    ``path`` then supplies the resolved policy AND backend with no
    per-call registry/regex work.

    ``x`` may also be the activation wire format ``{"m": int8 [.., K],
    "s": [.., K//bk]}`` (a previous layer's ``out_policy`` output);
    ``out_policy=`` (the CONSUMING layer's policy) returns that format
    instead of dense float — fused into the kernel epilogue on backends
    that support it, a bit-identical second requantization step
    elsewhere.
    """
    if isinstance(policy, _plan_cls()):
        return policy.gemm(x, w, path=path, key=key, out_policy=out_policy)
    gv = _grad_vjp()
    if gv.routable(x, w, key, out_policy) and w.ndim == 2:
        # dense float operands: the custom-VJP route — identical forward
        # (it calls gemm_and_tap), backward GEMMs through the backend
        # registry under the grad-path policies (repro.grad, §12)
        return gv.gemm(x, w, policy, path)
    # policy None goes through the registered "float" backend, so
    # re-registering it (instrumented or accelerated variants) also
    # covers policy-None GEMMs
    return gemm_and_tap(x, w, resolve_policy(policy, path), key, path=path,
                        out_policy=out_policy)


def conv2d(x: Any, w: Any, policy: PolicyLike = None, *,
           stride: int = 1, padding: str = "SAME",
           path: Optional[str] = None,
           key: Optional[jax.Array] = None,
           out_policy: Optional[Any] = None) -> Any:
    """NHWC convolution through the policy-selected BFP backend.

    ``x``: [B, H, W, C] float; ``w``: HWIO [kh, kw, C, OC] float or the
    prequant ``{"m": int8 HWIO, "s": [K//bk, OC]}`` wire format.  A
    backend with a faithful fused conv (pallas: the implicit-im2col
    kernel, no materialized patch matrix in HBM) takes it; everything
    else — float, emulated, pallas with a scheme the kernel can't honour
    — falls back honestly to the materialized im2col + :func:`gemm`
    route, which preserves exact GEMM-engine semantics per backend.
    ``policy=None`` consults the registered "float" backend's conv slot
    (same extension point as GEMMs) before taking the im2col route.

    ``x`` may be the NHWC activation wire format (blocks per
    (pixel, channel-chunk)); ``out_policy=`` returns it — see
    :func:`gemm`.  Chained convs on the pallas backend hand ``{"m","s"}``
    activations layer to layer with no dequantized f32 tensor in HBM.
    """
    if isinstance(policy, _plan_cls()):
        return policy.conv2d(x, w, path=path, stride=stride,
                             padding=padding, key=key,
                             out_policy=out_policy)
    gv = _grad_vjp()
    if gv.routable(x, w, key, out_policy) and w.ndim == 4 \
            and padding in ("SAME", "VALID"):
        return gv.conv2d(x, w, policy, stride, padding, path)
    return conv_and_tap(x, w, resolve_policy(policy, path), stride,
                        padding, key, path=path, out_policy=out_policy)


def conv2d_im2col(x: Any, w: Any, pol, stride: int = 1,
                  padding: str = "SAME", key=None,
                  out_policy=None) -> Any:
    """The materialized-im2col route: paper Fig. 1's matrix form, lowered
    through the GEMM engine (so backend selection, prequant handling, and
    fallbacks behave exactly as for any other GEMM).  :func:`conv2d`'s
    fallback; public so tests can force this route against the fused
    kernel.  ``pol`` is an
    already-resolved BFPPolicy or None, not a PolicyMap.  Does not emit
    tap events (the :func:`conv2d` entry does, once per conv site)."""
    return _conv_im2col_exec(x, w, pol, stride, padding, key,
                             out_policy=out_policy)[0]


def prequantize(params: Any, policy: PolicyLike) -> Any:
    """Quantize an LM param tree's GEMM weights once (wire format).

    Per-layer maps work: a PolicyMap rule resolving to None keeps that
    leaf float.  The returned tree feeds the same model code — every
    backend consumes the wire format directly.
    """
    return quantize_param_tree(params, policy)


def prequantize_cnn(params: Any, policy: PolicyLike) -> Any:
    """CNN counterpart of :func:`prequantize` (HWIO convs + dense)."""
    return quantize_cnn_param_tree(params, policy)
