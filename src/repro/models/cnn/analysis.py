"""Paper Table 4 — per-layer SNR validation over the REAL datapath.

:func:`analyze_model` runs any model twice — float reference and BFP —
with ``engine.taps`` observing every GEMM/conv site the engine actually
executes, then compares measured input/weight/output SNRs against the
paper's single-layer (eq. 18) and multi-layer (eq. 19-20) analytical
models.  Because the sites come from taps rather than a hand-rolled
walker, this traverses ANY topology the engine runs: sequential VGG,
ResNet residual blocks (projection shortcuts included), GoogLeNet
inception branches and aux heads — the four networks the paper
validates on.

Two inheritance modes for the multi-layer model's eta_1 (inherited NSR):

  * ``"analytic"``  — chain predictions site-by-site in execution order
    (eq. 19-20 exactly as the paper applies it to a sequential CNN;
    :func:`analyze_vgg` uses this and reproduces the pre-tap driver's
    rows bit-for-bit on zero-bias trees);
  * ``"measured"``  — measure eta_1 directly at each site's input from
    the dual runs (the float path and the BFP path are both available,
    so the carried error is observable).  This generalizes eq. 19-20 to
    branch/merge topologies where "the previous layer" is ill-defined:
    residual adds and concats mix inherited NSRs, and the measurement
    captures the mix exactly.

ReLU and pooling are traversed exactly as the paper does, because the
MODEL traverses them: ReLU is SNR-neutral (checked per row), pooling
feeds the next site through the real forward pass.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from repro import engine as EG
from repro.core import nsr
from repro.core.bfp_dot import quantize_activations
from repro.core.conv_utils import conv_weight_matrix, im2col
from repro.core.policy import BFPPolicy
from repro.engine import PolicyMap
from repro.models.cnn import vgg

__all__ = ["LayerRow", "SiteRow", "analyze_model", "analyze_vgg"]


@dataclasses.dataclass
class SiteRow:
    """One engine site's row of the paper's Table 4 (SNRs in dB)."""
    path: str
    kind: str             # "gemm" | "conv"
    input_ex: float       # experimental input SNR
    input_single: float   # single-layer model
    input_multi: float    # multi-layer model
    weight_ex: float
    weight_model: float
    output_ex: float
    output_single: float
    output_multi: float
    relu_ex: float        # SNR after ReLU (paper: ~= output SNR)


@dataclasses.dataclass
class LayerRow:
    """Legacy row shape kept for the VGG driver's consumers."""
    name: str
    input_ex: float
    input_single: float
    input_multi: float
    weight_ex: float
    weight_model: float
    output_ex: float
    output_single: float
    output_multi: float
    relu_ex: float


def _no_ste(policy):
    """The analysis measures the inference datapath: no STE grads."""
    if isinstance(policy, BFPPolicy):
        return policy.with_(straight_through=False)
    if isinstance(policy, PolicyMap):
        off = lambda p: None if p is None else p.with_(straight_through=False)
        return PolicyMap(
            rules=tuple((pat, off(p)) for pat, p in policy.rules),
            default=off(policy.default))
    return policy


def _site_matrices(ev: EG.TapEvent):
    """A tapped site in GEMM view: (x2d [rows, K], w [K, N]).

    Conv sites are lowered with the SAME im2col/weight-matrix helpers
    the engine's im2col route uses, so the matrices are bit-identical
    to what the datapath multiplied.
    """
    w = ev.w
    if EG.is_prequant(w):
        raise ValueError(
            "analyze_model needs float weights (the weight-SNR rows "
            "compare quantized vs unquantized); pass the original param "
            "tree, not plan.params / a prequantized tree")
    if ev.kind == "conv":
        kh, kw, _, _ = w.shape
        cols, _ = im2col(ev.x, kh, kw, ev.stride, ev.padding)
        return cols, conv_weight_matrix(w)
    return ev.x.reshape(-1, ev.x.shape[-1]), w


def analyze_model(apply_fn: Callable[[Any, jax.Array, Any], Any],
                  params: Any, x: jax.Array, policy,
                  *, inheritance: str = "measured",
                  max_sites: Optional[int] = None,
                  bias_fn: Optional[Callable[[str],
                                             Optional[jax.Array]]] = None
                  ) -> List[SiteRow]:
    """Dual-run (float / BFP) tap analysis of ``apply_fn``'s datapath.

    ``apply_fn(params, x, policy)`` must execute the model through the
    engine (every in-repo model does); its return value is ignored —
    the engine taps supply the per-site operands.  ``policy`` is a
    BFPPolicy (uniform) or PolicyMap (sites a rule pins to float are
    skipped: there is no quantization to analyze there), as are sites
    whose float input or output is all zero.  Rows appear in execution
    order.

    ``inheritance`` picks the multi-layer model's eta_1 source:
    "analytic" chains predictions in execution order (sequential
    models, the paper's Table-4 procedure), "measured" reads the
    carried error off the dual runs (any topology).

    Taps fire inside the engine, BEFORE the layer adds its bias, so by
    default output/ReLU SNRs are measured on pre-bias activations
    (identical to post-bias on the zero-bias He-init trees the
    analyses use).  For trained models pass ``bias_fn(path) -> b`` (or
    None for pre-bias sites) and the paper's exact procedure —
    ``snr(y_f + b, y_q + b)``, ReLU on the real activations — is
    restored; :func:`analyze_vgg` does this automatically.
    """
    if inheritance not in ("analytic", "measured"):
        raise ValueError(f"inheritance must be 'analytic' or 'measured', "
                         f"got {inheritance!r}")
    policy = _no_ste(policy)
    ev_f: List[EG.TapEvent] = []
    ev_q: List[EG.TapEvent] = []
    with EG.taps(ev_f.append):
        apply_fn(params, x, None)
    with EG.taps(ev_q.append):
        apply_fn(params, x, policy)
    if len(ev_f) != len(ev_q):
        raise RuntimeError(
            f"float/BFP runs executed different site counts "
            f"({len(ev_f)} vs {len(ev_q)}) — apply_fn must traverse the "
            f"same sites for both policies")

    rows: List[SiteRow] = []
    eta_multi = 0.0  # analytic mode: inherited NSR chained across sites
    for f, q in zip(ev_f, ev_q):
        if f.path != q.path:
            raise RuntimeError(f"site order diverged: {f.path} vs {q.path}")
        pol = q.policy
        if pol is None:
            continue  # float-pinned site: nothing to analyze
        if max_sites is not None and len(rows) >= max_sites:
            break
        cols_f, wmat = _site_matrices(f)
        if not (bool(jnp.any(cols_f != 0)) and bool(jnp.any(f.y != 0))):
            # no signal (e.g. a branch whose ReLU zeroed its whole input
            # at a small width): every SNR would read -inf, so the site
            # has no row
            continue
        cols_q, _ = _site_matrices(q)

        # --- input SNRs: measured + single/multi-layer models -------------
        in_fmt = quantize_activations(cols_q, pol).dequantize()
        input_ex = float(nsr.snr_db(cols_f, in_fmt))
        input_single = float(nsr.predict_matrix_snr(cols_f, pol.l_i, "i",
                                                    pol))
        eta_fresh = float(nsr.nsr_from_snr_db(
            nsr.predict_matrix_snr(cols_q, pol.l_i, "i", pol)))
        eta_inherited = (eta_multi if inheritance == "analytic" else
                         float(nsr.nsr_from_snr_db(
                             nsr.snr_db(cols_f, cols_q))))
        eta_in_multi = float(nsr.chain_input_nsr(eta_inherited, eta_fresh))
        input_multi = float(nsr.snr_db_from_nsr(jnp.asarray(eta_in_multi)))

        # --- weight SNRs ---------------------------------------------------
        weight_ex = float(nsr.measure_matrix_snr(wmat, pol.l_w, "w", pol))
        weight_model = float(nsr.predict_matrix_snr(wmat, pol.l_w, "w",
                                                    pol))
        eta_w = float(nsr.nsr_from_snr_db(weight_model))

        # --- outputs: the datapath's own y vs the float run's ------------
        b = bias_fn(f.path) if bias_fn is not None else None
        y_f = f.y if b is None else f.y + b
        y_q = q.y if b is None else q.y + b
        output_ex = float(nsr.snr_db(y_f, y_q))
        output_single = float(nsr.single_layer_output_snr(
            jnp.asarray(input_single), jnp.asarray(weight_model)))
        eta_out_multi = eta_in_multi + eta_w
        output_multi = float(nsr.snr_db_from_nsr(jnp.asarray(eta_out_multi)))

        # --- ReLU (paper §4.4: SNR-neutral check) --------------------------
        relu_ex = float(nsr.snr_db(jax.nn.relu(y_f), jax.nn.relu(y_q)))

        rows.append(SiteRow(f.path or "?", f.kind, input_ex, input_single,
                            input_multi, weight_ex, weight_model, output_ex,
                            output_single, output_multi, relu_ex))
        eta_multi = eta_out_multi
    return rows


def analyze_vgg(params, x: jax.Array, policy: BFPPolicy,
                max_layers: Optional[int] = None) -> List[LayerRow]:
    """The original Table-4 VGG driver, as a thin wrapper over
    :func:`analyze_model` (analytic inheritance, conv rows only, biases
    restored per site — reproducing the pre-tap sequential walker's
    rows exactly, trained or He-init trees alike)."""
    # VGG's conv sites strictly precede its fc sites, so max_sites=
    # max_layers truncates the per-site analysis exactly where the old
    # walker stopped (the forward itself still runs in full — taps
    # can't abort it — but the expensive per-site math does not).
    rows = [r for r in analyze_model(
                vgg.apply, params, x, policy, inheritance="analytic",
                max_sites=max_layers,
                bias_fn=lambda p: params[p]["b"] if p in params else None)
            if r.kind == "conv"]
    return [LayerRow(r.path, r.input_ex, r.input_single, r.input_multi,
                     r.weight_ex, r.weight_model, r.output_ex,
                     r.output_single, r.output_multi, r.relu_ex)
            for r in rows]
