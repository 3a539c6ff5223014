"""Bound execution plans — resolve/select/quantize ONCE, then just run.

``engine.bind(params, policy)`` is the deployment-mode entry point the
paper's accelerator design (and Ristretto / Fixflow-style fixed-point
serving) organizes around: walk the param tree once, resolve each
GEMM/conv site's PolicyMap rule against its layer path, select the
concrete backend execution (or honest emulated fallback) up front,
pre-quantize every eligible weight leaf into the ``{"m", "s"}`` wire
format, and return an immutable :class:`Plan`:

    plan = engine.bind(params, policy)
    logits = vgg.apply(plan.params, x, plan)     # plan rides the policy arg

A :class:`Plan` is a ``PolicyLike``: model code passes it exactly where
it passed a ``BFPPolicy``/``PolicyMap``, and ``engine.gemm`` /
``engine.conv2d`` delegate to the bound per-site entries — per-call
dispatch drops from regex resolution + registry lookup + support checks
to one dict hit.  Results are bit-identical to the per-call path (the
same backend executions run, selected earlier).

What is resolved when:
  * bind time: policy-rule backends exist (unknown names raise the
    ``available_backends`` KeyError HERE, not mid-forward), per-site
    policy resolution, backend support checks against the actual weight
    (downgrades warn once, or raise with ``strict=True``), weight
    pre-quantization;
  * call time: only geometry-dependent conv fusion (stride/padding) and
    the backend execution itself, under ``jax.named_scope(<site path>)``
    so every kernel a compiled program launches names its site in its
    ``op_name`` (``jit(run)/layer1/0/c1/jit(bfp_conv2d_...)/pallas_call``).

Paths the walk cannot see (e.g. the MoE expert runtime path "moe" vs
its per-matrix tree leaves "moe/w1...") fall back to legacy per-call
resolution against the original policy — correct, just not pre-bound;
``strict`` still applies to their backend selection.
"""
from __future__ import annotations

import contextlib
import dataclasses
import types
from typing import Any, Dict, Iterable, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.bfp import Rounding, Scheme
from repro.core.packed import is_packed, unpack_prequant
from repro.core.policy import BFPPolicy
from repro.core.prequant import (_path_keys, cnn_rule_path,
                                 detect_tree_kind, is_prequant,
                                 lm_eligible, lm_rule_path,
                                 quantize_cnn_param_tree,
                                 quantize_param_tree)
from repro.engine import backends as BK
from repro.engine.core import _grad_vjp, conv_and_tap, gemm_and_tap
from repro.engine.policy_map import PolicyLike, PolicyMap, resolve_policy
from repro.grad.paths import GradSpec, grad_path, resolve_grad_policy

__all__ = ["Site", "Plan", "BoundForward", "bind", "unpack_packed"]


def unpack_packed(params: Any) -> Any:
    """Replace every :class:`~repro.core.packed.PackedBFP` leaf with its
    ``{"m", "s"}`` prequant sidecar — the packed-artifact load path.

    This is how a serving engine consumes a ``format="bfp_packed"`` or
    ``format="bfp_packed_v2"`` checkpoint restored with
    ``packed="keep"``: the ~4x-smaller container unpacks straight into
    the wire format every backend executes, so no float weight is ever
    materialized for a prequant-eligible site.  Fixed- and
    variable-width containers decode through the same call (the
    container self-describes; ``unpack_prequant`` dispatches on its
    width plane), so binding a v3 artifact is exactly binding its fixed
    twin.  Trees without packed leaves pass through untouched (same
    object).
    """
    flat = jax.tree_util.tree_leaves(params, is_leaf=is_packed)
    if not any(is_packed(l) for l in flat):
        return params
    return jax.tree_util.tree_map(
        lambda l: unpack_prequant(l) if is_packed(l) else l,
        params, is_leaf=is_packed)


class BoundForward:
    """``fn(params, x, *args)`` jitted with the ARRAY leaves of
    ``params`` passed as arguments on every call.  Closed-over arrays
    would be embedded in each compiled program as constants: the whole
    model, once per batch bucket, and inside the compile-cache key.
    Non-array leaves (e.g. GoogLeNet's static widths) stay Python values.

    With ``mesh``, ``fn`` runs under ``jax.shard_map`` with ``x`` split
    over ``batch_axis`` and the arrays replicated (placed on the mesh
    once, here).  ``jit=False`` runs eagerly.
    """

    def __init__(self, fn, params, *, mesh=None, batch_axis=None,
                 jit: bool = True):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        is_arr = [isinstance(l, (jax.Array, np.ndarray)) for l in leaves]
        #: conv launches per call that take the narrow-channel patch
        #: path (``kernels.ops.count_conv_launches``), counted when the
        #: forward was last traced (on every call with ``jit=False``)
        self.patch_convs = 0
        #: MACs the conv launches of one call issue to the MXU, padding
        #: included (``count_conv_launches``), by the call's batch rows,
        #: counted when the forward was traced at that batch
        self.conv_macs_issued: Dict[int, int] = {}
        shards = 1
        if mesh is not None:
            for a in (batch_axis if isinstance(batch_axis, (tuple, list))
                      else (batch_axis,)):
                shards *= mesh.shape[a]

        def run(arrays, x, *args):
            # local: ops imports repro.tune, which imports the engine
            from repro.kernels.ops import count_conv_launches
            it = iter(arrays)
            tree = treedef.unflatten(
                [next(it) if a else l for l, a in zip(leaves, is_arr)])
            with count_conv_launches() as tally:
                out = fn(tree, x, *args)
            self.patch_convs = tally["patch"]
            # under shard_map this traces one shard's rows
            self.conv_macs_issued[x.shape[0] * shards] = \
                tally["macs_issued"] * shards
            return out

        self.arrays = [l for l, a in zip(leaves, is_arr) if a]
        if mesh is not None:
            run = jax.shard_map(run, mesh=mesh,
                                in_specs=(P(), P(batch_axis)),
                                out_specs=P(batch_axis), check_vma=False)
            self.arrays = jax.device_put(self.arrays,
                                         NamedSharding(mesh, P()))
        self._run = jax.jit(run) if jit else run

    def __call__(self, x, *args):
        return self._run(self.arrays, x, *args)

    def lower(self, x, *args):
        """``jax.jit(...).lower`` for input ``x`` (AOT compile/inspect)."""
        return self._run.lower(self.arrays, x, *args)


@dataclasses.dataclass(frozen=True)
class Site:
    """One bound GEMM/conv execution site."""

    path: str
    kind: str                       #: "gemm" | "conv"
    policy: Optional[BFPPolicy]     #: resolved concrete policy (None=float)
    backend: BK.Backend             #: concrete execution, selected at bind
    fallback: bool = False          #: requested backend was downgraded
    prequantized: bool = False      #: weight leaf holds the wire format
    #: backward-GEMM plans (repro.grad, DESIGN.md §12), resolved on the
    #: derived grad paths (``path#dx`` / ``path#dw``) at bind time —
    #: policy AND backend, so strict binds refuse unsupported backward
    #: backends up front.  None (legacy construction) means "resolve per
    #: call against the plan's original policy".
    dx: Optional[GradSpec] = None
    dw: Optional[GradSpec] = None


class Plan:
    """Immutable per-site execution table returned by :func:`bind`.

    ``plan.params`` is the (pre-quantized) tree the model should be
    applied with; the plan itself rides the ``policy`` argument.  Site
    entries are fixed at bind time — re-registering a backend afterwards
    does not change a bound plan (that is the point: serving runs the
    datapath that was admitted).
    """

    def __init__(self, sites: Dict[str, Site], params: Any,
                 policy: PolicyLike, strict: bool = False,
                 tune_cache: Any = None):
        self._sites = dict(sites)
        self.sites = types.MappingProxyType(self._sites)
        self.params = params
        self.policy = policy
        self.strict = strict
        #: TuneCache attached at bind time (``bind(..., tune_cache=)``):
        #: every bound execution runs with it active, so kernels launch
        #: with the autotuned tiles for their (shape, L, target) site
        self.tune_cache = tune_cache
        #: per-plan fallback-warning dedup for unbound-path dispatch, so
        #: one plan's downgrades never mute another's
        self._warned: set = set()
        #: per-plan cache of jitted forwards, keyed by (apply function,
        #: mesh, batch axis) — every consumer binding the same plan to the
        #: same model shares one traced callable (see :meth:`jit_forward`)
        self._jit_cache: Dict[Any, Any] = {}

    def __repr__(self) -> str:
        n_bfp = sum(1 for s in self._sites.values() if s.policy is not None)
        return (f"Plan({len(self._sites)} sites, {n_bfp} BFP, "
                f"strict={self.strict})")

    def site(self, path: str) -> Site:
        return self._sites[path]

    def resolve(self, path: Optional[str]) -> Optional[BFPPolicy]:
        """Concrete policy for ``path`` (the ``resolve_policy`` protocol,
        so code like the MoE layer that resolves before vmapping works on
        plans too)."""
        s = self._sites.get(path)
        if s is not None:
            return s.policy
        return resolve_policy(self.policy, path)

    # -- bound executions (execute + tap shared with the per-call shims) ----

    def _tuned(self):
        """Context activating this plan's tune cache (no-op when none)."""
        if self.tune_cache is None:
            return contextlib.nullcontext()
        from repro.tune.cache import use_cache
        return use_cache(self.tune_cache)

    def out_policy_for(self, path: Optional[str]) -> Optional[BFPPolicy]:
        """The resolved policy for ``path`` IF its execution would
        quantize its input to the activation wire format — i.e. the
        ``out_policy=`` the PRODUCING layer should pass so the handoff
        skips the dequantized-f32 round-trip.  None when ``path`` is
        float, doesn't quantize inputs, or its input quantization isn't
        the wire format (non-TILED, no block, stochastic, L_I > 8)."""
        pol = self.resolve(path)
        if pol is None or not pol.quantize_inputs:
            return None
        if (pol.scheme is not Scheme.TILED or not pol.block_k
                or pol.rounding is not Rounding.ROUND or pol.l_i > 8):
            return None
        return pol

    def gemm(self, x: Any, w: Any, *, path: Optional[str] = None,
             key: Optional[jax.Array] = None, out_policy=None) -> Any:
        site = self._sites.get(path)
        gv = _grad_vjp()
        with self._tuned():
            if site is not None and site.kind == "gemm":
                with jax.named_scope(path):
                    if gv.routable(x, w, key, out_policy) and w.ndim == 2:
                        return gv.gemm_bound(x, w, site)
                    return gemm_and_tap(x, w, site.policy, key,
                                        backend=site.backend, path=path,
                                        out_policy=out_policy)
            # unbound path: legacy per-call resolution (strict kept)
            if gv.routable(x, w, key, out_policy) and w.ndim == 2:
                return gv.gemm(x, w, self.policy, path, self.strict)
            return gemm_and_tap(x, w, resolve_policy(self.policy, path),
                                key, strict=self.strict, path=path,
                                warned=self._warned, out_policy=out_policy)

    def conv2d(self, x: Any, w: Any, *, path: Optional[str] = None,
               stride: int = 1, padding: str = "SAME",
               key: Optional[jax.Array] = None, out_policy=None) -> Any:
        site = self._sites.get(path)
        gv = _grad_vjp()
        routed = (gv.routable(x, w, key, out_policy) and w.ndim == 4
                  and padding in ("SAME", "VALID"))
        with self._tuned():
            if site is not None and site.kind == "conv":
                with jax.named_scope(path):
                    if routed:
                        return gv.conv2d_bound(x, w, site, stride, padding)
                    return conv_and_tap(x, w, site.policy, stride,
                                        padding, key, backend=site.backend,
                                        path=path, out_policy=out_policy)
            if routed:
                return gv.conv2d(x, w, self.policy, stride, padding,
                                 path, self.strict)
            return conv_and_tap(x, w, resolve_policy(self.policy, path),
                                stride, padding, key, strict=self.strict,
                                path=path, warned=self._warned,
                                out_policy=out_policy)

    def jit_forward(self, apply_fn, mesh=None, batch_axis="data"):
        """A jitted ``apply_fn(plan.params, x, plan)``, cached per
        ``(apply_fn, mesh, batch_axis)`` on this plan.

        This is how a bound plan is REUSED across jit'd callables: N
        serve engines (or batch buckets, or benchmark drivers) bound to
        the same plan get the SAME callable object back, so they share
        one trace-cache — jax retraces per input shape (each batch
        bucket compiles once), never per consumer.  The plan rides the
        closure; its params are an ARGUMENT of the jitted function (see
        :class:`BoundForward`).  Extra positional args (e.g. a model's
        ``training`` flag) pass through.

        With ``mesh``, the forward runs under ``jax.shard_map`` with the
        batch split over ``batch_axis`` and the params replicated (placed
        on the mesh once, here): Pallas kernels cannot be partitioned
        automatically, and per-shard execution is exact for row-local
        activation blocks.  Sites whose activation exponent spans the
        whole batch (EQ2/EQ4) are refused when that axis has more than
        one device — their numerics would become per-shard.
        """
        key = (apply_fn, mesh, batch_axis if mesh is not None else None)
        fn = self._jit_cache.get(key)
        if fn is None:
            def fwd(params, x, *args, _apply=apply_fn):
                return _apply(params, x, self, *args)
            if mesh is not None:
                self._check_row_local(mesh, batch_axis)
            fn = BoundForward(fwd, self.params, mesh=mesh,
                              batch_axis=batch_axis)
            self._jit_cache[key] = fn
        return fn

    def _check_row_local(self, mesh, batch_axis) -> None:
        from repro.dist.sharding import axis_size
        shards = axis_size(mesh.shape, batch_axis)
        shared = sorted(
            p for p, s in self._sites.items()
            if s.policy is not None and s.policy.quantize_inputs
            and s.policy.scheme in (Scheme.EQ2, Scheme.EQ4))
        if shards > 1 and shared:
            raise ValueError(
                f"sites {shared} share one activation exponent across the "
                f"batch (EQ2/EQ4); split over {shards} {batch_axis!r} "
                f"shards it would become per-shard.  Serve a row-local "
                f"scheme (TILED, EQ3, EQ5) on a multi-device mesh.")

    def describe(self) -> str:
        """Human-readable site table (examples / serving admission logs)."""
        lines = []
        for path in sorted(self._sites):
            s = self._sites[path]
            pol = ("float" if s.policy is None else
                   f"L_W={s.policy.l_w},L_I={s.policy.l_i},"
                   f"{s.policy.scheme.value}")
            extra = (" (fallback)" if s.fallback else "") + \
                    (" [prequant]" if s.prequantized else "")

            def gdesc(spec):
                if spec is None or spec.policy is None:
                    return "float"
                gp = spec.policy
                be = spec.backend.name if spec.backend is not None else "?"
                return f"L{gp.l_w}/{gp.l_i}@{be}"

            grad = f" grad[dx={gdesc(s.dx)},dw={gdesc(s.dw)}]"
            lines.append(f"{path:<24} {s.kind:<5} {pol:<24} "
                         f"-> {s.backend.name}{extra}{grad}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# bind
# ---------------------------------------------------------------------------

def _validate_policy_backends(policy: PolicyLike) -> None:
    """Every backend a policy (or any PolicyMap rule) names must exist —
    raise the available_backends KeyError at BIND time, not mid-forward."""
    pols = []
    if isinstance(policy, PolicyMap):
        pols = [p for _, p in policy.rules] + [policy.default]
    elif isinstance(policy, BFPPolicy):
        pols = [policy]
    for p in pols:
        if p is not None:
            BK.get_backend(p.backend_name)


class _ScopedPolicy:
    """``resolve_policy`` adapter limiting a policy to an explicit site
    set — leaves outside ``wanted`` resolve to None (stay float)."""

    def __init__(self, policy: PolicyLike, wanted):
        self._policy, self._wanted = policy, wanted

    def resolve(self, path):
        if path not in self._wanted:
            return None
        return resolve_policy(self._policy, path)


#: shared with core.packed.pack_param_tree — one detector, one walk
_detect_tree = detect_tree_kind


def _discover_sites(params: Any, tree: str):
    """Yield (runtime_path, kind, weight_leaf) for every GEMM/conv site
    the param walk can see — the same path derivation the prequant
    walkers use, so rules pin and plans bind exactly the layers the
    model apply functions execute."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_prequant)
    for path, leaf in leaves:
        keys = _path_keys(path)
        arr = leaf["m"] if is_prequant(leaf) else leaf
        if not hasattr(arr, "ndim"):
            continue
        if tree == "lm":
            if not lm_eligible(keys) or arr.ndim < 2:
                continue
            yield lm_rule_path(keys), "gemm", leaf
        else:
            rpath = cnn_rule_path(params, keys)
            if rpath is None:
                continue
            if arr.ndim == 4:
                yield rpath, "conv", leaf
            elif arr.ndim == 2:
                yield rpath, "gemm", leaf


def bind(params: Any, policy: PolicyLike,
         model_paths: Optional[Iterable[Union[str, Tuple[str, str]]]] = None,
         *, tree: str = "auto", strict: bool = False,
         prequantize: bool = True, tune_cache: Any = None) -> Plan:
    """Bind ``policy`` to a model's parameters: one walk, one Plan.

    Args:
      params: model param tree (models.cnn or models.lm conventions; an
        already pre-quantized tree is fine — quantization is idempotent —
        and so is a packed artifact: ``PackedBFP`` leaves restored with
        ``checkpoint.store.restore(..., packed="keep")`` unpack directly
        into their ``{"m", "s"}`` sidecars here).
      policy: None / BFPPolicy / PolicyMap — resolved per site, once.
      model_paths: optional explicit site list — strings or (path, kind)
        pairs.  Restricts the discovered sites to these paths and binds
        policy-only entries (no weight checks, no prequant) for paths
        the tree walk cannot see.  Default: every site the walk finds.
      tree: "cnn" | "lm" | "auto" — which path convention the tree uses.
      strict: refuse (raise) backend downgrades instead of the once-per-
        site warning; also applied to unbound-path fallbacks at call time.
      prequantize: convert eligible weight leaves to the ``{"m", "s"}``
        wire format (set False to bind dispatch only, e.g. when the
        caller already pre-quantized under a different policy).
      tune_cache: a :class:`repro.tune.TuneCache` (or a path string —
        loaded here, missing file = empty cache) of autotuned tile
        winners; the plan activates it around every bound execution so
        kernels launch with tuned tiles (``python -m repro.tune`` fills
        one for the canonical layers).

    Raises KeyError for policies naming unknown backends, and
    :class:`repro.engine.backends.BackendUnsupportedError` under
    ``strict`` when a requested backend cannot honour its policy.
    """
    _validate_policy_backends(policy)
    if isinstance(tune_cache, str):
        from repro.tune.cache import TuneCache
        tune_cache = TuneCache.load(tune_cache)
    # packed serving artifacts (checkpoint restore(packed="keep")) unpack
    # straight into {"m", "s"} sidecars here — never through float
    params = unpack_packed(params)
    kind = _detect_tree(params) if tree == "auto" else tree
    if kind not in ("cnn", "lm"):
        raise ValueError(f"tree must be 'cnn', 'lm', or 'auto'; got {kind!r}")

    wanted: Optional[Dict[str, Optional[str]]] = None
    if model_paths is not None:
        wanted = {}
        for mp in model_paths:
            if isinstance(mp, str):
                wanted[mp] = None
            else:
                p, k = mp
                wanted[p] = k

    qparams = params
    if prequantize:
        quantizer = quantize_param_tree if kind == "lm" \
            else quantize_cnn_param_tree
        # a model_paths restriction also scopes prequantization: sites
        # outside it keep their float leaves (they are not bound, so
        # they must not be converted either)
        qpolicy = policy if wanted is None else _ScopedPolicy(policy,
                                                              wanted)
        qparams = quantizer(params, qpolicy)

    warned: set = set()   # fresh per bind: each plan reports its own

    def _bind_grad(path: str, which: str) -> GradSpec:
        # backward plans resolve on the DERIVED grad path; a float
        # backward GEMM needs no backend choice, a BFP one selects (and
        # under strict, refuses) its backend HERE — before any training
        # step runs.  The weight leaf is irrelevant to the backward
        # GEMMs (they contract transposed/gradient operands), so support
        # is checked policy-only; a K-tile fitted at call time
        # (grad.fit_grad_policy) re-selects honestly then.
        gpol = resolve_grad_policy(policy, path, which)
        if gpol is None:
            return GradSpec(None, None)
        gpath = grad_path(path, which)
        if (gpol.backend_name, path) in warned:
            # the forward site already reported this exact downgrade;
            # don't repeat it two more times for #dx/#dw (strict raises
            # regardless — the dedup is warning-only)
            warned.add((gpol.backend_name, gpath))
        be = BK.select_backend(gpol, None, strict=strict, path=gpath,
                               warned=warned)
        return GradSpec(gpol, be)

    sites: Dict[str, Site] = {}
    for path, skind, leaf in _discover_sites(qparams, kind):
        if wanted is not None and path not in wanted:
            continue
        if path in sites:
            continue  # stacked trees can alias a runtime path; first wins
        pol = resolve_policy(policy, path)
        if pol is None:
            be, fb = BK.get_backend("float"), False
        else:
            be = BK.select_backend(pol, leaf, strict=strict, path=path,
                                   warned=warned)
            fb = be.name != pol.backend_name
        sites[path] = Site(path, skind, pol, be, fb,
                           prequantized=is_prequant(leaf),
                           dx=_bind_grad(path, "dx"),
                           dw=_bind_grad(path, "dw"))

    if wanted is not None:  # policy-only entries for undiscovered paths
        for path, k in wanted.items():
            if path in sites:
                continue
            pol = resolve_policy(policy, path)
            if pol is None:
                be, fb = BK.get_backend("float"), False
            else:
                be = BK.select_backend(pol, None, strict=strict, path=path,
                                       warned=warned)
                fb = be.name != pol.backend_name
            sites[path] = Site(path, k or "gemm", pol, be, fb,
                               dx=_bind_grad(path, "dx"),
                               dw=_bind_grad(path, "dw"))

    return Plan(sites, qparams, policy, strict, tune_cache=tune_cache)
