"""Batched CNN inference service on sharded BFP plans (DESIGN.md §9).

The paper's workload is a CNN *accelerator* serving fixed-point
inference; this module gives the four paper models (and anything with
the ``apply(params, x, policy)`` convention) the same deployment path
the LM decode engine has:

  * a shape-stable slot table (``serve.slots.SlotTable``, the
    continuous-batching-lite bookkeeping shared with ``ServeEngine``):
    image requests admit into free slots, finished slots free
    immediately for the next queued request;
  * iteration-level batching over batch buckets: each step stacks
    whatever slots are active into the smallest configured batch bucket
    (padding with duplicates of a live image — logits-neutral for any
    weights), so the jitted forward compiles once per bucket, not once
    per request count, and a partially-filled step RUNS instead of
    waiting behind a bucket barrier (``batching="bucket"`` keeps the
    barrier — defer until ``buckets[-1]`` slots are active or
    ``max_wait`` deferred steps elapse — as the measured baseline for
    ``benchmarks/serve_load.py``);
  * a double-buffered input: while a step's last forward runs, the
    batch of the group the next step admits first is built from the
    queue's head and copied to the device (host images: one stack, one
    transfer), so under a backlog the copy in leaves the path between
    forwards; a group that is not exactly the staged one is built anew;
  * a bind-once ``engine.Plan``: policy resolution, backend selection,
    and weight pre-quantization happen at admission-time construction
    (``strict_backend=True`` rejects undeployable configs HERE);
    ``Plan.jit_forward`` means N engines bound to one plan share one
    traced forward per bucket shape;
  * data-parallel batch sharding through ``dist.sharding.axis_rules``
    + a ``launch.mesh`` mesh: the stacked batch is annotated
    ``("batch", None, None, None)`` and the forward runs under
    ``jax.shard_map`` (Pallas kernels cannot be partitioned
    automatically), so every device runs the kernels on its own slice
    of the batch; the same code runs 1-device in tier-1 tests.

Bit-exactness contract (pinned by tests/test_serve_cnn.py through
``engine.taps`` events): a request served through the engine produces
exactly the logits of a direct ``apply(plan.params, batch, plan)`` on
the same rows.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as EG
from repro.dist import sharding as DS
from repro.engine import PolicyLike
from repro.engine.backends import BackendUnsupportedError
from repro.engine.plan import BoundForward, Plan
from repro.models.cnn import head_logits
from repro.serve.degrade import (DeadlineExceeded, DegradeConfig,
                                 DegradeController, QueueOverloaded,
                                 float_params)
from repro.serve.slots import SlotTable
from repro.serve.spans import SpanRecorder

__all__ = ["ImageRequest", "CnnServeEngine", "default_buckets"]

#: logical axes of an NHWC image batch — only the batch axis shards
#: (pure data parallelism; DEFAULT_RULES maps "batch" -> "data")
_BATCH_AXES = ("batch", None, None, None)


@dataclasses.dataclass
class ImageRequest:
    """One classification request: an [H, W, C] image in, logits out.

    ``deadline`` is an absolute value of the engine's monotonic clock;
    a request that has not produced logits by then completes
    exceptionally (``error`` = :class:`DeadlineExceeded`).  ``error``
    is set (and ``logits`` stays None) whenever the request failed —
    deadline expiry or a forward that raised.  ``degraded`` reports
    which plan served it (True = the lower-L fallback plan).
    """

    rid: int
    image: jax.Array
    logits: Optional[np.ndarray] = None
    label: Optional[int] = None
    done: bool = False
    deadline: Optional[float] = None
    error: Optional[BaseException] = None
    degraded: bool = False
    #: ``time.perf_counter_ns`` at ``submit`` (the queue wait's start)
    submitted_ns: int = 0


class _Staged(NamedTuple):
    """A batch built ahead of its forward (:meth:`CnnServeEngine._stage`)."""

    reqs: Tuple[ImageRequest, ...]
    bucket: int
    x: jax.Array


def default_buckets(slots: int) -> Tuple[int, ...]:
    """Powers of two up to ``slots`` (plus ``slots`` itself): 8 -> (1, 2,
    4, 8), 6 -> (1, 2, 4, 6).  One jit compilation per bucket."""
    out: List[int] = []
    b = 1
    while b < slots:
        out.append(b)
        b *= 2
    out.append(slots)
    return tuple(out)


class CnnServeEngine:
    """Slot-table batched CNN server over a bound execution plan.

    Args:
      params: param tree (``models.cnn`` conventions) — float, already
        pre-quantized ``{"m", "s"}``, or a packed artifact holding
        ``PackedBFP`` leaves (``checkpoint.store.restore(...,
        packed="keep")``): ``engine.bind`` unpacks those straight into
        sidecars, so serving loads the ~4x-smaller checkpoint without
        ever materializing float weights for prequant-eligible sites.
        Ignored when ``policy`` is already a bound :class:`engine.Plan`
        — pass ``None`` and reuse the plan's pre-quantized params (that
        is the multi-engine deployment shape: bind once, serve many).
      apply_fn: ``apply_fn(params, x, policy)`` -> logits, or a tuple of
        heads (GoogLeNet) — head 0 is taken as the classifier output.
      policy: None / BFPPolicy / PolicyMap (bound here via
        ``engine.bind``) or an existing ``Plan`` (reused as-is).
      slots: size of the admission slot table (max requests in flight).
      buckets: ascending batch-bucket sizes; each step pads the active
        group up to the smallest fitting bucket.  Default:
        ``default_buckets(slots)``.
      prequant: pre-quantize eligible weight leaves at bind time (the
        paper's deployment mode).  Ignored when ``policy`` is a Plan.
      strict_backend: refuse (raise) backend downgrades at construction
        instead of warn-once — an undeployable serving config fails at
        admission, not mid-traffic.  With a pre-bound Plan this verifies
        the plan carries no downgraded (fallback) sites.
      mesh / rules: optional ``launch.mesh`` mesh + logical-axis rules
        (default ``dist.sharding.DEFAULT_RULES``); when given, every
        forward runs under ``jax.shard_map`` with the batch split over
        the mesh axis the ``"batch"`` rule names (``Plan.jit_forward``),
        and buckets round up to multiples of that axis's size.
      jit: jit the bound forward (shared across engines via
        ``Plan.jit_forward``).  ``jit=False`` runs eagerly — slower,
        but ``engine.taps`` observers see every GEMM/conv site (taps
        are suppressed under jit tracing), which is how the
        bit-exactness regression pins this engine to the direct path.
      max_queue: queue depth limit; ``submit`` beyond it raises the
        typed :class:`~repro.serve.degrade.QueueOverloaded` (the request
        is never enqueued).  None = unbounded (the historical behavior).
      fallback_policy: a lower-L policy (or pre-bound Plan) to serve new
        admissions with while overloaded — bound ONCE here, so the
        degraded path never binds mid-traffic.  Requires ``params``
        unless a Plan is passed.  None disables degraded mode.
      degrade: watermarks/hysteresis for the overload state machine
        (default ``DegradeConfig(queue_high=slots)`` when
        ``fallback_policy`` is set).
      float_retry: when a group's logits come back non-finite, re-run
        that group ONCE on the float reference (the serving plan's
        weights dequantized, ``policy=None``) before reporting — a
        blown-up BFP datapath (exponent SEU, corrupted container)
        degrades to float numerics instead of returning NaNs.
      batching: ``"continuous"`` (default) runs partially-filled steps
        immediately — iteration-level batching, no bucket barrier.
        ``"bucket"`` is the barrier baseline: a step with fewer than
        ``buckets[-1]`` active slots defers its forward (up to
        ``max_wait`` consecutive deferred steps, so a trickle of
        requests still completes) hoping more arrivals fill the bucket.
      max_wait: bucket-mode flush bound — after this many consecutive
        deferred steps the partial batch runs anyway.  Ignored in
        continuous mode.
      clock: monotonic clock for deadlines (injectable for tests).
    """

    def __init__(self, params: Any, apply_fn: Callable[..., Any],
                 policy: PolicyLike = None, *, slots: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 prequant: bool = True, strict_backend: bool = False,
                 mesh=None, rules: Optional[Dict[str, Any]] = None,
                 jit: bool = True, max_queue: Optional[int] = None,
                 fallback_policy: PolicyLike = None,
                 degrade: Optional[DegradeConfig] = None,
                 float_retry: bool = True,
                 batching: str = "continuous", max_wait: int = 4,
                 clock: Callable[[], float] = time.monotonic):
        if batching not in ("continuous", "bucket"):
            raise ValueError(f"batching must be 'continuous' or 'bucket', "
                             f"got {batching!r}")
        if max_wait < 0:
            raise ValueError(f"max_wait must be >= 0, got {max_wait}")
        self.batching = batching
        self.max_wait = max_wait
        self._waited = 0   # consecutive bucket-mode deferred steps
        if isinstance(policy, Plan):
            # bind-once reuse across engines: the plan's params serve,
            # and its backend selection is already fixed — enforce the
            # documented contract instead of silently ignoring args
            if params is not None:
                raise ValueError("pass params=None when policy is a "
                                 "bound Plan (the plan's params serve)")
            if strict_backend:
                bad = sorted(s.path for s in policy.sites.values()
                             if s.fallback)
                if bad:
                    raise BackendUnsupportedError(
                        f"strict_backend: plan carries downgraded sites "
                        f"{bad}; rebind with engine.bind(..., strict=True)")
            self.plan = policy
        else:
            self.plan = EG.bind(params, policy, tree="cnn",
                                strict=strict_backend,
                                prequantize=prequant)
        self.apply_fn = apply_fn
        self.table = SlotTable(slots)
        self.buckets = (tuple(sorted(buckets)) if buckets
                        else default_buckets(slots))
        if self.buckets[-1] < 1:
            raise ValueError(f"bad buckets {self.buckets}")
        self.mesh = mesh
        self.rules = dict(rules) if rules is not None \
            else dict(DS.DEFAULT_RULES)
        ax = self.rules.get("batch") if mesh is not None else None
        self._batch_axis = tuple(ax) if isinstance(ax, list) else ax
        if self._batch_axis is not None:
            # shard_map splits each batch evenly over the batch axis:
            # round every bucket up to a multiple of its size
            d = DS.axis_size(mesh.shape, self._batch_axis)
            self.buckets = tuple(sorted({-(-b // d) * d
                                         for b in self.buckets}))
        self._jit = jit
        self._fwd = self._make_fwd(self.plan)
        self._shape: Optional[Tuple[int, ...]] = None
        self._next_rid = 0
        # -- graceful degradation state ---------------------------------
        self.max_queue = max_queue
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._clock = clock
        self._float_retry = float_retry
        self._float_fwds: Dict[bool, Callable[..., Any]] = {}
        if fallback_policy is not None:
            if isinstance(fallback_policy, Plan):
                self.fallback_plan: Optional[Plan] = fallback_policy
            else:
                if params is None:
                    raise ValueError(
                        "fallback_policy needs params to bind against; "
                        "pass a pre-bound Plan when reusing policy=Plan")
                self.fallback_plan = EG.bind(params, fallback_policy,
                                             tree="cnn",
                                             strict=strict_backend,
                                             prequantize=prequant)
            self._fb_fwd = self._make_fwd(self.fallback_plan)
            self.controller: Optional[DegradeController] = \
                DegradeController(degrade or DegradeConfig(
                    queue_high=slots))
        else:
            self.fallback_plan = None
            self._fb_fwd = None
            self.controller = (DegradeController(degrade)
                               if degrade is not None else None)
        #: serving counters — the shared taxonomy (DESIGN.md §9): every
        #: request ends in exactly one of completed/expired/failed
        #: (shed requests were never enqueued); float_retries and
        #: degraded_served tag HOW completions were served.  ``forwards``
        #: counts batched forwards issued (retries included), ``rows`` /
        #: ``rows_live`` their bucket rows and the live ones among them,
        #: ``patch_convs`` their conv launches on the narrow-channel
        #: patch path (``kernels.ops.bfp_conv2d``; per forward it is
        #: ``patch_convs / forwards``), ``conv_macs_issued`` the MACs
        #: their conv launches issued to the MXU, padding included
        #: (``engine.plan.BoundForward.conv_macs_issued``),
        #: ``batches_staged`` the forwards whose batch was built while
        #: the forward before them ran (:meth:`_stage`), ``admitted``
        #: the requests given a slot and ``queue_wait_ns`` their time
        #: from submit to admission, and ``host_ns.<phase>`` the host
        #: time of each phase of :meth:`step` (``self.spans``,
        #: docs/tracing.md)
        self.stats: Dict[str, int] = {"shed": 0, "expired": 0,
                                      "failed": 0, "completed": 0,
                                      "float_retries": 0,
                                      "degraded_served": 0,
                                      "forwards": 0, "rows": 0,
                                      "rows_live": 0, "patch_convs": 0,
                                      "conv_macs_issued": 0,
                                      "batches_staged": 0,
                                      "admitted": 0,
                                      "queue_wait_ns": 0}
        self.spans = SpanRecorder(self.stats)
        #: the next step's first batch, built by :meth:`_stage`, or None
        self._staged: Optional[_Staged] = None

    @property
    def ncalls(self) -> int:
        """Total batched forwards issued (retries included) — the load
        harness's machine-independent virtual-time unit (serve.load
        ``call_cost``)."""
        return self.stats["forwards"]

    def _make_fwd(self, plan: Plan) -> Callable[..., Any]:
        if not self._jit:
            return BoundForward(lambda p, x: self.apply_fn(p, x, plan),
                                plan.params, jit=False)
        if self._batch_axis is None:
            return plan.jit_forward(self.apply_fn)
        return plan.jit_forward(self.apply_fn, mesh=self.mesh,
                                batch_axis=self._batch_axis)

    # -- admission ----------------------------------------------------------

    def submit(self, req: Any = None, *, image: Optional[jax.Array] = None
               ) -> ImageRequest:
        """Queue a request (or wrap a bare ``image=`` into one).

        All images must share one [H, W, C] shape — the slot table is
        shape-stable by construction.  With ``max_queue`` set, a full
        queue sheds the submission with the typed
        :class:`~repro.serve.degrade.QueueOverloaded` instead of
        queueing unboundedly.
        """
        if req is None:
            if image is None:
                raise ValueError("pass a request or image=")
            req = ImageRequest(rid=self._next_rid, image=image)
        if self.max_queue is not None and \
                len(self.table.queue) >= self.max_queue:
            self.stats["shed"] += 1
            raise QueueOverloaded(
                f"queue depth {len(self.table.queue)} at limit "
                f"{self.max_queue}; request {req.rid} shed", rid=req.rid)
        self._next_rid = max(self._next_rid, req.rid) + 1
        img = req.image
        if getattr(img, "ndim", 0) != 3:
            raise ValueError(f"image must be [H, W, C], got "
                             f"{getattr(img, 'shape', None)}")
        if self._shape is None:
            self._shape = tuple(img.shape)
        elif tuple(img.shape) != self._shape:
            raise ValueError(f"image shape {tuple(img.shape)} != engine "
                             f"shape {self._shape} (slot table is "
                             f"shape-stable)")
        req.submitted_ns = time.perf_counter_ns()
        self.table.submit(req)
        return req

    # -- serving ------------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _sharding_ctx(self):
        return (DS.axis_rules(self.rules, self.mesh)
                if self.mesh is not None else contextlib.nullcontext())

    def _float_fwd(self, degraded: bool) -> Callable[..., Any]:
        """Lazily built float-reference forward of the serving plan's
        own (quantized) weights — the non-finite-logits retry path."""
        fwd = self._float_fwds.get(degraded)
        if fwd is None:
            plan = self.fallback_plan if degraded else self.plan
            tree = float_params(plan.params)
            fn = self.apply_fn

            fwd = BoundForward(lambda t, x: fn(t, x, None), tree,
                               jit=self._jit)
            self._float_fwds[degraded] = fwd
        return fwd

    def _fail_group(self, group: List[int], reqs: List[ImageRequest],
                    exc: BaseException) -> None:
        """Complete every request of a failed group exceptionally and
        free its slot — a raising forward must never leak slots (the
        table would otherwise fill with zombies and admission would
        stall forever)."""
        for s, r in zip(group, reqs):
            r.error = exc
            r.done = True
            self.stats["failed"] += 1
            self.table.free(s)

    def _expire(self) -> None:
        """Fail every queued or admitted request whose deadline passed.

        Runs BEFORE admission in :meth:`step`: a dead queued request
        must never occupy a slot (or pad out a forward) only to be
        failed afterwards.
        """
        now = self._clock()

        def dead(r):
            return r.deadline is not None and now > r.deadline

        expired_q = self.table.retain(lambda r: not dead(r))
        for s in self.table.active():
            r = self.table.req[s]
            if dead(r):
                expired_q.append(r)
                self.table.free(s)
        for r in expired_q:
            r.error = DeadlineExceeded(
                f"request {r.rid} missed deadline {r.deadline}", rid=r.rid)
            r.done = True
            self.stats["expired"] += 1

    def _build(self, reqs: Sequence[ImageRequest], bucket: int
               ) -> jax.Array:
        """The requests' images as the sharded device batch of ``bucket``
        rows."""
        imgs = [r.image for r in reqs]
        if len(imgs) < bucket:
            # pad with a DUPLICATE of a live image: rows are processed
            # independently by every conv/GEMM, so a duplicate row's
            # activations equal its original's at every layer and can
            # never raise a shared block max above the live rows' own —
            # logits-neutral for ANY weights.  (A zero image is only
            # neutral while zero rows STAY zero, i.e. zero biases/BN
            # shifts; a trained model's bias pattern could otherwise own
            # an EQ2/EQ4 whole-matrix exponent from layer 2 on.)
            imgs = imgs + [imgs[0]] * (bucket - len(imgs))
        with self._sharding_ctx():
            if all(isinstance(im, np.ndarray) for im in imgs):
                # host images: one stack into a fresh host array (no
                # buffer an in-flight copy may still read is reused) and
                # one transfer straight to the batch's sharding — where
                # jnp.stack would copy each image in on its own and run
                # a device program per image
                x = np.stack(imgs)
                return jax.device_put(
                    x, DS.named_sharding(x.shape, *_BATCH_AXES))
            return DS.shard(jnp.stack(imgs), *_BATCH_AXES)

    def _batch_in(self, reqs: List[ImageRequest], bucket: int) -> jax.Array:
        """The group's device batch: the staged one when it holds exactly
        these requests, in this order, at this bucket; else built now."""
        staged, self._staged = self._staged, None
        if staged is not None and staged.bucket == bucket and \
                len(staged.reqs) == len(reqs) and \
                all(a is b for a, b in zip(staged.reqs, reqs)):
            self.stats["batches_staged"] += 1
            return staged.x
        return self._build(reqs, bucket)

    def _stage(self) -> None:
        """Build the batch of the group the next step admits first (the
        queue's head, as many as a group and the slots hold) while the
        forward just dispatched runs.  Every slot frees by the end of this
        step, so that group is exactly this head unless a request expires
        or the queue changes meanwhile — which :meth:`_batch_in` sees as
        a miss, never as a wrong row."""
        n = min(self.buckets[-1], self.table.slots, len(self.table.queue))
        reqs = tuple(itertools.islice(self.table.queue, n))
        bucket = self._bucket_for(n)
        try:
            self._staged = _Staged(reqs, bucket, self._build(reqs, bucket))
        except Exception:                         # noqa: BLE001
            # the batch belongs to the next group, not to the one in
            # flight: leave nothing staged, and the next _batch_in builds
            # it again and fails the requests it belongs to if it must
            self._staged = None

    def _dispatch(self, fwd: Callable[..., Any], x: jax.Array,
                  live: int) -> Any:
        """Issue one forward of ``x`` (``live`` of its rows are
        requests); returns before the device finishes."""
        self.stats["forwards"] += 1
        self.stats["rows"] += x.shape[0]
        self.stats["rows_live"] += live
        with self._sharding_ctx():
            out = fwd(x)
        # a forward wrapped by its caller (the benchmark's planted
        # faults wrap Plan.jit_forward) is no BoundForward and counts none
        self.stats["patch_convs"] += getattr(fwd, "patch_convs", 0)
        self.stats["conv_macs_issued"] += getattr(
            fwd, "conv_macs_issued", {}).get(x.shape[0], 0)
        return out

    def _complete(self, group: List[int], reqs: List[ImageRequest],
                  logits: np.ndarray, degraded: bool) -> None:
        for i, (s, r) in enumerate(zip(group, reqs)):
            r.logits = logits[i]
            r.label = int(np.argmax(logits[i]))
            r.done = True
            r.degraded = degraded
            self.stats["completed"] += 1
            if degraded:
                self.stats["degraded_served"] += 1
            self.table.free(s)

    def _run_group(self, group: List[int], degraded: bool = False,
                   stage: bool = False) -> None:
        """One forward of the requests in ``group``'s slots, timed by
        phase (``serve.batch_in``, ``.dispatch``, ``.stage`` when
        ``stage`` and the queue holds requests, ``.logits_wait``,
        ``.finish``, and ``.float_retry`` when the logits were not
        finite)."""
        span = self.spans.span
        reqs = [self.table.req[s] for s in group]
        try:
            with span("serve.batch_in"):
                x = self._batch_in(reqs, self._bucket_for(len(reqs)))
            with span("serve.dispatch"):
                out = self._dispatch(self._fb_fwd if degraded else self._fwd,
                                     x, len(reqs))
            if stage and self.table.queue:
                with span("serve.stage"):
                    self._stage()
            with span("serve.logits_wait"):
                logits = np.asarray(head_logits(out))
        except Exception as e:                    # noqa: BLE001 — slots
            self._fail_group(group, reqs, e)      # must never leak
            return
        with span("serve.finish"):
            if not self._float_retry or \
                    np.all(np.isfinite(logits[:len(reqs)])):
                self._complete(group, reqs, logits, degraded)
                return
        with span("serve.float_retry"):
            # one retry on the float reference of the SAME weights:
            # isolates a blown-up BFP datapath (exponent SEU, bad
            # container) from a genuinely divergent model
            self.stats["float_retries"] += 1
            try:
                logits = np.asarray(head_logits(self._dispatch(
                    self._float_fwd(degraded), x, len(reqs))))
            except Exception as e:                # noqa: BLE001
                self._fail_group(group, reqs, e)
                return
            self._complete(group, reqs, logits, degraded)

    def step(self) -> int:
        """One engine iteration; returns the number of requests still
        queued or in flight AFTER the step (0 == drained) — the unified
        drive-loop contract both serve engines share (DESIGN.md §9):
        ``while eng.step(): ...`` serves to completion.  Completions are
        counted in ``stats["completed"]``, not the return value.

        Order per step: the controller observes the pre-admission queue
        depth, expiry runs BEFORE admission (a dead queued request is
        failed without ever occupying a slot), then the active slots run
        — immediately in continuous mode (partially-filled steps pad up
        to the smallest fitting bucket), or behind the bucket barrier in
        ``batching="bucket"`` (defer the forward until ``buckets[-1]``
        slots are active or ``max_wait`` consecutive deferred steps
        elapse).  While DEGRADED every admission of this step is tagged
        for (and served by) the pre-bound lower-L fallback plan.

        The step runs as one ``serve.step`` of :attr:`spans`; admission
        and expiry are its ``serve.admit`` phase, then each group's
        phases follow (:meth:`_run_group`).  While the last group's
        forward runs, the next step's first batch is built from the
        queue's head (``serve.stage``), so with a backlog the copy in of
        every forward but the first leaves the path between forwards.
        """
        with self.spans.step():
            with self.spans.span("serve.admit"):
                degraded = False
                if self.controller is not None:
                    state = self.controller.observe(len(self.table.queue))
                    degraded = (state == DegradeController.DEGRADED and
                                self._fb_fwd is not None)
                self._expire()
                now = time.perf_counter_ns()
                for s in self.table.admit():
                    self.stats["admitted"] += 1
                    self.stats["queue_wait_ns"] += \
                        now - self.table.req[s].submitted_ns
                active = self.table.active()
            if not active:
                return self.table.pending()
            cap = self.buckets[-1]
            if self.batching == "bucket" and len(active) < cap and \
                    self._waited < self.max_wait:
                # bucket barrier: hold the partial batch hoping arrivals
                # fill it — exactly the p99 stall continuous mode removes
                self._waited += 1
                return self.table.pending()
            self._waited = 0
            for i in range(0, len(active), cap):
                # the last group stages the next step's first batch
                self._run_group(active[i:i + cap], degraded=degraded,
                                stage=i + cap >= len(active))
            return self.table.pending()

    def run(self) -> List[Any]:
        """Drain the queue; returns the requests still in flight or
        queued when called.  Requests a prior step() already COMPLETED
        are not re-reported — keep your own list (as launch.serve_cnn
        does) when accounting across manual step() calls."""
        all_reqs = [self.table.req[s] for s in self.table.active()] + \
            list(self.table.queue)
        while self.table.pending():
            self.step()
        return all_reqs
