"""Batched serving engine: prefill + decode with KV/recurrent caches.

``generate`` drives the jitted decode_step over N tokens with greedy or
temperature sampling.  ``ServeEngine`` adds iteration-level (continuous)
batching: a slot table where finished sequences are replaced by queued
requests between decode steps (the Python driver swaps rows; the jitted
step is shape-stable), with PREFILL CHUNKED INTO THE STEP LOOP — an
admission consumes at most ``prefill_chunk`` prompt tokens per engine
step, so a long-prompt admission never stalls in-flight decodes behind
``len(prompt)`` jitted calls (``batching="bucket"`` keeps the legacy
blocking-prefill behaviour as the baseline the load harness
``serve.load`` compares against).  BFP weight pre-quantization
(``prequant=`` or an already-converted param tree) is the paper's
deployment mode, where weights live in HBM as int8 mantissas + exponent
sidecars, every GEMM runs the fixed-point datapath, and quantization
happens ONCE at engine construction, not per decode step.  ``policy`` may be
a per-layer ``repro.engine.PolicyMap``; at construction it is bound into
an ``engine.Plan`` (``self.plan``) so rule resolution and backend
selection also happen once, at admission-time weight load, and
``strict_backend=True`` rejects configs whose requested backend cannot
honour the policy (DESIGN.md §7.1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import engine as EG
from repro.configs.base import LMConfig
from repro.engine import PolicyLike
from repro.models.lm import model as Mdl
from repro.serve.degrade import (DeadlineExceeded, DegradeConfig,
                                 DegradeController, QueueOverloaded,
                                 RequestTooLarge, float_params)
from repro.serve.slots import SlotTable

__all__ = ["prefill", "generate", "ServeEngine", "Request"]


def prefill(params, cfg: LMConfig, tokens: jax.Array, cache,
            policy: PolicyLike = None,
            enc_feats: Optional[jax.Array] = None):
    """Sequential prefill through decode_step (state-correct for every
    family).  tokens: [B, S_prompt].  Returns (cache, last_logits)."""
    if cfg.is_encdec and enc_feats is not None:
        cache = dict(cache,
                     enc_out=Mdl.prefill_encoder(params, cfg, enc_feats,
                                                 policy))

    def body(carry, t):
        cache, _ = carry
        logits, cache = Mdl.decode_step(params, cfg, cache,
                                        tokens[:, t][:, None],
                                        t.astype(jnp.int32), policy)
        return (cache, logits), None

    zero_logits = jnp.zeros((tokens.shape[0], 1, cfg.vocab_size),
                            jnp.float32)
    (cache, logits), _ = jax.lax.scan(body, (cache, zero_logits),
                                      jnp.arange(tokens.shape[1]))
    return cache, logits


def generate(params, cfg: LMConfig, prompt: jax.Array, max_new: int,
             policy: PolicyLike = None, temperature: float = 0.0,
             key: Optional[jax.Array] = None,
             enc_feats: Optional[jax.Array] = None,
             max_len: Optional[int] = None) -> jax.Array:
    """Greedy/temperature generation.  Returns [B, max_new] tokens."""
    b, s = prompt.shape
    max_len = max_len or (s + max_new)
    cache = Mdl.init_cache(cfg, b, max_len)
    cache, logits = prefill(params, cfg, prompt, cache, policy, enc_feats)
    key = key if key is not None else jax.random.PRNGKey(0)

    def sample(logits, k):
        lg = logits[:, -1].astype(jnp.float32)
        if temperature <= 0.0:
            return jnp.argmax(lg, -1).astype(jnp.int32)
        return jax.random.categorical(k, lg / temperature).astype(jnp.int32)

    def body(carry, i):
        cache, tok, k = carry
        k, ks = jax.random.split(k)
        logits, cache = Mdl.decode_step(params, cfg, cache, tok[:, None],
                                        (s + i).astype(jnp.int32), policy)
        nxt = sample(logits, ks)
        return (cache, nxt, k), nxt

    first = sample(logits, key)
    (_, _, _), toks = jax.lax.scan(body, (cache, first, key),
                                   jnp.arange(1, max_new))
    return jnp.concatenate([first[:, None], toks.T], axis=1)


# ---------------------------------------------------------------------------
# Iteration-level continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: absolute engine-clock deadline; missing it completes the request
    #: exceptionally (``error`` = DeadlineExceeded) with partial ``out``
    deadline: Optional[float] = None
    error: Optional[BaseException] = None
    #: True when the request was admitted onto the lower-L fallback plan
    degraded: bool = False


class ServeEngine:
    """Slot-table batched server (shape-stable jitted decode step).

    Iteration-level batching (``batching="continuous"``, the default):
    every :meth:`step` expires, admits, and advances — free slots take
    queued requests with NO up-front prefill; a prefilling slot consumes
    at most ``prefill_chunk`` prompt tokens per step while already-active
    slots keep decoding one token per step, in the SAME grouped jitted
    calls wherever positions coincide.  Finished slots free immediately
    and are re-admitted the next step, so a slow admission never erects
    a barrier in front of in-flight work.  ``batching="bucket"`` keeps
    the legacy behaviour — admission runs the WHOLE prompt's jitted
    prefill before any active slot decodes — as the bucket-barrier
    baseline the load harness (``serve.load``) measures continuous
    batching against.

    Row independence makes both modes bit-identical per request to solo
    serving (pinned by tests/test_system.py + tests/test_serve_continuous
    .py): each slot's cache rows only ever see its own tokens at its own
    positions.
    """

    def __init__(self, params, cfg: LMConfig, slots: int = 4,
                 max_len: int = 512,
                 policy: PolicyLike = None,
                 prequant: PolicyLike = None,
                 strict_backend: bool = False,
                 max_queue: Optional[int] = None,
                 fallback_policy: PolicyLike = None,
                 degrade: Optional[DegradeConfig] = None,
                 float_retry: bool = True,
                 batching: str = "continuous",
                 prefill_chunk: Optional[int] = 8,
                 clock: Callable[[], float] = time.monotonic):
        if cfg.is_encdec:
            # decode-only slot engine: no encoder prefill path, and the
            # enc_out cache leaf ([B, S, D]) breaks the slot-axis-at-dim-1
            # contract _merge_rows relies on
            raise ValueError("ServeEngine does not serve encoder-decoder "
                             "configs; use serve.generate with enc_feats")
        if batching not in ("continuous", "bucket"):
            raise ValueError(f"batching must be 'continuous' or 'bucket', "
                             f"got {batching!r}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 or None, got "
                             f"{prefill_chunk}")
        # packed weight artifacts (checkpoint.store format="bfp_packed",
        # restored with packed="keep") unpack straight into {"m", "s"}
        # sidecars at admission — the ~4x-smaller load path; float
        # weights are never materialized for those sites
        params = EG.unpack_packed(params)
        if prequant is not None:
            # cached pre-quantized weights: block-format once here, serve
            # the int8+scale wire format on every subsequent GEMM
            params = EG.prequantize(params, prequant)
        # Admission-time bind: resolve every site's PolicyMap rule and
        # select its concrete backend ONCE, at weight load — decode steps
        # dispatch through the bound plan instead of re-resolving per
        # call.  ``strict_backend=True`` makes a serving config that
        # requested a backend the policy can't run on FAIL HERE (raising
        # BackendUnsupportedError) instead of silently drifting onto the
        # emulated path.  Weight quantization stays governed by the
        # ``prequant`` arg above, so numerics are unchanged.
        self.plan = EG.bind(params, policy, tree="lm",
                            strict=strict_backend, prequantize=False)
        self.params, self.cfg, self.policy = params, cfg, self.plan
        self.slots = slots
        self.max_len = max_len
        self.batching = batching
        self.prefill_chunk = prefill_chunk
        self.cache = Mdl.init_cache(cfg, slots, max_len)
        #: pristine per-slot state for admission-time row resets
        self._cache0 = self.cache
        #: shared slot-table bookkeeping (serve.slots); ``slot_req`` and
        #: ``queue`` are aliases of the table's containers, so row-level
        #: code below mutates the same state the table reports on
        self.table = SlotTable(slots)
        self.slot_req: List[Optional[Request]] = self.table.req
        self.slot_pos = [0] * slots
        #: prompt tokens already consumed by the slot's occupant; a slot
        #: with ``slot_fed < len(prompt)`` is still prefilling
        self.slot_fed = [0] * slots
        self.queue = self.table.queue
        self._tok = jnp.zeros((slots, 1), jnp.int32)

        plan = self.plan

        def _step(cache, tok, pos):
            return Mdl.decode_step(params, cfg, cache, tok, pos, plan)

        self._step = jax.jit(_step)

        # -- graceful degradation state ---------------------------------
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        self._clock = clock
        self._float_retry = float_retry
        self._float_step = None
        #: per-slot plan tag: True = this slot decodes on the fallback
        #: plan for its whole lifetime (a request never switches plans
        #: mid-sequence — its numerics stay internally consistent)
        self.slot_deg: List[bool] = [False] * slots
        if fallback_policy is not None:
            fb_plan = EG.bind(params, fallback_policy, tree="lm",
                              strict=strict_backend, prequantize=False)
            self.fallback_plan = fb_plan

            def _step_fb(cache, tok, pos):
                return Mdl.decode_step(params, cfg, cache, tok, pos,
                                       fb_plan)

            self._step_fb = jax.jit(_step_fb)
            self.controller: Optional[DegradeController] = \
                DegradeController(degrade or DegradeConfig(
                    queue_high=slots))
        else:
            self.fallback_plan = None
            self._step_fb = None
            self.controller = (DegradeController(degrade)
                               if degrade is not None else None)
        self.stats: Dict[str, int] = {"shed": 0, "expired": 0,
                                      "failed": 0, "completed": 0,
                                      "float_retries": 0,
                                      "degraded_served": 0}
        #: total jitted decode calls issued (prefill + decode + retries)
        #: — the load harness's machine-independent virtual-time unit
        #: (serve.load ``call_cost``): one whole-batch decode_step is
        #: one unit of accelerator occupancy regardless of host speed
        self.ncalls = 0

    def submit(self, req: Request):
        """Queue a request, validating it against the cache geometry.

        A request that cannot fit the cache is refused with the typed
        :class:`~repro.serve.degrade.RequestTooLarge`: decode positions
        past ``max_len`` would be CLAMPED/DROPPED by JAX's out-of-bounds
        ``.at[].set`` semantics (no error is ever raised in jit), so the
        engine would silently serve logits computed from a corrupt
        cache.  ``max_new < 1`` is refused too — the decode loop always
        emits at least one token, so "zero tokens" is not a request this
        engine can honour.
        """
        if not req.prompt:
            # an empty prompt would leave the prefill loop with no
            # logits to seed the first decode from, wedging the slot
            raise ValueError("request prompt must be non-empty")
        if req.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {req.max_new} "
                             f"(the decode loop always emits a token)")
        if len(req.prompt) + req.max_new > self.max_len:
            raise RequestTooLarge(
                f"request {req.rid}: len(prompt)={len(req.prompt)} + "
                f"max_new={req.max_new} exceeds the cache length "
                f"{self.max_len}; out-of-bounds cache writes are silently "
                f"clamped under jit, corrupting the logits", rid=req.rid)
        if self.max_queue is not None and \
                len(self.table.queue) >= self.max_queue:
            self.stats["shed"] += 1
            raise QueueOverloaded(
                f"queue depth {len(self.table.queue)} at limit "
                f"{self.max_queue}; request {req.rid} shed", rid=req.rid)
        self.table.submit(req)

    def _merge_rows(self, old, new, rows):
        """Keep only slot ``rows`` of the stepped cache; every other
        slot's rows are restored from ``old``.

        The jitted step is whole-batch and decode_step takes ONE scalar
        position, so any call writes every slot's cache row at that
        position — garbage for slots that are at a different position.
        ``init_cache`` puts the slot axis at dim 1 on every leaf
        ([n_layers, B, ...]) for the families this engine serves
        (encoder-decoder configs are rejected at construction), so the
        mask is structural, not guessed.
        """
        sel = jnp.zeros((self.slots,), bool)
        sel = sel.at[jnp.asarray(rows)].set(True)

        def one(o, n):
            shape = [1] * o.ndim
            shape[1] = self.slots
            return jnp.where(sel.reshape(shape), n, o)

        return jax.tree_util.tree_map(one, old, new)

    def _slot_step(self, s: int):
        """The jitted step serving slot ``s`` (primary or fallback)."""
        return self._step_fb if self.slot_deg[s] else self._step

    def _float_step_fn(self):
        """Lazily built float-reference decode step (retry path)."""
        if self._float_step is None:
            ftree = float_params(self.params)
            cfg = self.cfg

            def _fstep(cache, tok, pos):
                return Mdl.decode_step(ftree, cfg, cache, tok, pos, None)

            self._float_step = jax.jit(_fstep)
        return self._float_step

    def _fail_slots(self, slots: List[int], exc: BaseException) -> None:
        """Complete the requests in ``slots`` exceptionally and free them
        — a raising step must never leak slots."""
        for s in slots:
            req = self.slot_req[s]
            if req is None:
                continue
            req.error = exc
            req.done = True
            self.stats["failed"] += 1
            self.table.free(s)

    def _expire(self) -> None:
        """Fail queued or decoding requests whose deadline passed (their
        partial ``out`` stays — the client sees how far decode got).

        Runs BEFORE admission in :meth:`step`: an already-dead queued
        request must never be admitted (and, worst, prefilled for
        ``len(prompt)`` jitted calls) only to be failed afterwards.
        """
        now = self._clock()

        def dead(r):
            return r.deadline is not None and now > r.deadline

        expired = self.table.retain(lambda r: not dead(r))
        for s in self.table.active():
            r = self.slot_req[s]
            if dead(r):
                expired.append(r)
                self.table.free(s)
        for r in expired:
            r.error = DeadlineExceeded(
                f"request {r.rid} missed deadline {r.deadline}", rid=r.rid)
            r.done = True
            self.stats["expired"] += 1

    def _reset_slot(self, s: int, req: Request, degraded: bool) -> None:
        """Admission-time slot bookkeeping shared by both batching modes.

        Plan choice is an ADMISSION decision: the slot keeps it for the
        request's whole decode (prefill included), so degraded requests
        are end-to-end lower-L — bit-exact vs a direct lower-L bind —
        rather than a mid-sequence numeric splice.  The cache rows reset
        to pristine state: recurrent families (ssm/hybrid)
        read-modify-write their states h' = f(h, x), so a reused slot
        must not prefill from the previous occupant's (or a
        wholesale-stepped garbage) state.  KV rows are
        position-overwritten anyway, so this costs one merge and buys
        correctness for every cache family.
        """
        self.slot_deg[s] = degraded and self._step_fb is not None
        req.degraded = self.slot_deg[s]
        if req.degraded:
            self.stats["degraded_served"] += 1
        self.cache = self._merge_rows(self.cache, self._cache0, [s])
        self.slot_pos[s] = 0
        self.slot_fed[s] = 0

    def _admit(self, degraded: bool = False):
        """Admit queued requests into free slots.

        Continuous mode: allocation only — prompt tokens are fed by the
        step loop, ``prefill_chunk`` at a time, interleaved with active
        decodes.  Bucket mode (the legacy baseline): the WHOLE prompt
        prefills here, one jitted call per token, before any active slot
        advances — exactly the admission stall the load harness measures.
        """
        while (adm := self.table.admit_one()) is not None:
            s, req = adm
            self._reset_slot(s, req, degraded)
            if self.batching == "continuous":
                continue
            # -- legacy blocking prefill (bucket-barrier baseline) ------
            others = [r for i, r in enumerate(self.slot_req)
                      if r is not None and i != s]
            # per-slot prefill: the shape-stable step runs the whole
            # batch, but ONLY row s's cache writes are kept — already
            # active slots would otherwise have their rows clobbered
            # at the new request's (wrong) positions.  Batch rows are
            # independent in decode_step, so garbage other rows pick
            # up MID-loop is never read by row s: one merge after the
            # loop is bit-identical and len(prompt)x cheaper; with no
            # other slot active the merge is skipped entirely.
            cache = self.cache
            step_fn = self._slot_step(s)
            try:
                for t, tok in enumerate(req.prompt):
                    toks = self._tok.at[s, 0].set(tok)
                    self.ncalls += 1
                    logits, cache = step_fn(
                        cache, toks, jnp.asarray(t, jnp.int32))
            except Exception as e:               # noqa: BLE001 — a
                self._fail_slots([s], e)         # raising prefill must
                continue                         # not wedge the slot
            self.cache = (self._merge_rows(self.cache, cache, [s])
                          if others else cache)
            self.slot_pos[s] = self.slot_fed[s] = len(req.prompt)
            req._next = int(jnp.argmax(logits[s, -1]))

    def _feed_round(self, fed: List[int]) -> None:
        """Advance every slot in ``fed`` one token — its next PROMPT
        token while prefilling, its last sampled token while decoding.

        decode_step takes a scalar position, but staggered admissions
        leave slots at DIFFERENT positions — and mixed admission states
        leave slots on DIFFERENT plans.  Step each (plan, position)
        group separately, keeping only that group's rows — one jitted
        call per distinct group (usually 1; bounded by #slots).  Rows
        are independent, so a prefill token and a decode token sharing
        one grouped call are each bit-identical to solo serving.
        """
        live = self.table.active()
        toks = self._tok
        pos_of: Dict[int, int] = {}
        for s in fed:
            req = self.slot_req[s]
            if self.slot_fed[s] < len(req.prompt):
                tok, pos = req.prompt[self.slot_fed[s]], self.slot_fed[s]
            else:
                tok = req._next if not req.out else req.out[-1]
                pos = self.slot_pos[s]
            toks = toks.at[s, 0].set(tok)
            pos_of[s] = pos
        by_grp: Dict[Tuple[bool, int], List[int]] = {}
        for s in fed:
            by_grp.setdefault((self.slot_deg[s], pos_of[s]), []).append(s)
        logits_of: Dict[int, jax.Array] = {}
        for (deg, pos), group in sorted(by_grp.items()):
            step_fn = self._step_fb if deg else self._step
            try:
                self.ncalls += 1
                logits, stepped = step_fn(self.cache, toks,
                                          jnp.asarray(pos, jnp.int32))
                if self._float_retry and not bool(jnp.all(jnp.isfinite(
                        logits[jnp.asarray(group)]))):
                    # one retry on the float reference of the same
                    # weights: a blown-up BFP step (faulty container,
                    # exponent SEU) degrades to float numerics instead
                    # of feeding NaN logits into sampling
                    self.stats["float_retries"] += 1
                    self.ncalls += 1
                    logits, stepped = self._float_step_fn()(
                        self.cache, toks, jnp.asarray(pos, jnp.int32))
            except Exception as e:               # noqa: BLE001 — slots
                self._fail_slots(group, e)       # must never leak
                continue
            # when ONE group covers every live slot (steady state),
            # inactive rows are rewritten before any read, so the masked
            # merge copy would protect nothing — skip it.
            self.cache = (stepped
                          if len(by_grp) == 1 and len(group) == len(live)
                          else self._merge_rows(self.cache, stepped,
                                                group))
            for s in group:
                logits_of[s] = logits
        for s in fed:
            if s not in logits_of:
                continue              # group failed; slot already freed
            req = self.slot_req[s]
            nxt = int(jnp.argmax(logits_of[s][s, -1]))
            if self.slot_fed[s] < len(req.prompt):
                self.slot_fed[s] += 1
                self.slot_pos[s] = self.slot_fed[s]
                if self.slot_fed[s] == len(req.prompt):
                    req._next = nxt
            else:
                req.out.append(nxt)
                self.slot_pos[s] += 1
                if len(req.out) >= req.max_new:
                    req.done = True
                    self.stats["completed"] += 1
                    self.table.free(s)

    def step(self) -> int:
        """One engine iteration; returns the number of requests still
        queued or in flight AFTER the step (0 == drained) — the unified
        drive-loop contract both serve engines share (DESIGN.md §9):
        ``while eng.step(): ...`` serves to completion.

        Order per step: the overload controller observes the
        pre-admission queue depth, expiry runs BEFORE admission (a dead
        queued request is failed without ever being admitted, let alone
        prefilled), admissions made while DEGRADED decode on the
        pre-bound lower-L fallback plan for their whole lifetime, then
        every active slot advances — decoding slots one token,
        prefilling slots up to ``prefill_chunk`` prompt tokens (plus
        their first decode when the prompt completes within the chunk).
        """
        degraded = False
        if self.controller is not None:
            state = self.controller.observe(len(self.queue))
            degraded = state == DegradeController.DEGRADED
        self._expire()
        self._admit(degraded)
        active = self.table.active()
        if not active:
            return self.table.pending()
        # per-slot feed budget this step: decoders advance 1; prefilling
        # slots advance min(remaining, chunk) prompt tokens, +1 decode
        # when that finishes the prompt (matching the legacy per-step
        # visible behaviour for prompts shorter than the chunk)
        chunk = self.prefill_chunk
        budget: Dict[int, int] = {}
        for s in active:
            req = self.slot_req[s]
            rem = len(req.prompt) - self.slot_fed[s]
            if rem > 0:
                n = rem if chunk is None else min(rem, chunk)
                budget[s] = n + (1 if n == rem else 0)
            else:
                budget[s] = 1
        while True:
            fed = [s for s in self.table.active() if budget.get(s, 0) > 0]
            if not fed:
                break
            self._feed_round(fed)
            for s in fed:
                budget[s] -= 1
        return self.table.pending()

    def run(self) -> List[Request]:
        # include requests a prior step() already admitted into slots —
        # snapshotting only the queue would drop them from the result
        all_reqs = [r for r in self.slot_req if r is not None] + \
            list(self.queue)
        while self.table.pending():
            self.step()
        return all_reqs
