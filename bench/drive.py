"""The one traffic generator, and the loop that drives the server with it.

A mix is a JSON file of parameters (``traffic/<mix>.json``):

* ``arrivals``: the name of the arrival process, a module
  ``traffic/<arrivals>.py`` found by that name (see :class:`Arrivals`);
  its own parameters sit beside it in the mix;
* ``slots`` and ``buckets``: the server's slot table and batch buckets
  (``null`` for the server's default powers of two);
* ``image_pool``: how many distinct images the requests cycle through;
  ``image_rects``: rectangles in each image (see :func:`image_pool`).

Requests carry host (NumPy) images, so the host-to-device copy is part
of serving.  Every request is timed from the moment it was due.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Protocol

import numpy as np

__all__ = ["ReqRec", "StepRec", "Record", "Arrivals", "arrivals_of",
           "image_pool", "drive", "no_span", "DRAIN_S"]

#: how long after the window closes the loop keeps serving requests that
#: were due inside it
DRAIN_S = 60.0


@dataclasses.dataclass
class ReqRec:
    rid: int
    image: int                  #: index into the image pool
    due: float                  #: clock time the request was due
    submitted: float = float("nan")
    step: int = -1              #: index of the step that served it
    done: float = float("nan")  #: clock time its logits were on the host
    ok: bool = False
    req: Any = None             #: the server's request object


@dataclasses.dataclass
class StepRec:
    start: float
    end: float
    live: int                   #: requests the step finished
    bucket: int                 #: rows of the forward it ran


@dataclasses.dataclass
class Record:
    """What one measured window did, on the host's clock."""

    t0: float                   #: window open
    close: float                #: ``t0 + seconds``
    end: float = 0.0            #: end of the last step begun before close
    requests: List[ReqRec] = dataclasses.field(default_factory=list)
    steps: List[StepRec] = dataclasses.field(default_factory=list)

    def in_window(self) -> List[ReqRec]:
        """Requests due before the window closed."""
        return [r for r in self.requests if r.due < self.close]


class Arrivals(Protocol):
    """What ``traffic/<arrivals>.py`` provides, as ``make(mix, seconds,
    rng) -> Arrivals``."""

    def due(self, now: float, t0: float, close: float,
            queued: int) -> List[float]:
        """Due times of the requests to submit at ``now``, with ``queued``
        requests still unserved."""

    def next_due(self, t0: float) -> Optional[float]:
        """When the next request falls due, or None when no more will
        (asked only while nothing is queued)."""


def arrivals_of(mix: Dict[str, Any], seconds: float,
                rng: np.random.Generator) -> Arrivals:
    """The mix's arrival process, from ``traffic/<arrivals>.py``."""
    from bench import spec as S
    return S.load_module("traffic", mix["arrivals"]).make(mix, seconds, rng)


def image_pool(mix: Dict[str, Any], shape, rng: np.random.Generator
               ) -> np.ndarray:
    """``image_pool`` distinct float32 scenes: a background colour under
    ``image_rects`` rectangles of random colour, place and size (an
    eighth to a half of each side), with faint noise; each image is
    standardized and then shifted by a colour of its own.  Unlike white
    noise, whose statistics every image shares, scenes keep distinct
    images' logits apart through a deep network."""
    n, (h, w, c) = mix["image_pool"], shape
    out = np.empty((n, h, w, c), np.float32)
    for i in range(n):
        img = np.broadcast_to(rng.standard_normal(c), (h, w, c)).copy()
        for _ in range(mix["image_rects"]):
            y, x = rng.integers(0, (h, w))
            dy, dx = rng.integers((max(1, h // 8), max(1, w // 8)),
                                  (max(2, h // 2), max(2, w // 2)))
            img[y:y + dy, x:x + dx] = 1.5 * rng.standard_normal(c)
        img += 0.1 * rng.standard_normal((h, w, c))
        out[i] = (img - img.mean()) / img.std() + 0.5 * rng.standard_normal(c)
    return out


def no_span(name: str):
    """A span that records nothing (the untraced run's)."""
    return contextlib.nullcontext()


def _bucket(buckets, n: int) -> int:
    return next((b for b in buckets if b >= n), buckets[-1])


def drive(server, make_request: Callable[[int, np.ndarray], Any],
          images: np.ndarray, mix: Dict[str, Any], buckets, seconds: float,
          rng: np.random.Generator, *,
          span: Callable[[str], Any] = no_span,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Record:
    """Serve ``mix`` for ``seconds`` through ``server`` (``submit`` /
    ``step``), then serve what was due in the window, for at most
    :data:`DRAIN_S` more.  ``span(name)`` wraps the generator's own calls
    (wait for an arrival, submit, step) so a trace can attribute them."""
    arrivals = arrivals_of(mix, seconds, rng)
    order = rng.permutation(len(images))
    t0 = clock()
    rec = Record(t0=t0, close=t0 + seconds)
    pending: deque = deque()

    def submit(due: float) -> None:
        rid = len(rec.requests)
        img = int(order[rid % len(order)])
        r = ReqRec(rid=rid, image=img, due=due)
        r.req = make_request(rid, images[img])
        server.submit(r.req)
        r.submitted = clock()
        rec.requests.append(r)
        pending.append(r)

    while True:
        now = clock()
        with span("bench.submit"):
            for due in arrivals.due(now, t0, rec.close, len(pending)):
                submit(due)
        if now >= rec.close + DRAIN_S or (now >= rec.close and not any(
                r.due < rec.close for r in pending)):
            break
        if not pending:
            nxt = arrivals.next_due(t0)
            if nxt is None:
                break
            with span("bench.wait_arrival"):
                sleep(max(0.0, nxt - clock()))
            continue
        start = clock()
        with span("bench.step"):
            server.step()
        end = clock()
        live = 0
        while pending and pending[0].req.done:
            r = pending.popleft()
            r.step, r.done = len(rec.steps), end
            r.ok = r.req.error is None and r.req.logits is not None
            live += 1
        rec.steps.append(StepRec(start, end, live, _bucket(buckets, live)))
        if start < rec.close:
            rec.end = end
    return rec
