"""Host phase spans of the serving step, as counters and profiler events.

One :class:`SpanRecorder` per engine times the phases of its step
(``docs/tracing.md`` lists them).  Each ``span(name)``:

* adds its duration in ns to the engine's ``stats`` under
  ``host_ns.<name>`` — always on, the counters every reader of ``stats``
  already sees;
* opens a ``jax.profiler.TraceAnnotation(name)``, so a profile taken with
  host tracing on shows the phase beside the device's operations (the
  whole step runs under ``StepTraceAnnotation("serve.step")``);
* while ``keep`` is true, appends ``[name, start_ns, duration_ns, step]``
  to ``records``: ``time.perf_counter_ns``, the clock and the first three
  fields of ``bench/trace.py``'s ``HostSpans`` rows, so its ``align``
  offset moves them onto the device trace's clock.  Off by default, so a
  long-running server keeps nothing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List

import jax

__all__ = ["SpanRecorder", "PREFIX"]

#: ``stats`` key prefix of a phase's total host time
PREFIX = "host_ns."


class SpanRecorder:
    """Times spans into ``stats`` (and ``records`` while ``keep``)."""

    def __init__(self, stats: Dict[str, int]):
        self.stats = stats
        self.keep = False
        self.records: List[list] = []
        #: id of the current (or last) ``serve.step``
        self.step_num = -1

    @contextlib.contextmanager
    def step(self) -> Iterator[None]:
        """One ``serve.step``: a profiler step, and a record while
        ``keep``; its time is the sum of its phases plus its self time, so
        it adds no counter of its own."""
        self.step_num += 1
        t = time.perf_counter_ns()
        try:
            with jax.profiler.StepTraceAnnotation("serve.step",
                                                  step_num=self.step_num):
                yield
        finally:
            if self.keep:
                self.records.append(["serve.step", t,
                                     time.perf_counter_ns() - t,
                                     self.step_num])

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """One phase of the current step."""
        t = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            d = time.perf_counter_ns() - t
            key = PREFIX + name
            self.stats[key] = self.stats.get(key, 0) + d
            if self.keep:
                self.records.append([name, t, d, self.step_num])
