"""Reduction of a profiler trace to device metrics.

The trace is taken with the host tracer off: its events, by the million
from the runtime's threads, slowed the host loop that feeds the chip and
so read as device idle time.  ``load_xplane`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote and keeps, as plain lists, each device's
operations (the ``XLA Ops`` line: one event per HLO instruction run,
named by its HLO text) and program runs (``XLA Modules``).  The
benchmark's own host spans (``bench.*``) are timed by :class:`HostSpans`
on the host's clock and moved onto the trace's by :func:`align`, which
pairs each ``bench.step`` with the forward it ran.
``kernel_classes`` names each Pallas kernel's instruction by the kernel
function it came from, read from the compiled programs' metadata
(``op_name="jit(...)/jit(bfp_conv2d_pallas)/pallas_call"``: class
``conv``; ``...matmul...``: class ``matmul``).  ``summarize`` reduces the
two to busy time, idle gaps, per-kernel-class sums and program times.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import time
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = ["load_xplane", "kernel_classes", "summarize", "Summary",
           "merge", "instr_name", "HostSpans", "align", "PROFILE_OPTIONS"]

#: what ``jax.profiler.ProfileOptions`` is set to: no Python or host
#: tracer, device tracing and nothing else
PROFILE_OPTIONS = {"python_tracer_level": 0, "host_tracer_level": 0,
                   "enable_hlo_proto": False}

_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_CALL = re.compile(r"^\s*%?([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\""
                   r".*op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def instr_name(event_name: str) -> str:
    """``"%fusion.3 = f32[..] fusion(..)"`` -> ``"fusion.3"``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def load_xplane(tdir) -> Dict[str, Any]:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}}``, each
    event ``[name, start_ns, duration_ns]`` from the trace's start; op
    names are reduced to the HLO instruction's name."""
    import jax

    files = sorted(Path(tdir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    out: Dict[str, Any] = {"devices": {}}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev["ops"] = [[instr_name(e.name), e.start_ns,
                               e.duration_ns] for e in line.events]
            elif line.name == "XLA Modules":
                dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                  for e in line.events]
        out["devices"][plane.name] = dev
    return out


class HostSpans:
    """The benchmark's host spans, ``[name, start_ns, duration_ns]`` on
    ``time.perf_counter_ns``; ``span(name)`` is a context manager."""

    def __init__(self):
        self.spans: List[List[float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append([name, t, time.perf_counter_ns() - t])


def align(trace: Dict[str, Any], classes: Dict[str, Any],
          spans: List[List[float]]) -> Optional[float]:
    """Put ``spans`` (host clock) into ``trace["host"]`` on the trace's
    clock; returns the offset added, in ns, or None where no forward ran.

    The k-th ``bench.step`` ran the k-th run of a served program (one
    forward a step), and its logits reached the host after that run
    ended, so ``trace = host + offset`` with ``offset >= module_end -
    step_end`` for every pair; the largest of these bounds is taken (it
    lies short of the true offset by the quickest copy of logits back)."""
    programs = tuple(classes["programs"])
    ends = sorted(s + d for dev in trace["devices"].values()
                  for n, s, d in dev["modules"]
                  if n.split("(")[0] in programs)
    steps = sorted(s + d for n, s, d in spans if n == "bench.step")
    pairs = list(zip(ends, steps))
    if not pairs:
        trace["host"] = []
        return None
    offset = max(m - h for m, h in pairs)
    trace["host"] = [[n, s + offset, d] for n, s, d in spans]
    return offset


def kernel_classes(hlo_texts: Iterable[str]) -> Dict[str, Any]:
    """``{"kernels": {instruction: class}, "programs": [module names]}``
    from compiled programs' HLO text.  A kernel's class is the name of the
    jitted function around its ``pallas_call`` with ``conv`` or ``matmul``
    in it, reduced to that word."""
    kernels: Dict[str, str] = {}
    programs: List[str] = []
    for text in hlo_texts:
        for line in text.splitlines():
            m = _MODULE.match(line)
            if m:
                programs.append(m.group(1))
                continue
            m = _CALL.match(line)
            if not m:
                continue
            fns = re.findall(r"jit\(([\w.\-]+)\)", m.group(2))
            fn = fns[-1] if fns else m.group(2)
            cls = next((c for c in ("conv", "matmul") if c in fn), fn)
            kernels[m.group(1)] = cls
    return {"kernels": kernels, "programs": sorted(set(programs))}


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of ``(start, end)`` intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


class Summary:
    """Device time of one traced window, in seconds."""

    def __init__(self, window: Tuple[float, float], busy: List[float],
                 op_totals: Dict[str, float], class_totals: Dict[str, float],
                 program_runs: List[float], gaps: Dict[str, List[float]]):
        self.window_s = (window[1] - window[0]) * 1e-9
        #: busy seconds, averaged over the devices that ran an operation
        self.devices = len(busy)
        self.busy_s = sum(busy) / len(busy) * 1e-9 if busy else 0.0
        self.op_totals = op_totals
        self.class_totals = class_totals
        #: device seconds of each run of a served program (the forward)
        self.program_runs = program_runs
        #: idle seconds between operations: ``"in-program"`` inside a
        #: program run, else by the benchmark's host span open at the
        #: time; {name: [total seconds, gaps, longest]}
        self.gaps = gaps

    @property
    def idle_share(self):
        """1 - busy / window; None where no device ran an operation."""
        if not self.devices or not self.window_s:
            return None
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"{k}: {int(n)} gaps, longest {lo} s", t]
                              for k, (t, n, lo) in gaps]}


def _span_at(spans, starts, s, e) -> str:
    """The benchmark span that covers most of ``[s, e)``; ``spans`` are
    sequential (sorted by start, none inside another), ``starts`` their
    start times."""
    best, best_cover = "none", 0.0
    i = bisect.bisect_left(starts, e) - 1
    while i >= 0 and spans[i][2] > s:
        name, hs, he = spans[i]
        cover = min(e, he) - max(s, hs)
        if cover > best_cover:
            best, best_cover = name, cover
        i -= 1
    return best


def summarize(trace: Dict[str, Any], classes: Dict[str, Any]) -> Summary:
    """Reduce a :func:`load_xplane` trace over the ``bench.window`` host
    span (over all device events where that span is missing)."""
    host = [(n, s, s + d) for n, s, d in trace.get("host", [])]
    win = [(s, e) for n, s, e in host if n == "bench.window"]
    devices = [d for d in trace["devices"].values() if d["ops"]]
    ev = [(s, s + d) for dev in devices for _, s, d in dev["ops"]]
    if win:
        lo, hi = win[0]
    elif ev:
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    else:
        lo = hi = 0
    inner = sorted((h for h in host if h[0] != "bench.window"),
                   key=lambda h: h[1])
    starts = [h[1] for h in inner]
    kernels = classes["kernels"]
    programs = tuple(classes["programs"])
    busy: List[float] = []
    op_totals: Dict[str, float] = {}
    class_totals: Dict[str, float] = {}
    runs: List[float] = []
    gaps: Dict[str, List[float]] = {}
    for dev in devices:
        ops = [(n, s, d) for n, s, d in dev["ops"] if lo <= s < hi]
        merged = _clip(merge((s, s + d) for _, s, d in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for n, _, d in ops:
            op_totals[n] = op_totals.get(n, 0.0) + d * 1e-9
            if n in kernels:
                c = kernels[n]
                class_totals[c] = class_totals.get(c, 0.0) + d * 1e-9
        runs += [d * 1e-9 for n, s, d in dev["modules"]
                 if lo <= s < hi and n.split("(")[0] in programs]
        progs = merge((s, s + d) for _, s, d in dev["modules"])
        pstarts = [s for s, _ in progs]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                i = bisect.bisect_right(pstarts, (s + e) / 2) - 1
                inside = i >= 0 and progs[i][1] >= (s + e) / 2
                name = ("in-program" if inside
                        else _span_at(inner, starts, s, e))
                g = gaps.setdefault(name, [0.0, 0, 0.0])
                g[0] += (e - s) * 1e-9
                g[1] += 1
                g[2] = max(g[2], (e - s) * 1e-9)
    return Summary((lo, hi), busy, op_totals, class_totals, runs, gaps)
