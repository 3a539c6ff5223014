"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload vgg16.batch --seed 7 --seconds 20 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json`` and its reference module) and a traffic
mix (``bench/traffic/<mix>.json``).  The run makes the weights on the
device and the images on the host from ``--seed``, binds the program's
BFP plan, warms every batch bucket of the mix, and serves the mix through
``CnnServeEngine`` for ``--seconds``.  It then compares a sample of the
served logits, drawn from the seed, with the plain float32 reference, and
prints each compared number beside its limit.  With ``--trace 1`` the
window runs under the JAX profiler (device tracing only, ``trace.py``)
and the per-layer metrics are read from the trace; with ``--trace 0``
the end-to-end metrics are printed.

Exits with 2, printing no result, unless JAX finds a TPU with as many
chips as the cell asks for; exits with 1 when the run cannot finish.
The last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from functools import partial
from pathlib import Path
from typing import Any, Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import spec as S  # noqa: E402

REF_BLOCK = 16          # images per block of the reference


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seeds(seed: int):
    """Independent streams from one ``--seed`` (any whole number)."""
    import numpy as np
    ss = np.random.SeedSequence(seed % 2 ** 64)
    w, i, t, s = ss.spawn(4)
    return (int(w.generate_state(1)[0]), np.random.default_rng(i),
            np.random.default_rng(t), np.random.default_rng(s))


def policy_of(precision: Dict[str, Any]):
    from repro.core.bfp import Scheme
    from repro.core.policy import BFPPolicy
    return BFPPolicy(l_w=precision["l_w"], l_i=precision["l_i"],
                     scheme=Scheme[precision["scheme"]],
                     block_k=precision["block_k"],
                     backend=precision["backend"], straight_through=False)


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _compile_counter():
    """Counts compilations from here on (none should happen in the
    window)."""
    import jax
    box = {"n": 0, "on": False}

    def listen(event: str, duration: float, **kw) -> None:
        if box["on"] and event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def set_up(cfg: Dict[str, Any], mix: Dict[str, Any], *, seed: int,
           t_start: float, precision: Optional[Dict[str, Any]] = None
           ) -> Run:
    """Weights from the seed, the bound plan, the server, the image pool,
    and every bucket of the mix warmed.  ``precision`` overrides the
    configuration's (the control's path)."""
    import jax
    import numpy as np
    from repro import engine
    from repro.models.cnn import MODELS
    from repro.serve.cnn import CnnServeEngine, ImageRequest

    from bench import drive

    ref = S.load_module("configs", cfg["reference"])
    prec = {**cfg["precision"], **(precision or {})}
    wseed, img_rng, traffic_rng, sample_rng = seeds(seed)
    parts: Dict[str, float] = {"start": time.perf_counter() - t_start}

    t = time.perf_counter()
    params = jax.jit(partial(ref.init, cfg=cfg))(jax.random.PRNGKey(wseed))
    jax.block_until_ready(params)
    parts["init"] = time.perf_counter() - t

    t = time.perf_counter()
    plan = engine.bind(ref.program_params(params, cfg), policy_of(prec),
                       tree="cnn", strict=prec["strict"],
                       prequantize=prec["prequantize"])
    jax.block_until_ready(plan.params)
    parts["bind"] = time.perf_counter() - t
    spec = MODELS[cfg["program_model"]]
    server = CnnServeEngine(None, spec.apply, plan, slots=mix["slots"],
                            buckets=mix["buckets"], strict_backend=True)

    t = time.perf_counter()
    shape = (cfg["input_hw"], cfg["input_hw"], cfg["in_ch"])
    images = drive.image_pool(mix, shape, img_rng)
    parts["images"] = time.perf_counter() - t

    t = time.perf_counter()
    for b in server.buckets:
        for _ in range(2):
            reqs = [server.submit(ImageRequest(rid=10 ** 9 + i,
                                               image=images[i % len(images)]))
                    for i in range(b)]
            while server.step():
                pass
            if not all(r.done and r.error is None for r in reqs):
                raise RuntimeError(f"warm-up of bucket {b} failed: "
                                   f"{[r.error for r in reqs if r.error]}")
    # the mix's own traffic for ``warm_s``, on a stream of its own, so the
    # host path (stack, copy in, copy out) is at its steady state when the
    # window opens
    warm = drive.drive(server, lambda rid, img: ImageRequest(
        rid=10 ** 9 + rid, image=img), images, mix, server.buckets,
        mix.get("warm_s", 0.0), np.random.default_rng(wseed))
    if not all(r.ok for r in warm.requests):
        raise RuntimeError("warm-up traffic failed")
    parts["warm"] = time.perf_counter() - t
    return Run(cfg=cfg, mix=mix, ref=ref, params=params, plan=plan,
               apply=spec.apply, server=server, buckets=server.buckets,
               images=images, shape=shape, traffic_rng=traffic_rng,
               sample_rng=sample_rng, parts=parts, precision=prec,
               warm_stats=dict(server.stats), make_request=lambda rid, img:
               ImageRequest(rid=rid, image=img))


def serve(run: Run, seconds: float, span=None):
    """One measured window of ``run.mix`` through ``run.server``;
    ``span(name)`` wraps the generator's calls."""
    from bench import drive
    return drive.drive(run.server, run.make_request, run.images, run.mix,
                       run.buckets, seconds, run.traffic_rng,
                       span=span or drive.no_span)


def run_cell(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any],
             *, seed: int, seconds: float, trace: bool, devices,
             t_start: float, peaks: Optional[Dict[str, float]] = None,
             precision: Optional[Dict[str, Any]] = None) -> Run:
    """Set up, serve the window, and compare; returns the :class:`Run`."""
    import jax
    import numpy as np

    from bench import compare
    from bench import trace as T

    run = set_up(cfg, mix, seed=seed, t_start=t_start, precision=precision)
    compiles = _compile_counter()
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    run.setup_s = time.perf_counter() - t_start
    log("set-up parts (s): " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in run.parts.items()))
    compiles["on"] = True
    spans = T.HostSpans()
    if trace:
        opts = jax.profiler.ProfileOptions()
        for k, v in T.PROFILE_OPTIONS.items():
            setattr(opts, k, v)
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with spans.span("bench.window"):
            rec = serve(run, seconds, spans.span if trace else None)
    finally:
        if trace:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace stop: {time.perf_counter() - t:.3f} s")
    compiles["on"] = False
    server = run.server
    stats = {k: v - run.warm_stats.get(k, 0) for k, v in server.stats.items()}
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices[:cell["chips"]]]
    summary = None
    if trace:
        t = time.perf_counter()
        events = T.load_xplane(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        t_load = time.perf_counter() - t
        fwd = run.plan.jit_forward(run.apply)
        hlo = [fwd.lower(np.zeros((b, *run.shape), np.float32)).compile()
               .as_text() for b in sorted({s.bucket for s in rec.steps})]
        del fwd
        t_hlo = time.perf_counter() - t - t_load
        classes = T.kernel_classes(hlo)
        offset = T.align(events, classes, spans.spans)
        forwards = sum(1 for d in events["devices"].values()
                       for n, _, _ in d["modules"]
                       if n.split("(")[0] in classes["programs"])
        log(f"trace: {forwards} forwards for {len(rec.steps)} steps; host "
            f"clock + {offset} ns = trace clock")
        summary = T.summarize(events, classes)
        log(f"trace read (s): load {t_load:.3f}, programs {t_hlo:.3f}, "
            f"reduce {time.perf_counter() - t - t_load - t_hlo:.3f}")

    # -- correctness: a seeded sample of what the window served --------
    due = rec.in_window()
    served = [r for r in due if r.ok]
    n = min(cfg["correct"]["sample"], len(served))
    pick = sorted(run.sample_rng.choice(len(served), size=n, replace=False))
    got = np.stack([np.asarray(served[i].req.logits, np.float32)
                    for i in pick]) if n else \
        np.zeros((0, cfg["num_classes"]), np.float32)
    idx = np.asarray([served[i].image for i in pick], np.int64)
    # free the program's state before the reference runs
    del server
    run.server = run.plan = None
    for r in rec.requests:
        r.req = None
    gc.collect()
    t = time.perf_counter()
    want = compare.reference_logits(run.ref, run.params, cfg,
                                    run.images[idx], REF_BLOCK)
    log(f"reference: {len(idx)} images in {time.perf_counter() - t:.3f} s")
    run.params = None
    sep = compare.separation(want, idx)
    log(f"separation of distinct images' references: min {sep['min']:.4f}"
        f" median {sep['median']:.4f} over {len(np.unique(idx))} images")
    run.compared = compare.judge(got, want, idx, cfg["correct"],
                                 failed=len(due) - len(served),
                                 float_retries=stats["float_retries"])
    run.__dict__.update(
        cell=cell, record=rec, seconds=seconds, sites=run.ref.sites(cfg),
        peaks=peaks, stats=stats, memory_peak_bytes=max(mem) if mem else 0,
        compiles_in_window=compiles["n"], trace=summary,
        attempted=len(due), failed=len(due) - len(served))
    return run


def _num(v: Any) -> Any:
    """A JSON number, or None where it is not finite."""
    return v if v == v and abs(v) != float("inf") else None


def result_line(run: Run, bm: Dict[str, Any], trace: bool, devices
                ) -> Dict[str, Any]:
    """The result object: the cell's metrics that its readers found, the
    device, and the compared numbers last."""
    metrics = {}
    for m in S.metrics_for(bm, run.cell["name"], trace):
        value = S.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": run.cell["chips"],
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c["ok"] for c in run.compared.values()),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["compared"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                       for k, v in run.compared.items()}
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = S.load_benchmark()
    cell = S.find_cell(bm, args.workload)
    cfg = S.load_json("configs", cell["config"])
    mix = S.load_json("traffic", cell["traffic"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 2
    from bench.work import load_peaks
    peaks = load_peaks(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    run = run_cell(cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   t_start=t_start, peaks=peaks)
    out = result_line(run, bm, bool(args.trace), devices)
    if run.compiles_in_window:
        log(f"WARNING: {run.compiles_in_window} compilations inside the "
            f"measured window")
    lat = [r.submitted - r.due for r in run.record.in_window()]
    if lat:
        log(f"generator late (ms): median {1e3 * sorted(lat)[len(lat) // 2]:.3f}"
            f" max {1e3 * max(lat):.3f} over {len(lat)} requests")
    print(json.dumps(out))
    for k, v in run.compared.items():
        log(f"compared {k}: {v['value']} limit {v['limit']} "
            f"{'ok' if v['ok'] else 'FAILED'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
