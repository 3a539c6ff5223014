"""CNN serving launcher: batched BFP inference on a bound plan.

The paper-model counterpart of ``repro.launch.serve`` — admits image
requests into the slot-table engine, serves them with iteration-level
batching on the bind-once plan, optionally under a data-parallel mesh,
or as several MULTI-TENANT models in one process:

  PYTHONPATH=src python -m repro.launch.serve_cnn --model vgg16 \
      --requests 32 --slots 8 --bfp --prequant
  PYTHONPATH=src python -m repro.launch.serve_cnn --model resnet18 \
      --scale full --mesh 1x1 --bfp --strict-backend
  PYTHONPATH=src python -m repro.launch.serve_cnn \
      --tenants lenet,cifarnet --requests 12 --bfp

``--bfp`` is the paper's EQ4 policy, which the ``emulated`` (pure jnp)
backend executes: the Pallas kernels need Scheme.TILED, so this launcher
never runs them.  ``chip_smoke.py`` drives the kernel path.  The exit
code is 1 when any request failed.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.core.policy import PAPER_DEFAULT
from repro.dist.sharding import DEFAULT_RULES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.cnn import MODELS
from repro.serve.cnn import CnnServeEngine, ImageRequest


def _serve_tenants(args, policy):
    """Multi-tenant path: every listed model serves from one process."""
    from repro.serve.tenants import MultiTenantServer

    names = [m.strip() for m in args.tenants.split(",") if m.strip()]
    bad = [m for m in names if m not in MODELS]
    if bad:
        raise SystemExit(f"unknown tenant model(s) {bad}; "
                         f"available: {sorted(MODELS)}")
    srv = MultiTenantServer(slots=args.slots, batching=args.batching,
                            max_wait=args.max_wait,
                            strict_backend=args.strict_backend)
    for m in names:
        srv.add_tenant(m, m, params=MODELS[m].init(jax.random.PRNGKey(0)),
                       policy=policy, prequant=args.prequant)
    keys = jax.random.split(jax.random.PRNGKey(1), args.requests)
    reqs = []
    for i in range(args.requests):
        m = names[i % len(names)]
        shape = MODELS[m].input_shape()
        reqs.append((m, srv.submit(
            m, ImageRequest(rid=i, image=jax.random.normal(keys[i],
                                                           shape)))))
    t0 = time.perf_counter()
    srv.run()
    dt = max(time.perf_counter() - t0, 1e-9)
    for m, r in reqs[:4]:
        print(f"req {r.rid} [{m}]: label={r.label}")
    st = srv.stats()
    for m in names:
        print(f"tenant {m}: {st['tenants'][m]}")
    print(f"{st['total']['completed']} requests across {len(names)} "
          f"tenants in {dt:.2f}s ({st['total']['completed'] / dt:.1f} "
          f"req/s) batching={args.batching}")
    return _report_failures([r for _, r in reqs])


def _report_failures(reqs) -> int:
    """Exit code for a finished run: 1 (with the first error on stderr)
    when any request ended without logits, else 0."""
    failed = [r for r in reqs if r.error is not None or not r.done]
    if not failed:
        return 0
    print(f"{len(failed)} of {len(reqs)} requests failed; first: "
          f"{failed[0].error!r}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS),
                    help="single-tenant model (or use --tenants)")
    ap.add_argument("--tenants", metavar="M1,M2,...",
                    help="serve several models as tenants of one "
                         "process (round-robin traffic)")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--bfp", action="store_true",
                    help="BFP-8 activation x weight datapath per site")
    ap.add_argument("--prequant", action="store_true",
                    help="pre-quantize weights at bind (wire format)")
    ap.add_argument("--strict-backend", action="store_true",
                    help="refuse backend downgrades at admission")
    ap.add_argument("--mesh", metavar="DxM",
                    help="data x model mesh, e.g. 1x1 (device count must "
                         "match); shards the request batch axis")
    ap.add_argument("--batching", default="continuous",
                    choices=["continuous", "bucket"],
                    help="run partially-filled steps immediately vs the "
                         "bucket-barrier baseline")
    ap.add_argument("--max-wait", type=int, default=4,
                    help="bucket mode: deferred steps before a partial "
                         "batch runs anyway")
    args = ap.parse_args(argv)
    enable_compile_cache()

    policy_ = (PAPER_DEFAULT.with_(straight_through=False) if args.bfp
               else None)
    if args.tenants:
        return _serve_tenants(args, policy_)
    if not args.model:
        ap.error("pass --model (single tenant) or --tenants")

    spec = MODELS[args.model]
    reduced = args.scale == "smoke"
    params = spec.init(jax.random.PRNGKey(0), reduced=reduced)
    policy = policy_
    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    eng = CnnServeEngine(params, spec.apply, policy, slots=args.slots,
                         prequant=args.prequant,
                         strict_backend=args.strict_backend,
                         batching=args.batching, max_wait=args.max_wait,
                         mesh=mesh, rules=DEFAULT_RULES)
    print(f"bound plan: {eng.plan!r}")
    h, w, c = spec.input_shape(reduced=reduced)
    keys = jax.random.split(jax.random.PRNGKey(1), args.requests)
    reqs = [eng.submit(ImageRequest(
        rid=i, image=jax.random.normal(keys[i], (h, w, c))))
        for i in range(args.requests)]
    # compile EVERY bucket off the clock (a tail batch smaller than the
    # slot count selects a smaller bucket, whose first compile would
    # otherwise land inside the timed window), via a throwaway engine on
    # the same plan — Plan.jit_forward shares the traced callables
    warm = CnnServeEngine(None, spec.apply, eng.plan, slots=args.slots,
                          mesh=mesh, rules=DEFAULT_RULES)
    for b in warm.buckets:
        for _ in range(b):
            warm.submit(image=jax.numpy.zeros((h, w, c)))
        warm.run()
    t0 = time.perf_counter()
    eng.run()
    dt = max(time.perf_counter() - t0, 1e-9)
    served = [r for r in reqs if r.done and r.error is None]
    for r in served[:4]:
        print(f"req {r.rid}: label={r.label}")
    print(f"{len(served)} requests served in {dt:.2f}s "
          f"({len(served) / dt:.1f} req/s) model={args.model} "
          f"bfp={args.bfp} prequant={args.prequant} mesh={args.mesh}")
    return _report_failures(reqs)


if __name__ == "__main__":
    sys.exit(main())
