"""Serving launcher: --arch <id>, continuous-batching engine, optional
BFP-8 datapath + prequantized weights (the paper's deployment).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
      --requests 8 --max-new 16 --bfp --bfp-weights
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.core.policy import BFPPolicy, PAPER_DEFAULT
from repro.core.prequant import quantize_param_tree
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm.model import init_params
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--bfp", action="store_true",
                    help="BFP-8 activation x weight datapath per GEMM")
    ap.add_argument("--bfp-weights", action="store_true",
                    help="store weights as int8 mantissa + exponent sidecar")
    ap.add_argument("--batching", default="continuous",
                    choices=["continuous", "bucket"],
                    help="iteration-level batching (chunked prefill in "
                         "the step loop) vs the legacy blocking-prefill "
                         "bucket baseline")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens a prefilling slot consumes per "
                         "step in continuous mode (0 = whole prompt)")
    args = ap.parse_args()
    enable_compile_cache()

    base = ARCHS[args.arch]
    cfg = base if args.scale == "full" else reduced(
        base, n_layers=4, d_model=128, d_ff=256, vocab=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if args.bfp_weights:
        params = quantize_param_tree(params, BFPPolicy(block_k=32))
    policy = PAPER_DEFAULT.with_(straight_through=False) if args.bfp else None

    eng = ServeEngine(params, cfg, slots=args.slots, max_len=args.max_len,
                      policy=policy, batching=args.batching,
                      prefill_chunk=args.prefill_chunk or None)
    for i in range(args.requests):
        eng.submit(Request(rid=i, prompt=[1 + i, 7, 3], max_new=args.max_new))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    for r in done[:4]:
        print(f"req {r.rid}: {r.out}")
    print(f"{toks} tokens in {dt:.1f}s ({toks / dt:.1f} tok/s) "
          f"bfp={args.bfp} bfp_weights={args.bfp_weights}")


if __name__ == "__main__":
    main()
