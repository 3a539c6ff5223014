"""Production training launcher: --arch <id> at full or scaled size.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --scale smoke --steps 100 --ckpt-dir /tmp/ckpt [--bfp] [--compress-grads]

--scale full uses the exact public config (needs a pod: params won't fit
one CPU host); --scale smoke / 100m build reduced same-family configs.
On a real TPU fleet this driver runs under jax.distributed with the mesh
from repro.launch.mesh and the shardings from repro.dist.specs — the
single-host path here exercises the identical step/loop/checkpoint code.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.core.policy import PAPER_DEFAULT
from repro.data.pipeline import LMBatchSpec
from repro.dist.compress import make_compressor
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import optimizers as opt
from repro.train.loop import LoopConfig, run_training
from repro.train.step import init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--scale", default="smoke", choices=["smoke", "100m",
                                                         "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd",
                                                             "const"])
    ap.add_argument("--bfp", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()

    base = ARCHS[args.arch]
    if args.scale == "full":
        cfg = base
    elif args.scale == "100m":
        cfg = reduced(base, n_layers=8, d_model=512, d_ff=2048, vocab=8192)
    else:
        cfg = reduced(base)

    state = init_state(cfg, jax.random.PRNGKey(0))
    sched = {"cosine": opt.cosine_schedule(args.lr, 20, args.steps),
             "wsd": opt.wsd_schedule(args.lr, 20, int(args.steps * 0.6),
                                     int(args.steps * 0.3)),
             "const": opt.constant_schedule(args.lr)}[args.schedule]

    grad_transform = None
    if args.compress_grads:
        init_fn, transform = make_compressor(bits=8)
        residual = [init_fn(state.params)]

        def grad_transform(grads):
            q, residual[0] = transform(grads, residual[0])
            return q

    step = make_train_step(cfg, sched,
                           policy=PAPER_DEFAULT if args.bfp else None,
                           grad_transform=grad_transform)
    if grad_transform is None:
        step = jax.jit(step)
    spec = LMBatchSpec(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch)
    out = run_training(state, step, spec,
                       LoopConfig(total_steps=args.steps,
                                  ckpt_dir=args.ckpt_dir,
                                  ckpt_every=args.ckpt_every),
                       log_fn=lambda s, m: print(
                           f"step {s} loss {m['loss']:.4f}", flush=True))
    h = out["history"]
    print(f"done: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f}; "
          f"median step {out['median_step_s'] * 1e3:.0f} ms")


if __name__ == "__main__":
    main()
