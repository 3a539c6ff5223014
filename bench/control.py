"""Readings that the limits of ``correct`` are set from.

  python3 bench/control.py --workload vgg16.batch --seconds 3 \\
      --seeds 11 12 13 --precision config control \\
      --fault rows_rolled half_duplicated

For each seed, in one process: set up the cell as ``run.py`` does, serve
a short window of the cell's own traffic, and compare the sampled served
logits with the float32 reference.  ``--precision config`` runs the
precision the configuration states; ``control`` its
``control_precision``, the program's own lower-precision path (BFP with
4-bit mantissas for BFP-8), which has to come out as not correct.  Each
``--fault`` (``bench/faults.py``) is read at the configuration's
precision with that fault planted under the timed path, and has to come
out as not correct too.  One JSON line per reading; needs the cell's
chips, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import run as R
from bench import faults as F
from bench import spec as S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", nargs="*", default=["config", "control"],
                    choices=("config", "control"))
    ap.add_argument("--fault", nargs="*", default=[], choices=sorted(F.FAULTS))
    args = ap.parse_args(argv)
    bm = S.load_benchmark()
    cell = S.find_cell(bm, args.workload)
    cfg = S.load_json("configs", cell["config"])
    mix = S.load_json("traffic", cell["traffic"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        R.log(f"control: needs {cell['chips']} TPU chip(s)")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()
    readings = [(p, None) for p in args.precision] + \
        [("config", f) for f in args.fault]
    for which, fault in readings:
        prec = cfg["control_precision"] if which == "control" else None
        for seed in args.seeds:
            t = time.perf_counter()
            try:
                with F.planted(fault) if fault else contextlib.nullcontext():
                    run = R.run_cell(cell, cfg, mix, seed=seed,
                                     seconds=args.seconds, trace=False,
                                     devices=devices, t_start=t,
                                     precision=prec)
                out = {k: R._num(v["value"]) for k, v in run.compared.items()}
                out["correct"] = all(v["ok"] for v in run.compared.values())
            except Exception as e:          # noqa: BLE001 — a reading
                out = {"error": repr(e)[:500], "correct": False}
            print(json.dumps({"workload": args.workload, "precision": which,
                              "fault": fault, "seed": seed, **out}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
