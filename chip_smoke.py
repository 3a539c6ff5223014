"""Smoke run of the BFP-8 CNN serving path on TPU.

Binds VGG-16 at full width (224x224x3 in, 1000 classes; random weights
from ``--seed``) under BFP-8 Scheme.TILED with block_k=128 on the Pallas
backend, weights prequantized, ``strict=True``, and serves a few
requests through ``CnnServeEngine`` (README "CNN serving").  It checks
that

* every conv/fc site is bound to the ``pallas`` backend, and the
  compiled forward holds one ``tpu_custom_call`` (a compiled Pallas
  kernel) per site;
* every request completes, with no failure and no float retry;
* the served logits equal, bit for bit, those of the kernels' oracle
  (``repro.kernels.ref``: the same TILED blocks, zero K-padding, exact
  int32 tile dots, f32 accumulation in kernel order) on the same weights.
  The emulated backend cannot be this reference: it refuses a block_k
  that does not divide K, and conv1_1, conv1_2 and conv2_1 have K = 27,
  576 and 576;

and prints NSR and top-1 agreement of the logits against the float32
reference (the paper's Table-4 quantities).

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # data-parallel serving on a (4, 1)
                                     # mesh against the same batch on one
                                     # chip, and nothing else

Exits non-zero, printing no result, when JAX finds too few TPU devices,
when the program is missing, or when a check fails.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: requests per one-chip run, and the slot table / only batch bucket
N_REQUESTS, SLOTS = 16, 8
#: four-chip batch: 8 images per chip
FOUR_CHIP_BATCH = 32
BLOCK_K = 128


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _policy():
    from repro.core.bfp import Scheme
    from repro.core.policy import BFPPolicy
    return BFPPolicy(scheme=Scheme.TILED, block_k=BLOCK_K, backend="pallas",
                     straight_through=False)


def _register_oracle() -> str:
    """Register the kernels' oracle (``repro.kernels.ref``) as an engine
    backend, so the reference runs the same model code as the server."""
    import jax.numpy as jnp
    from repro.engine import backends as BK
    from repro.kernels import ref

    def matmul(x2d, w, pol, key=None):
        pad = -x2d.shape[1] % pol.block_k     # zero K-padding, as the kernel
        return ref.bfp_matmul_ref(jnp.pad(x2d, ((0, 0), (0, pad))),
                                  jnp.pad(w, ((0, pad), (0, 0))),
                                  pol.l_i, pol.l_w, pol.block_k)

    def conv(x, w, pol, stride, padding, key=None):
        return ref.bfp_conv2d_ref(x, w, pol.l_i, pol.l_w, pol.block_k,
                                  stride, padding)

    BK.register_backend("kernel_oracle", matmul, conv=conv)
    return "kernel_oracle"


def _kernel_calls(hlo_text: str) -> list:
    """Lines of a compiled module that launch a compiled Pallas kernel."""
    return [ln for ln in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _bind(spec, seed: int):
    import jax
    from repro import engine

    params = spec.init(jax.random.PRNGKey(seed), reduced=False)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    plan = engine.bind(params, _policy(), tree="cnn", strict=True)
    jax.block_until_ready(plan.params)
    bind_s = time.perf_counter() - t0
    backends = {s.backend.name for s in plan.sites.values()}
    print(f"bind (set-up): {bind_s:.3f} s, {len(plan.sites)} sites, "
          f"backends {sorted(backends)}, prequantized "
          f"{sum(s.prequantized for s in plan.sites.values())}")
    _check(backends == {"pallas"}, f"sites not all pallas: {backends}")
    return params, plan


def _compile(fwd, x, n_sites: int, label: str) -> str:
    t0 = time.perf_counter()
    text = fwd.lower(x).compile().as_text()
    n = len(_kernel_calls(text))
    print(f"compile (set-up) {label}: {time.perf_counter() - t0:.3f} s, "
          f"tpu_custom_call {n} for {n_sites} conv/fc sites")
    _check(n == n_sites, f"{n} compiled kernels for {n_sites} sites")
    return text


def _serve(eng, images):
    import numpy as np
    from repro.serve.cnn import ImageRequest

    reqs = [eng.submit(ImageRequest(rid=i, image=img))
            for i, img in enumerate(images)]
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    st = eng.stats
    print(f"served: completed {st['completed']} failed {st['failed']} "
          f"float_retries {st['float_retries']} of {len(reqs)}; "
          f"{len(reqs) / dt:.3f} images/s (smoke figure, not a benchmark)")
    _check(st["failed"] == 0 and st["completed"] == len(reqs),
           f"requests failed: {st}")
    _check(st["float_retries"] == 0, f"float retries: {st}")
    _check(all(r.error is None for r in reqs), "a request carries an error")
    return np.stack([r.logits for r in reqs])


def _peak_memory(devices) -> None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    print(f"peak device memory: {peaks} bytes")


def one_chip(seed: int) -> None:
    """The one-chip run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import engine
    from repro.core import nsr
    from repro.models.cnn import MODELS
    from repro.serve.cnn import CnnServeEngine

    spec = MODELS["vgg16"]
    hw = spec.input_shape(reduced=False)
    params, plan = _bind(spec, seed)
    print(f"model: vgg16 input {hw}, "
          f"{params['fc8']['w'].shape[1]} classes")
    fwd = plan.jit_forward(spec.apply)
    zeros = jnp.zeros((SLOTS, *hw), jnp.float32)
    _compile(fwd, zeros, len(plan.sites), f"bucket {SLOTS}")
    t0 = time.perf_counter()
    jax.block_until_ready(fwd(zeros))
    print(f"first call (set-up, compile cache): "
          f"{time.perf_counter() - t0:.3f} s")

    eng = CnnServeEngine(None, spec.apply, plan, slots=SLOTS,
                         buckets=(SLOTS,), strict_backend=True)
    images = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (N_REQUESTS, *hw), jnp.float32)
    served = _serve(eng, list(images))
    _check(served.shape == (N_REQUESTS, params["fc8"]["w"].shape[1]),
           f"logits shape {served.shape}")
    _check(bool(np.all(np.isfinite(served))), "non-finite logits")

    oracle = engine.bind(params, _policy().with_(
        backend=_register_oracle()), tree="cnn", strict=True,
        prequantize=False).jit_forward(spec.apply)
    with jax.default_matmul_precision("highest"):
        flt = jax.jit(lambda p, x: spec.apply(p, x, None))
        ref = [(oracle(images[i:i + SLOTS]), flt(params, images[i:i + SLOTS]))
               for i in range(0, N_REQUESTS, SLOTS)]
    want = np.concatenate([np.asarray(o) for o, _ in ref])
    f32 = np.concatenate([np.asarray(f) for _, f in ref])
    diff = np.abs(served - want)
    print(f"vs kernel oracle: {int(np.sum(served != want))} of {want.size} "
          f"logits differ, max |diff| {float(diff.max()):.6g}")
    _check(np.array_equal(served, want), "served logits != kernel oracle")
    snr = float(nsr.snr_db(jnp.asarray(f32), jnp.asarray(served)))
    top1 = float(np.mean(np.argmax(served, 1) == np.argmax(f32, 1)))
    print(f"vs float32 reference: SNR {snr:.3f} dB, NSR "
          f"{float(nsr.nsr_from_snr_db(snr)):.6g}, top-1 agreement "
          f"{top1:.4f} over {N_REQUESTS} images")
    _check(np.isfinite(snr), "float32 reference comparison is not finite")
    _peak_memory(jax.devices()[:1])


def four_chips(seed: int) -> None:
    """The four-chip run and its one-chip comparison."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import DEFAULT_RULES
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import MODELS
    from repro.serve.cnn import CnnServeEngine

    spec = MODELS["vgg16"]
    hw = spec.input_shape(reduced=False)
    params, plan = _bind(spec, seed)
    mesh = make_mesh((4, 1), ("data", "model"))
    print(f"model: vgg16 input {hw}, {params['fc8']['w'].shape[1]} "
          f"classes; mesh {dict(mesh.shape)}, batch {FOUR_CHIP_BATCH}")
    fwd4 = plan.jit_forward(spec.apply, mesh=mesh, batch_axis="data")
    zeros = jax.device_put(jnp.zeros((FOUR_CHIP_BATCH, *hw), jnp.float32),
                           NamedSharding(mesh, P("data")))
    text = _compile(fwd4, zeros, len(plan.sites), "4-chip program")
    first = _kernel_calls(text)[0].strip()
    print(f"first kernel launch: {first[:160]}")
    per_chip = FOUR_CHIP_BATCH // 4
    _check(f"[{per_chip},{hw[0]},{hw[1]}," in first,
           f"first kernel does not run on a {per_chip}-image quarter")
    coll = [c for c in ("all-gather", "all-reduce", "all-to-all",
                        "collective-permute", "reduce-scatter")
            if c in text]
    print(f"collectives in the 4-chip program: {coll or 'none'}")
    _check(not coll, f"4-chip program holds collectives {coll}")

    eng = CnnServeEngine(None, spec.apply, plan, slots=FOUR_CHIP_BATCH,
                         buckets=(FOUR_CHIP_BATCH,), strict_backend=True,
                         mesh=mesh, rules=DEFAULT_RULES)
    images = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (FOUR_CHIP_BATCH, *hw), jnp.float32)
    served = _serve(eng, list(images))
    one = np.asarray(plan.jit_forward(spec.apply)(
        jax.device_put(images, jax.devices()[0])))
    print(f"4-chip vs 1-chip: {int(np.sum(served != one))} of {one.size} "
          f"logits differ")
    _check(np.array_equal(served, one), "4-chip logits != 1-chip logits")
    _peak_memory(jax.devices()[:4])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel path and its "
                         "1-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and images")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device_kind: {devices[0].device_kind}; compile cache "
          f"{enable_compile_cache()}")
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
