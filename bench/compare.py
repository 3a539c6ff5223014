"""The comparison that decides ``correct``.

The served logits of a seeded sample of the window's requests are held
against the plain float32 reference of the configuration, run on the
same weights and images at ``"highest"`` matmul precision.  Compared,
each beside its limit:

* ``rel_err_max``: the widest relative error over the sample,
  ``|served - reference| / |reference|`` per request (L2 over the
  classes), the square root of the per-image noise-to-signal ratio the
  paper analyses; its limit is the configuration's ``rel_err_max``;
* ``misrouted``: requests whose served logits lie nearer another sampled
  image's reference than their own, so an answer served to the wrong
  request fails even where it lies within ``rel_err_max``; limit 0;
* ``failed``: requests that failed or never finished, and
  ``float_retries``: forwards the server had to retry in float; limit 0.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

__all__ = ["reference_logits", "rel_errors", "misrouted", "separation",
           "judge"]


def reference_logits(ref, params, cfg: Dict[str, Any], images: np.ndarray,
                     block: int) -> np.ndarray:
    """The reference's logits of ``images``, ``block`` images at a time
    (the last block padded with copies, so one program serves all)."""
    import jax

    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: ref.forward(p, x, cfg))
        out = []
        for i in range(0, len(images), block):
            x = images[i:i + block]
            n = len(x)
            if n < block:
                x = np.concatenate([x, np.repeat(x[:1], block - n, 0)])
            out.append(np.asarray(fwd(params, x))[:n])
    if not out:
        return np.zeros((0, cfg["num_classes"]), np.float32)
    return np.concatenate(out).astype(np.float32)


def rel_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row ``|got - want| / |want|``; a non-finite row reads inf."""
    got = got.astype(np.float64)
    want = want.astype(np.float64)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return np.where(np.all(np.isfinite(got), axis=1), err, np.inf)


def _distinct(want: np.ndarray, images: np.ndarray):
    """The sampled images once each, and their reference rows."""
    uniq, first = np.unique(images, return_index=True)
    return uniq, want[first].astype(np.float64)


def misrouted(got: np.ndarray, want: np.ndarray, images: np.ndarray) -> int:
    """Rows of ``got`` whose nearest reference, among the distinct images
    of the sample, is not their own image's (a non-finite row counts)."""
    uniq, refs = _distinct(want, images)
    if len(uniq) < 2:
        return 0
    bad = ~np.all(np.isfinite(got), axis=1)
    d = np.linalg.norm(got.astype(np.float64)[:, None] - refs[None], axis=2)
    nearest = uniq[np.argmin(np.where(np.isfinite(d), d, np.inf), axis=1)]
    return int(np.sum((nearest != images) | bad))


def separation(want: np.ndarray, images: np.ndarray) -> Dict[str, float]:
    """How far apart distinct images' references lie: over all ordered
    pairs, ``|ref_a - ref_b| / |ref_b|``, which is what an answer for
    image ``a`` served to a request for ``b`` reads as its relative
    error; the least and the median."""
    _, refs = _distinct(want, images)
    if len(refs) < 2:
        return {"min": float("nan"), "median": float("nan")}
    d = np.linalg.norm(refs[:, None] - refs[None], axis=2)
    rel = d / np.linalg.norm(refs, axis=1)[None]
    rel = rel[~np.eye(len(refs), dtype=bool)]
    return {"min": float(rel.min()), "median": float(np.median(rel))}


def judge(got: np.ndarray, want: np.ndarray, images: np.ndarray,
          limits: Dict[str, Any], *, failed: int, float_retries: int
          ) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "limit", "ok"}}`` for every compared number;
    ``images`` names each row's image.  An empty sample is not correct."""
    err = rel_errors(got, want)
    worst = float(err.max()) if err.size else float("inf")
    out = {
        "rel_err_max": {"value": worst, "limit": limits["rel_err_max"],
                        "n": int(err.size)},
        "misrouted": {"value": misrouted(got, want, images), "limit": 0},
        "failed": {"value": failed, "limit": 0},
        "float_retries": {"value": float_retries, "limit": 0},
    }
    for v in out.values():
        v["ok"] = bool(v["value"] <= v["limit"])
    return out
