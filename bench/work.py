"""Operations and bytes each conv and fc needs, counted from its shapes.

The count is what the BFP-8 algorithm needs, whatever implements it:
``2 * MACs`` int8 multiply-adds, and HBM bytes for the weights, the input
activations and the output activations, each as int8 mantissas plus one
float32 step per ``block``-long run along the channel (K) axis, read or
written once.  Padded tiles, K-padding and re-reads of a kernel are not
counted, so the count does not change when the kernel does.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable

from bench.spec import BENCH

__all__ = ["site_work", "model_work", "least_seconds", "load_peaks"]

STEP_BYTES = 4          # one float32 step per block


def _wire(elems_per_row: int, rows: int, block: int, bits: int) -> int:
    """Bytes of ``rows`` rows of ``elems_per_row`` values in BFP wire form."""
    mant = -(-bits // 8)
    return rows * (elems_per_row * mant
                   + -(-elems_per_row // block) * STEP_BYTES)


def site_work(site: Dict[str, Any], batch: int, *, block: int = 128,
              bits: int = 8) -> Dict[str, int]:
    """``{"macs", "ops", "bytes"}`` of one conv or fc site at ``batch``."""
    if site["kind"] == "conv":
        k = site["k"] ** 2 * site["cin"]
        rows_in = batch * site["h"] * site["w"]
        rows_out = batch * site["ho"] * site["wo"]
    else:
        k = site["cin"]
        rows_in = rows_out = batch
    n = site["cout"]
    macs = rows_out * k * n
    weights = n * (k * -(-bits // 8) + -(-k // block) * STEP_BYTES)
    byts = (weights + _wire(site["cin"], rows_in, block, bits)
            + _wire(n, rows_out, block, bits))
    return {"macs": macs, "ops": 2 * macs, "bytes": byts}


def model_work(sites: Iterable[Dict[str, Any]], batch: int, *,
               kind: str = "", block: int = 128, bits: int = 8
               ) -> Dict[str, int]:
    """Sums of :func:`site_work` over the sites (of one ``kind`` when
    given: ``"conv"`` or ``"fc"``)."""
    tot = {"macs": 0, "ops": 0, "bytes": 0}
    for s in sites:
        if kind and s["kind"] != kind:
            continue
        for key, v in site_work(s, batch, block=block, bits=bits).items():
            tot[key] += v
    return tot


def least_seconds(work: Dict[str, int], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    the int8 peak and bytes over the HBM bandwidth."""
    return max(work["ops"] / peak["int8_ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The row of ``peaks.json`` for this device; an unknown kind is an
    error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table['devices'])})"
                       ) from None
