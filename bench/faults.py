"""Faults planted under the timed path, to show that ``correct`` fails them.

Each fault corrupts the logits of every forward the server runs, where
they are produced: ``Plan.jit_forward`` is wrapped so its output passes
through the fault on the host.  ``tests/bench`` reads each one on the CPU;
``control.py --fault`` reads them at a cell's own size on the chip.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict

import numpy as np

__all__ = ["FAULTS", "planted"]


def half_left_out(out: np.ndarray) -> np.ndarray:
    """The second half of each batch never computed (zeros)."""
    out[len(out) // 2:] = 0.0
    return out


def half_duplicated(out: np.ndarray) -> np.ndarray:
    """The second half of each batch answered with the first half's rows."""
    h = len(out) // 2
    out[len(out) - h:] = out[:h]
    return out


def rows_rolled(out: np.ndarray) -> np.ndarray:
    """Each request answered with the next row's logits (a batch routed
    one slot off)."""
    return np.roll(out, 1, axis=0)


def answer_altered(out: np.ndarray) -> np.ndarray:
    """Each answer's classes shifted by one."""
    return np.roll(out, 1, axis=1)


FAULTS: Dict[str, Callable[[np.ndarray], np.ndarray]] = {
    f.__name__: f for f in (half_left_out, half_duplicated, rows_rolled,
                            answer_altered)}


@contextlib.contextmanager
def planted(name: str):
    """Within the block, every forward a newly built server runs returns
    ``FAULTS[name](logits)``."""
    from repro.engine.plan import Plan

    corrupt = FAULTS[name]
    orig = Plan.jit_forward

    def jit_forward(self, apply_fn, *args, **kw):
        fwd = orig(self, apply_fn, *args, **kw)
        return lambda x: corrupt(np.array(fwd(x)))

    Plan.jit_forward = jit_forward
    try:
        yield
    finally:
        Plan.jit_forward = orig
