"""Offline backlog: until the window closes, the queue is topped up to
``queue_factor * slots`` requests, each due when it is submitted, so every
forward runs the full bucket and nothing but the server bounds the rate."""
from __future__ import annotations

from typing import Any, Dict, List, Optional


class Backlog:
    def __init__(self, mix: Dict[str, Any]):
        self.target = mix["queue_factor"] * mix["slots"]

    def due(self, now: float, t0: float, close: float,
            queued: int) -> List[float]:
        return [now] * (self.target - queued) if now < close else []

    def next_due(self, t0: float) -> Optional[float]:
        return None


def make(mix: Dict[str, Any], seconds: float, rng) -> Backlog:
    return Backlog(mix)
