"""The generator: seeded images, arrival processes found by name, and the
drive loop's bookkeeping against a fake server on a fake clock."""
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import drive as D

BACKLOG = {"arrivals": "backlog", "queue_factor": 2, "slots": 4,
           "buckets": [4], "image_pool": 6, "image_rects": 3}
#: an open loop for the drive loop's tests: 100 requests due evenly over
#: two seconds
OPEN = {"arrivals": "stub", "slots": 4, "buckets": [1, 2, 4],
        "image_pool": 6}


class EvenArrivals:
    """Due at ``t0 + k * 0.02`` for k < 100."""

    def __init__(self):
        self.times = [k * 0.02 for k in range(100)]
        self.next = 0

    def due(self, now, t0, close, queued):
        out = []
        while self.next < len(self.times) and \
                t0 + self.times[self.next] <= now:
            out.append(t0 + self.times[self.next])
            self.next += 1
        return out

    def next_due(self, t0):
        return (t0 + self.times[self.next] if self.next < len(self.times)
                else None)


@pytest.fixture
def stub_arrivals(monkeypatch):
    real = D.arrivals_of
    monkeypatch.setattr(D, "arrivals_of", lambda mix, s, rng: (
        EvenArrivals() if mix["arrivals"] == "stub" else real(mix, s, rng)))


def test_image_pool_seeded_and_distinct():
    big = 2 ** 33 + 5
    a = D.image_pool(BACKLOG, (16, 16, 3), np.random.default_rng(big))
    b = D.image_pool(BACKLOG, (16, 16, 3), np.random.default_rng(big))
    c = D.image_pool(BACKLOG, (16, 16, 3), np.random.default_rng(big + 1))
    assert a.shape == (6, 16, 16, 3) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({a[i].tobytes() for i in range(6)}) == 6
    # standardized, then shifted by a colour of each image's own
    assert np.all(np.abs(a.std(axis=(1, 2, 3)) - 1) < 0.5)


def test_arrivals_found_by_name():
    arr = D.arrivals_of(BACKLOG, 1.0, np.random.default_rng(0))
    assert arr.due(5.0, 5.0, 6.0, 3) == [5.0] * 5
    assert arr.due(6.0, 5.0, 6.0, 0) == []
    assert arr.next_due(5.0) is None
    with pytest.raises(FileNotFoundError):
        D.arrivals_of({**BACKLOG, "arrivals": "no_such_process"}, 1.0,
                      np.random.default_rng(0))


class _Req:
    def __init__(self, rid, image):
        self.rid, self.image = rid, image
        self.done, self.error, self.logits = False, None, None


class FakeServer:
    """FIFO server: each step serves up to ``slots`` queued requests and
    advances the fake clock by ``step_s``."""

    def __init__(self, clock, slots, step_s):
        self.clock, self.slots, self.step_s = clock, slots, step_s
        self.queue = []

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        batch, self.queue = self.queue[:self.slots], self.queue[self.slots:]
        self.clock.t += self.step_s
        for r in batch:
            r.done, r.logits = True, np.zeros(3)
        return len(self.queue)


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def _drive(mix, seconds, step_s):
    clock = Clock()
    srv = FakeServer(clock, mix["slots"], step_s)
    images = np.zeros((mix["image_pool"], 2, 2, 1), np.float32)
    rec = D.drive(srv, _Req, images, mix, mix["buckets"], seconds,
                  np.random.default_rng(7), clock=clock, sleep=clock.sleep)
    return rec, srv


def test_backlog_keeps_every_forward_full():
    rec, srv = _drive(BACKLOG, 1.0, 0.125)
    assert len([s for s in rec.steps if s.start < rec.close]) == 8
    assert all(s.live == 4 and s.bucket == 4 for s in rec.steps)
    assert rec.end == pytest.approx(rec.t0 + 1.0)
    assert all(r.ok for r in rec.requests) and not srv.queue
    assert [r.step for r in rec.requests] == sorted(r.step
                                                    for r in rec.requests)


def test_open_loop_times_from_due_and_drains(stub_arrivals):
    rec, _ = _drive(OPEN, 2.0, 0.01)
    due = rec.in_window()
    assert len(due) == len(rec.requests) == 100
    assert all(r.ok and r.done >= r.due for r in due)
    assert all(r.submitted >= r.due for r in due)
    for r in due:
        s = rec.steps[r.step]
        assert s.start >= r.submitted and s.end == r.done
        assert s.bucket >= s.live
    assert sum(s.live for s in rec.steps) == 100


def test_open_loop_overload_stops_after_drain_limit(stub_arrivals):
    rec, _ = _drive(OPEN, 2.0, 4.0)         # 1 per second served of 50
    assert rec.steps[-1].start <= rec.close + D.DRAIN_S
    assert not all(r.ok for r in rec.in_window())
