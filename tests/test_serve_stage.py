"""The double-buffered input of ``CnnServeEngine``: while a step's last
forward runs, the batch of the group the next step admits first is built
from the queue's head (``serve.stage``), and ``_batch_in`` takes it only
when the admitted group is exactly that one.

Every case compares the served logits bit for bit with an engine fed one
group at a time, whose queue is empty at every dispatch, so that nothing
is ever staged there."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import engine as EG
from repro.core import BFPPolicy
from repro.dist.sharding import DEFAULT_RULES
from repro.launch.mesh import make_mesh
from repro.models.cnn import small
from repro.serve.cnn import CnnServeEngine, ImageRequest
from repro.serve.degrade import DeadlineExceeded

SLOTS = 4


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def plan():
    return EG.bind(small.lenet_init(jax.random.PRNGKey(0)),
                   BFPPolicy(straight_through=False))


def _images(n, kind="numpy"):
    rng = np.random.default_rng(7)
    imgs = [rng.standard_normal((28, 28, 1)).astype(np.float32)
            for _ in range(n)]
    return imgs if kind == "numpy" else [jnp.asarray(im) for im in imgs]


def _engine(plan, **kw):
    return CnnServeEngine(None, small.lenet_apply, plan,
                          **{"slots": SLOTS, **kw})


def _assert_as_served_alone(plan, reqs, images, groups):
    """Each group's logits equal those of its images served alone, one
    group at a time: the queue is empty at every dispatch there, so
    nothing is staged."""
    eng = _engine(plan)
    for g in groups:
        alone = [eng.submit(image=images[i]) for i in g]
        eng.run()
        for i, r in zip(g, alone):
            np.testing.assert_array_equal(reqs[i].logits, r.logits)
    assert eng.stats["batches_staged"] == 0
    assert eng.stats["forwards"] == len(groups)


@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_backlog_stages_every_forward_after_the_first(plan, kind):
    """(a), (d): 2 x slots queued, topped up after every step as the
    benchmark's backlog does: every forward after the first takes the
    staged batch, from host and from device images alike."""
    images = _images(6 * SLOTS, kind)
    eng = _engine(plan)
    reqs = [eng.submit(image=im) for im in images[:2 * SLOTS]]
    for k in range(2, 6):
        eng.step()
        reqs += [eng.submit(image=im)
                 for im in images[k * SLOTS:(k + 1) * SLOTS]]
    eng.run()
    st = eng.stats
    assert st["forwards"] == 6 and st["completed"] == 6 * SLOTS
    assert st["batches_staged"] == st["forwards"] - 1
    groups = [range(k * SLOTS, (k + 1) * SLOTS) for k in range(6)]
    _assert_as_served_alone(plan, reqs, images, groups)


def test_expired_staged_request_is_a_miss(plan):
    """(b): a staged request whose deadline passes before its admission
    makes the staged batch stale; the group is built anew, its logits are
    right, and no slot leaks."""
    clock = Clock()
    images = _images(3 * SLOTS - 1)
    eng = _engine(plan, clock=clock)
    reqs = [eng.submit(ImageRequest(rid=i, image=im,
                                    deadline=1.0 if i == 5 else None))
            for i, im in enumerate(images)]
    eng.step()                  # serves 0-3, stages 4-7
    assert [r.rid for r in eng._staged.reqs] == [4, 5, 6, 7]
    clock.t = 2.0
    eng.step()                  # 5 expired: admits 4, 6, 7, 8 -> a miss
    assert eng.stats["batches_staged"] == 0
    eng.run()                   # 9, 10 were staged at bucket 2 -> a hit
    st = eng.stats
    assert st["expired"] == 1 and isinstance(reqs[5].error,
                                             DeadlineExceeded)
    assert st["completed"] == len(images) - 1
    assert st["forwards"] == 3 and st["batches_staged"] == 1
    assert eng.table.req == [None] * SLOTS and not eng.table.pending()
    groups = [range(4), [4, 6, 7, 8], [9, 10]]
    _assert_as_served_alone(plan, reqs, images, groups)


def test_partial_last_group_stages_at_its_own_bucket(plan):
    """(c): 6 requests in 4 slots: the first step stages the last two at
    bucket 2, and the second step's forward takes them."""
    images = _images(SLOTS + 2)
    eng = _engine(plan)
    reqs = [eng.submit(image=im) for im in images]
    eng.step()
    staged, bucket, x = eng._staged
    assert [r.rid for r in staged] == [4, 5] and bucket == 2
    assert x.shape == (2, 28, 28, 1)
    eng.run()
    assert eng.stats["batches_staged"] == 1
    assert eng.stats["rows"] == SLOTS + 2
    groups = [range(SLOTS), range(SLOTS, SLOTS + 2)]
    _assert_as_served_alone(plan, reqs, images, groups)


def test_more_slots_than_a_group_stage_after_the_last_group(plan):
    """8 slots in groups of 4: the step's second group stages the next
    step's first group, which is the queue's head."""
    images = _images(4 * SLOTS)
    eng = _engine(plan, slots=2 * SLOTS, buckets=(SLOTS,))
    reqs = [eng.submit(image=im) for im in images]
    eng.spans.keep = True
    eng.run()
    order = [n for n, *_ in sorted(eng.spans.records, key=lambda r: r[1])
             if n in ("serve.dispatch", "serve.stage")]
    assert order == ["serve.dispatch", "serve.dispatch", "serve.stage",
                     "serve.dispatch", "serve.dispatch"]
    assert eng.stats["forwards"] == 4 and eng.stats["batches_staged"] == 1
    groups = [range(k * SLOTS, (k + 1) * SLOTS) for k in range(4)]
    _assert_as_served_alone(plan, reqs, images, groups)


def test_failed_staging_builds_the_batch_again(plan):
    """A staging that raises fails nothing: the forward in flight
    completes, and the next group's batch is built on its own path."""
    images = _images(2 * SLOTS)
    eng = _engine(plan)
    build, calls = eng._build, []

    def flaky_build(reqs, bucket):
        calls.append(len(reqs))
        if len(calls) == 2:         # the staging after the first dispatch
            raise RuntimeError("no room on the device")
        return build(reqs, bucket)

    eng._build = flaky_build
    reqs = [eng.submit(image=im) for im in images]
    eng.run()
    assert len(calls) == 3
    assert eng.stats["completed"] == 2 * SLOTS and eng.stats["failed"] == 0
    assert eng.stats["batches_staged"] == 0
    groups = [range(SLOTS), range(SLOTS, 2 * SLOTS)]
    _assert_as_served_alone(plan, reqs, images, groups)


@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_staged_batch_carries_the_mesh_sharding(plan, kind):
    """(e): ``test_serve_sharded_mesh_bitexact``'s (1, 1) mesh with
    staging engaged: the staged array is placed with the batch sharding,
    and the served logits equal the direct forward's."""
    mesh = make_mesh((1, 1), ("data", "model"))
    images = _images(2 * SLOTS, kind)
    eng = _engine(plan, buckets=(SLOTS,), mesh=mesh, rules=DEFAULT_RULES)
    reqs = [eng.submit(image=im) for im in images]
    eng.step()
    x = eng._staged.x
    assert x.sharding == NamedSharding(mesh, P("data", None, None, None))
    eng.run()
    assert eng.stats["batches_staged"] == 1
    for g in (slice(0, SLOTS), slice(SLOTS, 2 * SLOTS)):
        direct = small.lenet_apply(plan.params, jnp.stack(images[g]), plan)
        for r, want in zip(reqs[g], np.asarray(direct)):
            np.testing.assert_array_equal(r.logits, want)
