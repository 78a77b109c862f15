"""fit()'s feed (data/feed.py): one schedule, and equal items whichever
way they are staged.

- the schedule covers every batch once, in order, with K-groups only on
  K boundaries — from the start, from a position inside a group, and
  after a rewind to each;
- a resident feed, a streamed one (the prefetch ring) and a synchronous
  one hand out equal arrays for the same schedule;
- `restage()` stages the resident items again, against whatever the
  stagers' shardings are by then.
"""

import numpy as np
import pytest

from dlrm_flexflow_tpu.data.feed import BatchFeed, Entry

BS = 4


def _data(n, seed=0):
    r = np.random.RandomState(seed)
    return ({"x": r.rand(n, 3).astype(np.float32),
             "ids": r.randint(0, 9, size=(n, 2)).astype(np.int32)},
            r.rand(n, 1).astype(np.float32))


class _Stagers:
    """Stand-ins for the model's two stagers: an item is the host batch
    it was made from, tagged with the "sharding" current at staging."""

    def __init__(self):
        self.sharding = "mesh-0"
        self.calls = 0

    def step(self, batch):
        self.calls += 1
        return ("step", self.sharding, batch)

    def superstep(self, stacked):
        self.calls += 1
        return ("super", self.sharding, stacked)


def _feed(n, k, epochs=2, st=None, **kw):
    x, y = _data(n)
    st = st or _Stagers()
    return BatchFeed(x, y, BS, k, epochs, st.step, st.superstep, **kw)


def _covered(entries, nb):
    """The batches a run of one epoch's entries covers, in order."""
    out = []
    for e in entries:
        out.extend(range(e.b, e.b + e.k))
    return out


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("n", [BS * 8, BS * 7, BS * 6 + 3, BS * 5 + 1])
def test_schedule_covers_every_batch_once_in_order(k, n):
    feed = _feed(n, k, epochs=3, mode="never", depth=0)
    nb, rem = n // BS, n % BS
    want = list(range(nb + (1 if rem else 0)))   # the remainder is batch nb

    def check(entries, epoch0, b0):
        by_epoch = {}
        for ent in entries:
            assert isinstance(ent, Entry)
            by_epoch.setdefault(ent.epoch, []).append(ent)
            if ent.k > 1:
                # a fused group sits on a K boundary and inside the epoch
                assert ent.k == k and ent.b % k == 0 and ent.b + k <= nb
            elif k > 1 and ent.b % k == 0 and ent.b + k <= nb:
                pytest.fail(f"{ent} should have fused")
            if ent.b == nb:
                assert ent.k == 1 and rem
        for e in range(epoch0, 3):
            first = b0 if e == epoch0 else 0
            assert (_covered(by_epoch.pop(e, []), nb)
                    == [b for b in want if b >= first])
        assert not by_epoch

    check(list(feed.schedule()), 0, 0)
    # a start at every position of an epoch, inside a group included,
    # and the feed's own walk after a rewind to each
    for b0 in range(nb + 1):
        check(list(feed.schedule(1, b0)), 1, b0)
        feed.rewind(1, b0)
        walked = []
        for epoch in (1, 2):
            while (ent := feed.peek(epoch)) is not None:
                item = feed.get()
                assert item[0] == ("super" if ent.k > 1 else "step")
                walked.append(ent)
            # an epoch's end is not the next one's entry
            assert feed.peek(epoch) is None
        assert walked == list(feed.schedule(1, b0))
    # the slices are the data, once each
    x, y = _data(n)
    got = np.concatenate([
        feed.host_slice(e.b, e.k)["label"].reshape(-1, 1)
        for e in feed.epoch_entries(0)])
    np.testing.assert_array_equal(got, y)
    sup = feed.host_slice(0, k)["x"]
    assert sup.shape == ((k, BS, 3) if k > 1 else (BS, 3))
    np.testing.assert_array_equal(sup.reshape(-1, 3), x["x"][:k * BS])


@pytest.mark.parametrize("k,start", [(1, 0), (2, 0), (2, 3), (4, 5)])
def test_resident_streamed_and_synchronous_hand_out_equal_arrays(k, start):
    n = BS * 9 + 2

    def drain(**kw):
        feed = _feed(n, k, **kw)
        feed.restage()
        feed.rewind(0, start)
        out = []
        with feed:
            for epoch in range(2):
                while (ent := feed.peek(epoch)) is not None:
                    out.append((ent, feed.get()))
        return feed, out

    rf, resident = drain(mode="always")
    sf, streamed = drain(mode="never", depth=3)
    _, sync = drain(mode="never", depth=0)
    assert rf.resident and not sf.resident
    assert len(resident) == len(streamed) == len(sync)
    for (ea, a), (eb, b), (ec, c) in zip(resident, streamed, sync):
        assert ea == eb == ec
        assert a[0] == b[0] == c[0]
        for name in a[2]:
            np.testing.assert_array_equal(a[2][name], b[2][name])
            np.testing.assert_array_equal(a[2][name], c[2][name])


def test_residency_follows_the_budget_and_the_split():
    x, y = _data(BS * 4)
    total = sum(v.nbytes for v in x.values()) + y.nbytes
    st = _Stagers()

    def resident(**kw):
        return BatchFeed(x, y, BS, 1, 1, st.step, st.superstep,
                         **kw).resident

    assert resident(budget=total)
    assert not resident(budget=total - 1)
    # spread over 4 chips, a chip's share is a quarter
    split = {"x": 4, "ids": 4, "label": 4}
    assert resident(budget=total / 4, split=split)
    assert not resident(budget=total / 4 - 1, split=split)
    assert resident(mode="always") and not resident(mode="never",
                                                    budget=total)


def test_restage_stages_again_against_changed_shardings():
    st = _Stagers()
    feed = _feed(BS * 6 + 1, 2, st=st, mode="always")
    feed.restage()
    staged_once = st.calls
    assert staged_once == 3 + 1          # three groups and the remainder
    feed.rewind(0, 0)
    first = [feed.get() for _ in range(4)]
    assert {it[1] for it in first} == {"mesh-0"}
    assert st.calls == staged_once       # resident: nothing staged anew

    st.sharding = "mesh-1"               # elastic recovery re-planned
    feed.restage()
    feed.rewind(0, 1)                    # inside the first group
    again = []
    while feed.peek(0) is not None:
        again.append(feed.get())
    assert {it[1] for it in again} == {"mesh-1"}
    # batch 1 staged on the fly, then the resident groups and remainder
    assert [it[0] for it in again] == ["step", "super", "super", "step"]
    np.testing.assert_array_equal(again[1][2]["label"],
                                  first[1][2]["label"])


def test_a_remainder_that_cannot_stage_is_dropped_and_closing_tells():
    closed = []

    def step(batch):
        if len(batch["label"]) != BS:
            raise ValueError("odd shape")
        return ("step", None, batch)

    x, y = _data(BS * 3 + 2)
    feed = BatchFeed(x, y, BS, 1, 2, step, None, mode="always",
                     on_close=lambda: closed.append(1))
    assert feed.rem == 2
    feed.restage()
    assert feed.rem == 0
    feed.rewind(0, 0)
    assert len(closed) == 1
    assert [e.b for e in feed.schedule()] == [0, 1, 2] * 2


@pytest.mark.parametrize("kw", [dict(mode="always"),
                                dict(mode="never", depth=3),
                                dict(mode="never", depth=0)],
                         ids=["resident", "streamed", "synchronous"])
@pytest.mark.parametrize("k,at", [(4, 0), (4, 5), (2, 7), (8, 3), (4, 14)])
def test_replan_in_mid_schedule_goes_on_in_groups(kw, k, at):
    """fit()'s pace probe re-plans a feed that began per step: from batch
    `at` of epoch 0 on, the feed hands out what a feed of groups of `k`
    from the start hands out from there, and covers every batch once."""
    n = BS * 14 + 3

    def walk(feed, epochs):
        out = []
        for epoch in epochs:
            while (ent := feed.peek(epoch)) is not None:
                out.append((ent, feed.get()))
        return out

    st = _Stagers()
    with _feed(n, 1, st=st, **kw) as feed:
        feed.restage()
        feed.rewind(0, 0)
        before = [feed.get() for _ in range(at)]
        assert all(it[0] == "step" for it in before)
        feed.replan(k, 0, at)
        after = walk(feed, (0, 1))
    with _feed(n, k, **kw) as planned:
        planned.restage()
        planned.rewind(0, at)
        want = walk(planned, (0, 1))
    assert [e for e, _ in after] == [e for e, _ in want]
    assert any(e.k == k for e, _ in after)
    first = [e for e, _ in after if e.epoch == 0]
    assert _covered(first, 14) == list(range(at, 15))
    # single steps up to the next boundary of k, the tail and the remainder
    assert all(e.k == 1 for e in first if e.b < -(-at // k) * k)
    for (ea, a), (_, b) in zip(after, want):
        assert a[0] == b[0] == ("super" if ea.k > 1 else "step")
        for name in a[2]:
            np.testing.assert_array_equal(a[2][name], b[2][name])


def test_at_hand_says_whether_get_would_wait_for_the_stager():
    """fit()'s pace probe asks nothing at a dispatch whose input is not
    staged yet: a resident feed and a synchronous one always have theirs
    at hand, a ring when an item waits in it."""
    import threading
    import time
    n = BS * 6
    for kw in (dict(mode="always"), dict(mode="never", depth=0)):
        with _feed(n, 1, **kw) as feed:
            feed.restage()
            feed.rewind(0, 0)
            assert feed.at_hand()
    gate = threading.Event()
    st = _Stagers()
    step = st.step
    st.step = lambda batch: (gate.wait(30), step(batch))[1]
    with _feed(n, 1, st=st, mode="never", depth=2) as feed:
        feed.rewind(0, 0)
        assert not feed.at_hand()       # the stager has not come through
        gate.set()
        feed.get()                      # waits for the first item
        deadline = time.monotonic() + 30
        while not feed.at_hand() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert feed.at_hand()           # the second waits in the ring
