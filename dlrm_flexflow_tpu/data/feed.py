"""fit()'s one feed: a schedule of dispatches over an in-memory data set,
and the staged item of each.

``FFModel.fit`` trains whatever :meth:`BatchFeed.get` hands it, one
``StagedStep`` a dispatch. The feed owns, and nothing else knows: the
slice that makes batch ``b`` (:meth:`~BatchFeed.host_slice`), the schedule
(:meth:`~BatchFeed.schedule`: which batches fuse into a superstep; producer
and consumer walk the one list), and where items come from: *resident*
(every entry of an epoch staged once, up front — the reference's design,
the whole data set in zero-copy memory, dlrm.cc:384-589) or *streamed* (a
:class:`~.prefetch.PrefetchPipeline` stages ``depth`` entries ahead while
the device trains — the reference's DataLoader tasks staging batch N+1
under batch N's compute — or, at depth 0, each entry is staged as it is
asked for). Staging is deterministic, so the three hand out equal arrays
and training is bit-identical (tests/test_feed.py, tests/test_prefetch.py).
Whatever invalidates staged work goes through the feed: ``rewind``,
``restage``, ``replan``, ``drop_remainder``, ``close``. The model is not imported
here: its stagers (``_stage_step``, ``_stage_superstep``) come in as
callables.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional

import numpy as np

from ..obs import trace as obstrace
from ..utils.logging import log_model
from .prefetch import PrefetchPipeline


class Entry(NamedTuple):
    """One dispatch: ``k`` batches starting at batch ``b`` of ``epoch``
    (``k`` > 1: one fused superstep's megabatch). The remainder is the
    entry whose ``b`` is the number of full batches."""

    epoch: int
    b: int
    k: int


class BatchFeed:
    """The schedule of ``epochs`` passes over ``(inputs, labels)`` in
    batches of ``batch_size``, fused ``k`` at a time where they align, and
    its staged items.

    mode/budget/split : the residency choice. ``mode`` is
        ``FFConfig.stage_dataset`` ("never" streams, "always" trusts the
        caller on capacity); under "auto" the data set is resident when
        one chip's share of it — an array's bytes over ``split[name]``,
        the chips its sharding spreads it on — fits ``budget`` bytes.
    depth      : entries the streamed feed stages ahead (0: none, staged
                 synchronously in :meth:`get`).
    deadline_s : the staging thread's liveness deadline
                 (:class:`~.prefetch.PrefetchPipeline`).
    on_close   : called whenever staged-ahead work is abandoned (the
                 model forgets the host-table gather it chained to an
                 item that will never train).
    """

    def __init__(self, inputs: Dict[str, np.ndarray], labels: np.ndarray,
                 batch_size: int, k: int, epochs: int,
                 stage_step: Callable[[Dict], Any],
                 stage_superstep: Callable[[Dict], Any], *,
                 mode: str = "auto", budget: float = 0.0,
                 split: Optional[Dict[str, int]] = None, depth: int = 2,
                 deadline_s: Optional[float] = None,
                 on_close: Optional[Callable[[], None]] = None):
        self._inputs, self._labels = inputs, labels
        self.bs, self.k, self.epochs = int(batch_size), int(k), int(epochs)
        self.n = len(labels)
        self.num_batches = self.n // self.bs
        # samples of the remainder batch; 0 when there is none, or once
        # it was dropped
        self.rem = self.n - self.num_batches * self.bs
        self._stage_step, self._stage_superstep = stage_step, stage_superstep
        split = split or {}
        cost = sum(v.nbytes / split.get(name, 1)
                   for name, v in inputs.items())
        cost += labels.nbytes / split.get("label", 1)
        self.resident = mode == "always" or (mode != "never"
                                             and cost <= budget)
        self._depth, self._deadline_s = int(depth), deadline_s
        self._on_close = on_close
        self._staged: Dict[int, Any] = {}   # first batch -> resident item
        self._sched: List[Entry] = []
        self._i = 0                         # the next entry to hand out
        self._pipe: Optional[PrefetchPipeline] = None
        self._ahead = None                  # item peek_host_idx fetched
        self._deferred: Optional[BaseException] = None

    # --- the slice and the schedule ------------------------------------
    def host_slice(self, b: int, k: int = 1) -> Dict[str, np.ndarray]:
        """Host views (no copy) of ``k`` batches from batch ``b``, the
        label under "label": ``[bs, ...]`` arrays, ``[k, bs, ...]`` for a
        megabatch, fewer rows for the remainder."""
        sl = slice(b * self.bs, min((b + k) * self.bs, self.n))
        batch = {name: v[sl] for name, v in self._inputs.items()}
        batch["label"] = self._labels[sl]
        if k > 1:
            batch = {name: np.asarray(v).reshape((k, self.bs) + v.shape[1:])
                     for name, v in batch.items()}
        return batch

    def epoch_entries(self, epoch: int, b0: int = 0) -> Iterator[Entry]:
        """The dispatches of one epoch from batch ``b0`` on."""
        b = b0
        while b < self.num_batches:
            k = (self.k if b % self.k == 0
                 and b + self.k <= self.num_batches else 1)
            yield Entry(epoch, b, k)
            b += k
        if self.rem:
            yield Entry(epoch, self.num_batches, 1)

    def schedule(self, epoch0: int = 0, b0: int = 0) -> Iterator[Entry]:
        """Every dispatch from batch ``b0`` of ``epoch0`` to the end of
        the last epoch, in order."""
        for e in range(epoch0, self.epochs):
            yield from self.epoch_entries(e, b0 if e == epoch0 else 0)

    # --- staging -------------------------------------------------------
    def _produce(self, ent: Entry):
        host = self.host_slice(ent.b, ent.k)
        return (self._stage_superstep(host) if ent.k > 1
                else self._stage_step(host))

    def restage(self) -> None:
        """(Re)stage the resident items against the stagers' CURRENT
        shardings: before the first epoch, and after elastic recovery
        re-planned the mesh (arrays staged on the old mesh must not feed a
        program compiled for the new one). Aligned groups stage as
        megabatches (one put each), the tail and the remainder (the cost
        counted it) on their own. A streamed feed stages in
        :meth:`rewind`."""
        if not self.resident:
            return
        with obstrace.span("fit/stage"):
            self._staged = {}
            for ent in self.epoch_entries(0):
                try:
                    self._staged[ent.b] = self._produce(ent)
                except Exception as e:
                    if ent.b != self.num_batches:
                        raise
                    self.drop_remainder(e)

    def rewind(self, epoch: int, b: int) -> None:
        """Position the feed at batch ``b`` of ``epoch`` (resume,
        rollback, recovery). What the ring staged ahead is dropped and
        staged again from there (deterministic, so exact). A position
        inside a resident megabatch's group has no resident item:
        :meth:`get` stages those batches on the fly until the schedule is
        aligned again."""
        self.close()
        sched = self._sched = list(self.schedule(epoch, b))
        self._i = 0
        if sched and self._depth > 0 and not self.resident:
            self._pipe = PrefetchPipeline(
                lambda i: self._produce(sched[i]), depth=self._depth,
                num_items=len(sched), name="fit",
                deadline_s=self._deadline_s)

    def replan(self, k: int, epoch: int, b: int) -> None:
        """Go on in groups of ``k`` from batch ``b`` of ``epoch``: fit()'s
        pace probe found the host setting the pace in mid-schedule. The
        resident items are staged again as groups; the batches before the
        next boundary of ``k`` stay single dispatches."""
        self.k = int(k)
        self.restage()
        self.rewind(epoch, b)

    def drop_remainder(self, why: BaseException) -> None:
        """The remainder's shape cannot stage or train: take it out of
        the schedule, loudly (the reference loop silently trains only
        full batches). A caller in mid-schedule rewinds past it."""
        log_model.warning(
            "dropping the remainder batch (%d samples): it cannot stage "
            "or train at its own shape (%s) — pad the dataset or pick a "
            "batch size dividing %d", self.rem, why, self.n)
        self.rem = 0
        self._staged.pop(self.num_batches, None)

    def close(self) -> None:
        """Stop the staging thread and forget what it staged ahead."""
        if self._pipe is not None:
            self._pipe.close()
            self._pipe = None
        self._ahead = self._deferred = None
        if self._on_close is not None:
            self._on_close()

    def __enter__(self) -> "BatchFeed":
        return self

    def __exit__(self, *exc) -> bool:
        # the staging thread must not outlive fit() on ANY exit path
        self.close()
        return False

    # --- the consumer --------------------------------------------------
    def peek(self, epoch: int) -> Optional[Entry]:
        """The entry :meth:`get` hands out next, if it belongs to
        ``epoch``; None once the epoch (or the schedule) is through."""
        if self._i < len(self._sched):
            ent = self._sched[self._i]
            if ent.epoch == epoch:
                return ent
        return None

    def at_hand(self) -> bool:
        """Would :meth:`get` return without waiting for the staging
        thread? True for a resident feed and for one that stages in
        :meth:`get` itself; with a ring, when an item waits in it."""
        return (self._pipe is None or self._ahead is not None
                or self._pipe.ready())

    def get(self):
        """The staged item of the next entry. A staging error surfaces
        here, at the step boundary, and is sticky: rewind (or drop the
        remainder) before asking again."""
        if self._deferred is not None:
            exc, self._deferred = self._deferred, None
            raise exc
        if self._ahead is not None:
            item, self._ahead = self._ahead, None
        elif self._pipe is not None:
            item = self._pipe.get()
        else:
            ent = self._sched[self._i]
            item = self._staged.get(ent.b) or self._produce(ent)
        self._i += 1
        return item

    def peek_host_idx(self):
        """Host-table indices of the entry AFTER the one being trained,
        for the async host-table worker to chain its gather behind this
        step's scatter. Runs inside the train step at scatter-launch time
        (the device already executes this step): it takes the next item
        off the ring, and :meth:`get` hands that one out. None when
        nothing is staged ahead (no ring, or the schedule ends). A
        staging error must not skip this step's scatter: it is kept for
        the next :meth:`get`."""
        if self._pipe is None:
            return None
        try:
            self._ahead = self._pipe.get()
            return self._ahead.host_idx
        except IndexError:        # end of schedule
            return None
        except BaseException as e:
            self._deferred = e
            return None
