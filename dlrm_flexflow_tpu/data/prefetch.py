"""Pipelined input staging: a depth-K prefetch ring fed by a background
staging thread.

The reference overlaps its data pipeline with device compute — Legion
DataLoader tasks stage batch N+1 into each GPU's framebuffer while the
device trains batch N (reference: examples/cpp/DLRM/dlrm.cc:486-589,
python/flexflow_dataloader.cc keeps the dataset zero-copy resident and
launches the per-batch scatters asynchronously). The TPU analog lives
here: a staging thread runs ``produce(i)`` for future step indices —
typically slice → ``jax.device_put`` against the model's input shardings
→ host-table gather — and parks the results in a bounded ring while the
consumer trains the current step.

Contracts (tests/test_prefetch.py pins all three):

- **Order**: items are delivered strictly in produce order
  (i = 0, 1, 2, ...), so a deterministic ``produce`` makes prefetched
  training bit-identical to calling it inline.
- **Errors**: transient ``IOError``/``OSError`` from ``produce`` are first
  absorbed by the shared :func:`~.dataloader.read_with_retries`
  backoff (same discipline as the ``.ffbin`` reader); anything that
  survives is re-raised at the consumer's next :meth:`get` — the step
  boundary — exactly like ``FFModel._host_drain`` surfaces async
  host-scatter failures. The error is sticky: the producer is dead, and
  the pipeline must be rebuilt.
- **Drain**: :meth:`close` stops the producer, discards staged items and
  joins the thread. Call it before anything that invalidates staged work
  (checkpoint restore, rollback, a reshuffle, loader state capture) and
  rebuild afterwards — re-producing dropped items is exact because
  ``produce`` is deterministic.

:meth:`stats` reports how much staging time was hidden under compute
(``overlap_fraction``), which benchmarks/bench_pipeline.py turns into the
gather/H2D overlap metric.

``fit()`` drives the ring through :class:`~.feed.BatchFeed`, whose
schedule gives a K-step superstep ONE slot: ``produce`` stages its K host
batches as a single stacked ``[K, batch, ...]`` device_put.
:func:`stack_batches` is the host-side stacking helper for non-contiguous
batch lists (contiguous data-set slices reshape for free).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Callable, Optional

from ..obs import metrics as obsm
from ..obs import trace as obstrace
from ..utils.watchdog import StallReport, WorkerStalled

# global ordinal for thread naming: every staging thread in the process
# is distinguishable in a stack dump / stall report (ff-prefetch-0, ...)
_PIPE_SEQ = itertools.count()


def stack_batches(batches):
    """Stack a list of same-keyed host batches into one ``[K, ...]``
    megabatch dict (the input to ``FFModel._stage_superstep``). All
    batches must share keys, shapes, and dtypes — a ragged list cannot
    fuse into one scan and raises here rather than at trace time."""
    import numpy as np
    if not batches:
        raise ValueError("stack_batches needs at least one batch")
    keys = set(batches[0])
    for i, b in enumerate(batches[1:], 1):
        if set(b) != keys:
            raise ValueError(
                f"batch {i} keys {sorted(b)} differ from batch 0 keys "
                f"{sorted(keys)}; superstep batches must be homogeneous")
    out = {}
    for k in batches[0]:
        arrs = [np.asarray(b[k]) for b in batches]
        if any(a.shape != arrs[0].shape or a.dtype != arrs[0].dtype
               for a in arrs[1:]):
            raise ValueError(
                f"input {k!r} has ragged shapes/dtypes across batches; "
                f"superstep batches must be homogeneous")
        out[k] = np.stack(arrs)
    return out


class PrefetchPipeline:
    """Depth-K ring buffer fed by one background staging thread.

    produce    : callable(i) -> item, for i = 0, 1, 2, ...; runs on the
                 staging thread, so it must only do thread-safe work
                 (numpy slicing and jax device_puts are).
    depth      : ring capacity = how many items may be staged ahead.
    num_items  : total items to produce (None = unbounded); `get()` past
                 the end raises IndexError.
    io_site    : fault-injection/retry site name for the transient-error
                 backoff wrapped around every produce call.
    deadline_s : liveness deadline for the staging thread: `get()` that
                 waits longer than this raises
                 :class:`~..utils.watchdog.WorkerStalled` with a
                 structured stall report instead of hanging (0/None =
                 wait forever, the pre-watchdog behavior).
    """

    def __init__(self, produce: Callable[[int], object], depth: int = 2,
                 num_items: Optional[int] = None, name: str = "prefetch",
                 io_site: str = "prefetch", io_retries: int = 3,
                 io_backoff_s: float = 0.05,
                 deadline_s: Optional[float] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._produce = produce
        self._depth = int(depth)
        self._num = num_items
        self._io_site = io_site
        self._io_retries = io_retries
        self._io_backoff_s = io_backoff_s
        self._deadline_s = deadline_s if deadline_s else None
        self._buf: deque = deque()
        self._cond = threading.Condition()
        self._stopped = False
        self._exc: Optional[BaseException] = None
        self._produced = 0
        self._consumed = 0
        # staging-time accounting for the overlap metric
        self._produce_s = 0.0
        self._wait_s = 0.0
        self.name = name
        obsm.register_collector(self._obs_collect)
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"ff-prefetch-{next(_PIPE_SEQ)}")
        self._thread.start()

    def _obs_collect(self):
        """Registry collector: the ring's staging accounting as
        scrapeable samples (same numbers stats() reports)."""
        s = self.stats()
        lab = {"pipeline": self.name}
        yield "ff_prefetch_items_total", lab, s["items"]
        yield "ff_prefetch_produce_seconds_total", lab, s["produce_s"]
        yield "ff_prefetch_wait_seconds_total", lab, s["wait_s"]
        yield "ff_prefetch_overlap_fraction", lab, s["overlap_fraction"]
        yield "ff_prefetch_ring_depth", lab, len(self._buf)

    # --- producer side -------------------------------------------------
    def _run(self):
        from .dataloader import read_with_retries
        from ..utils import faults
        i = 0
        while True:
            with self._cond:
                while len(self._buf) >= self._depth and not self._stopped:
                    self._cond.wait()
                if self._stopped or (self._num is not None
                                     and i >= self._num):
                    return
            t0 = time.perf_counter()
            try:
                faults.maybe_stall("prefetch")   # simulated wedged stager
                # span lands on THIS (ff-prefetch-N) thread: staging
                # time shows as its own trace lane under the consumer's
                # train/step spans
                with obstrace.span("prefetch/produce",
                                   pipeline=self.name, item=i):
                    item = read_with_retries(lambda: self._produce(i),
                                             self._io_site,
                                             retries=self._io_retries,
                                             backoff_s=self._io_backoff_s)
            except BaseException as e:
                with self._cond:
                    self._exc = e
                    self._cond.notify_all()
                return
            dt = time.perf_counter() - t0
            with self._cond:
                if self._stopped:
                    return
                self._buf.append(item)
                self._produced += 1
                self._produce_s += dt
                self._cond.notify_all()
            i += 1

    # --- consumer side -------------------------------------------------
    def get(self):
        """Next staged item, in produce order. Blocks until staged.

        Raises the staging thread's error (sticky — rebuild the pipeline
        after), IndexError past `num_items`, or — when `deadline_s` is
        set — :class:`WorkerStalled` if the staging thread misses its
        liveness deadline (wedged device_put, stuck IO): the structured
        stall report names the thread and what was awaited, and the
        elastic layer recovers instead of the job hanging."""
        t0 = time.perf_counter()
        with self._cond:
            while not self._buf:
                if self._exc is not None:
                    raise self._exc
                if self._stopped:
                    raise RuntimeError("prefetch pipeline is closed")
                if self._num is not None and self._consumed >= self._num:
                    raise IndexError(
                        f"prefetch pipeline exhausted after {self._num} "
                        f"items")
                waited = time.perf_counter() - t0
                if (self._deadline_s is not None
                        and waited >= self._deadline_s):
                    raise WorkerStalled(StallReport(
                        worker=self._thread.name,
                        waiting_for=f"staged item {self._consumed}",
                        waited_s=waited, deadline_s=self._deadline_s,
                        detail=(f"pipeline {self.name!r}: produced "
                                f"{self._produced}, consumed "
                                f"{self._consumed}, depth {self._depth}"),
                        alive=self._thread.is_alive()))
                timeout = (None if self._deadline_s is None
                           else self._deadline_s - waited)
                self._cond.wait(timeout)
            item = self._buf.popleft()
            self._consumed += 1
            self._wait_s += time.perf_counter() - t0
            self._cond.notify_all()
        return item

    def ready(self) -> bool:
        """Does the ring hold an item, so that :meth:`get` returns at
        once? Asked without the lock (one `len` of a deque)."""
        return bool(self._buf)

    def close(self, join_timeout_s: float = 10.0):
        """Stop the producer, discard staged items, join the thread.
        Never raises — pending staging errors die with the pipeline
        (a caller closing is abandoning the staged stream anyway). The
        join is BOUNDED: a wedged staging thread is abandoned (it is a
        daemon, so interpreter shutdown and test teardown never hang on
        it) rather than waited on forever."""
        obsm.unregister_collector(self._obs_collect)
        with self._cond:
            self._stopped = True
            self._buf.clear()
            self._cond.notify_all()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=join_timeout_s)
            if self._thread.is_alive():
                from ..utils.logging import get_logger
                get_logger("prefetch").warning(
                    "staging thread %s did not exit within %.3gs of "
                    "close(); abandoning it (daemon)",
                    self._thread.name, join_timeout_s)

    @property
    def closed(self) -> bool:
        return self._stopped

    def stats(self) -> dict:
        """Staging accounting: `overlap_fraction` is the share of total
        staging time hidden under the consumer's compute (1.0 = the
        consumer never waited on the ring)."""
        with self._cond:
            ps, ws = self._produce_s, self._wait_s
            items = self._consumed
        hidden = max(ps - min(ws, ps), 0.0)
        return {"items": items,
                "produce_s": ps,
                "wait_s": ws,
                "overlap_fraction": (hidden / ps) if ps > 0 else 1.0}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
