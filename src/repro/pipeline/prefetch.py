"""Async feed prefetch pipeline: staleness-aware batch construction overlap.

The synchronous feed path puts host-side batch construction and the
host→device transfer squarely on the critical path of every step:

    feed row -> starts lookup -> device_put -> jitted step   (lockstep)

Index-batching made the *device* side of the step cheap (the gather runs
from the resident series), which leaves the host feed path as the visible
overhead — exactly the latency MSPipe (arXiv:2402.15113) shows can be
hidden by bounded staleness in the temporal-GNN data path with no accuracy
loss.  This module is that pipeline, in two explicit stages:

- **Stage 1 — host materialization** (always on, background thread): pull
  ``[<=chunk, width]`` numpy row blocks from a :meth:`feed_stream`-style
  iterator and queue them, bounded by ``depth`` blocks.  Pure host work:
  feeds are pure functions of (seed, epoch, rank), so a row materialized
  several steps early holds the identical window ids it would hold if built
  lockstep.

- **Stage 2 — host→device transfer**:

  * ``staleness == 0`` — transfer at consume, on the CALLER thread:
    ``next()`` pops a host row and calls ``transfer(row)`` right there,
    which is the exact op order of the synchronous path (`batch_of_starts`
    immediately before the step).  This is the provable identity: pure
    rows + unchanged caller-thread op order ⇒ bit-identical training.
  * ``staleness >= 1`` — a second background thread runs ``transfer`` up to
    ``staleness`` batches beyond the one being consumed, so the transfer
    for step k+1..k+staleness is dispatched (and its copy proceeds) while
    step k's jitted computation is in flight.  Batch construction may then
    overlap across step boundaries — bounded-stale semantics.  Values are
    still identical (pure feeds); what changes is only *when* host/transfer
    work happens relative to the step stream.

Threading rules: stage 1 does numpy work only (its ``feed.build`` host span
initialises no backend), so it is safe to start before
``jax.distributed.initialize()`` has run.  Stage 2 calls the
transfer fn (``device_put`` / ``make_array_from_process_local_data``) from
its own thread — process-local calls with no collectives, safe under
``jax.distributed`` — and only exists at staleness >= 1.  Kernel-level
backend defaults are resolved lazily per call (``repro.kernels.common``),
so neither thread can pin a backend verdict the main thread has not made.

``close()`` drains the pipeline: both threads stop, queued work is dropped,
and the iterator ends.  The engine drains on every elastic re-mesh so a
kill→shrink→grow cycle resumes from checkpoint coordinates with no stale
in-flight batches — determinism is the checkpoint's, not the pipeline's.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro import trace_names

#: End-of-stream marker flowing through the stage queues.
_DONE = object()

#: Queue put/get timeout — how often blocked stage threads re-check stop.
_TICK = 0.05


@dataclasses.dataclass(frozen=True)
class PrefetchPlan:
    """How far ahead each pipeline stage may run.

    ``depth``      host row blocks stage 1 may materialize beyond the block
                   being consumed (bounds host memory: depth × chunk rows).
    ``staleness``  device batches stage 2 may transfer beyond the batch
                   being consumed.  0 = today's lockstep semantics (transfer
                   at consume, caller thread — bit-identical by
                   construction); s >= 1 = the transfer for step k+s may be
                   in flight while step k computes.
    ``chunk``      feed rows per stage-1 block.
    """

    depth: int = 2
    staleness: int = 0
    chunk: int = 8

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {self.depth}")
        if self.staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {self.staleness}")
        if self.chunk < 1:
            raise ValueError(f"prefetch chunk must be >= 1, got {self.chunk}")


class FeedPrefetcher:
    """Iterator of device-ready batches over a host feed-chunk stream.

    ``rows``: iterator of ``[<=chunk, width]`` numpy blocks (e.g.
    ``DataPlane.grid_stream(epoch)``).  ``transfer``: one host row ->
    device batch (e.g. ``DataPlane.batch_of_starts``).  Yields exactly
    ``transfer(row)`` for every row of every block, in order — the same
    sequence the synchronous loop produces.
    """

    def __init__(self, rows: Iterator[np.ndarray],
                 transfer: Callable[[np.ndarray], Any],
                 plan: PrefetchPlan = PrefetchPlan()):
        self.plan = plan
        self._transfer = transfer
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._finished = False
        # Stage 1: host row blocks, materialized `depth` blocks ahead.
        self._host_q: queue.Queue = queue.Queue(maxsize=plan.depth)
        self._host_thread = threading.Thread(
            target=self._host_stage, args=(rows,),
            name="feed-prefetch-host", daemon=True)
        # Stage 2 (staleness >= 1 only): device batches, transferred up to
        # `staleness` beyond the consumed batch (queue slots + the row the
        # thread is transferring bound the run-ahead).
        self._dev_q: queue.Queue | None = None
        self._dev_thread: threading.Thread | None = None
        if plan.staleness >= 1:
            self._dev_q = queue.Queue(maxsize=plan.staleness)
            self._dev_thread = threading.Thread(
                target=self._transfer_stage, name="feed-prefetch-transfer",
                daemon=True)
        # staleness-0 consume path: rows of the block currently being drained
        self._pending: list[np.ndarray] = []
        self._host_thread.start()
        if self._dev_thread is not None:
            self._dev_thread.start()

    # ------------------------------------------------------------- stages
    def _put(self, q: queue.Queue, item) -> bool:
        """Bounded put that aborts (returns False) once close() is called."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=_TICK)
                return True
            except queue.Full:
                continue
        return False

    def _host_stage(self, rows: Iterator[np.ndarray]) -> None:
        try:
            rows = iter(rows)
            while True:
                with jax.profiler.TraceAnnotation(trace_names.FEED_BUILD):
                    block = next(rows, _DONE)
                if block is _DONE:
                    self._put(self._host_q, _DONE)
                    break
                if self._stop.is_set() or not self._put(self._host_q, block):
                    break
        except BaseException as e:  # surfaced to the consumer in __next__
            self._error = e
            self._put(self._host_q, _DONE)
        finally:
            close = getattr(rows, "close", None)
            if close is not None:
                close()

    def _transfer_stage(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    block = self._host_q.get(timeout=_TICK)
                except queue.Empty:
                    continue
                if block is _DONE:
                    self._put(self._dev_q, _DONE)
                    return
                for row in block:
                    with jax.profiler.TraceAnnotation(
                            trace_names.FEED_TRANSFER):
                        batch = self._transfer(row)
                    if not self._put(self._dev_q, batch):
                        return
            # closed mid-stream: nothing more to do
        except BaseException as e:
            self._error = e
            self._put(self._dev_q, _DONE)

    # ----------------------------------------------------------- consumer
    def __iter__(self) -> "FeedPrefetcher":
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        src = self._dev_q
        if src is None:
            # staleness 0: pop a host row and transfer it HERE, on the
            # caller thread — the synchronous path's exact op order.
            while not self._pending:
                block = self._get(self._host_q)
                if block is _DONE:
                    return self._finish()
                self._pending = list(block)
            with jax.profiler.TraceAnnotation(trace_names.FEED_TRANSFER):
                return self._transfer(self._pending.pop(0))
        batch = self._get(src)
        if batch is _DONE:
            return self._finish()
        return batch

    def _get(self, q: queue.Queue):
        with jax.profiler.TraceAnnotation(trace_names.FEED_WAIT):
            while True:
                if self._stop.is_set():
                    return _DONE
                try:
                    return q.get(timeout=_TICK)
                except queue.Empty:
                    if self._error is not None:
                        return _DONE

    def _finish(self):
        self._finished = True
        if self._error is not None:
            err, self._error = self._error, None
            raise err
        raise StopIteration

    # -------------------------------------------------------------- drain
    def close(self, *, timeout: float = 5.0) -> None:
        """Drain the pipeline: stop both threads, drop queued work.

        Idempotent, and safe to call from the step loop's ``finally`` as
        well as the engine's elastic re-mesh path.  After close() the
        iterator is exhausted; a re-mesh builds a fresh prefetcher over the
        new data plane rather than reusing this one.
        """
        self._stop.set()
        self._finished = True
        for t in (self._host_thread, self._dev_thread):
            if t is None or not t.is_alive():
                continue
            deadline = time.monotonic() + timeout
            while t.is_alive() and time.monotonic() < deadline:
                # unblock producers stuck on a full queue
                for q in (self._host_q, self._dev_q):
                    if q is not None:
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass
                t.join(timeout=_TICK)
