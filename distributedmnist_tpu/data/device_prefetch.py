"""Device-side prefetch: a bounded queue of batches already staged
through ``Topology.device_put_batch``.

The synchronous loop serially runs ``next(iter)`` → ``device_put`` →
dispatch, so host batch assembly and the H2D transfer sit on the
device's critical path every step. ``DevicePrefetcher`` moves both
onto a producer thread behind a bounded queue (depth ≥ 1): while the
device executes step *k*, the producer assembles and stages batch
*k+1* (and *k+2*, …, up to the depth), so ``next()`` hands the loop a
ready sharded global array. This is the input-pipeline overlap both
arXiv:1909.09756 (MLPerf on TPU-v3 pods) and arXiv:1605.08695
(TensorFlow) name as the first-order throughput fix — the same job
tf.data's ``prefetch_to_device`` does, built here over the repo's own
iterator protocol.

Guarantees the experiments lean on:

* **Exact order.** One producer thread and a FIFO queue: the staged
  stream is the inner iterator's stream, batch for batch. The CDF /
  quorum experiments replay bit-identical data under either feed.
* **Checkpointing.** ``state()`` returns the inner iterator's cursor
  *as of the last consumed batch* (the producer snapshots the cursor
  alongside every batch it stages), so a resume replays exactly the
  batches the training step never saw — prefetched-but-unconsumed
  batches are not skipped. ``restore()`` passes through.
* **Clean shutdown.** ``stop()``/``close()`` unblock and join the
  producer even when it is parked on a full queue, and re-sync the
  inner iterator's cursor to the consumed position so a later
  ``state()``/restart observes no phantom progress. A consumer that
  raises mid-stream just calls ``stop()`` from its ``finally``.

Producer errors (a broken inner iterator, a failed ``device_put``)
are captured and re-raised in the consumer at the next ``next()``.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

from ..obsv import spans

_ITEM, _DONE, _ERR = "item", "done", "err"


class DevicePrefetcher:
    """Wraps a batch iterator; stages each batch via ``put`` (typically
    ``Topology.device_put_batch``) on a producer thread, ``depth``
    batches ahead.

    ``put`` may return anything — the eval path stages
    ``(host_weight_sum, global_array)`` tuples through it.

    The producer starts lazily on the first ``next()``, so wrapping an
    iterator costs nothing until the loop actually runs (and a restore
    before the first step never races the producer).
    """

    def __init__(self, it: Iterator[dict], put: Callable[[dict], Any],
                 depth: int = 2):
        self._it = it
        self._put = put
        self.depth = max(1, int(depth))
        self.has_state = callable(getattr(it, "state", None))
        self._restorable = self.has_state and callable(
            getattr(it, "restore", None))
        self._consumed_state = it.state() if self.has_state else None
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._closed = False

    @property
    def inner(self) -> Iterator[dict]:
        """The wrapped host-batch iterator."""
        return self._it

    # -- producer ------------------------------------------------------

    def _q_put(self, kind: str, payload: Any) -> bool:
        """Bounded put that stays responsive to ``stop()``; returns
        False when asked to stop instead of blocking forever on a full
        queue nobody will drain."""
        while not self._stop.is_set():
            try:
                self._q.put((kind, payload), timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _producer(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    with spans.span(spans.PREFETCH_ASSEMBLE):
                        batch = next(self._it)
                except StopIteration:
                    self._q_put(_DONE, None)
                    return
                # cursor AFTER producing this batch == "this batch
                # consumed" once the consumer takes it
                snap = self._it.state() if self.has_state else None
                with spans.span(spans.PREFETCH_PUT):
                    staged = self._put(batch)
                if not self._q_put(_ITEM, (staged, snap)):
                    return  # stopping; stop() re-syncs the cursor
        except BaseException as e:  # surface in the consumer thread
            self._q_put(_ERR, e)

    def _ensure_started(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._producer, name="device-prefetch", daemon=True)
            self._thread.start()

    # -- consumer ------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self) -> Any:
        if self._closed:
            raise RuntimeError("DevicePrefetcher is closed")
        self._ensure_started()
        while True:
            try:
                kind, payload = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # the producer may have enqueued its terminal
                    # sentinel between our timeout and the liveness
                    # check — drain once before declaring it lost
                    try:
                        kind, payload = self._q.get_nowait()
                        break
                    except queue.Empty:
                        raise RuntimeError(
                            "device-prefetch producer died without "
                            "a sentinel")
        if kind is _ERR:
            # full stop(), not just a join: the producer advanced the
            # inner cursor past a batch it failed to stage — re-sync
            # (or close, if the inner can't rewind) so a consumer that
            # catches the error and retries never sees a silent hole
            self.stop()
            raise payload
        if kind is _DONE:
            self._join()
            raise StopIteration
        staged, snap = payload
        self._consumed_state = snap
        return staged

    @property
    def qsize(self) -> int:
        """Staged batches ready right now (the overlap gauge: 0 every
        step means the producer is the bottleneck; ``depth`` means the
        device is)."""
        return self._q.qsize()

    # -- checkpoint passthrough ---------------------------------------

    def state(self) -> dict:
        """The inner iterator's cursor at the last *consumed* batch."""
        if not self.has_state:
            raise RuntimeError("inner iterator has no checkpointable state")
        return dict(self._consumed_state)

    def restore(self, state: dict) -> None:
        if self._closed:
            raise RuntimeError("DevicePrefetcher is closed")
        if not self._restorable:
            raise RuntimeError("inner iterator is not restorable")
        self.stop()
        self._it.restore(state)
        self._consumed_state = dict(state)

    # -- lifecycle -----------------------------------------------------

    def _join(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            while t.is_alive():
                # the producer may be parked on a full queue; drain so
                # its put (or the stop check after it) can complete
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)
        while True:  # discard anything staged after the last drain
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread = None

    def stop(self) -> None:
        """Stop and join the producer, discarding staged batches, and
        re-sync the inner cursor to the consumed position (so nothing
        is skipped if iteration resumes — ``next()`` restarts the
        producer lazily). With a non-restorable inner iterator the
        discarded batches cannot be regenerated, so the prefetcher
        closes instead of resuming with a hole in the stream."""
        self._join()
        if self._restorable:
            self._it.restore(self._consumed_state)
        else:
            self._closed = True

    def close(self) -> None:
        """``stop()`` + permanently closed. Idempotent."""
        if not self._closed:
            self.stop()
        self._closed = True

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            if not self._closed:
                self._stop.set()  # don't block GC on a full-queue join
                self.close()
        except Exception:
            pass
