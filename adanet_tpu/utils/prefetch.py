"""Background-thread input prefetching: the tf.data `.prefetch` analogue.

The reference's input pipelines run inside tf.data's C++ runtime, which
overlaps host-side batch preparation (decode, augment, copy) with
accelerator steps for free. This framework's `input_fn`s are plain Python
iterators, so without prefetch every host-side batch-prep millisecond
adds directly to device step time.

`PrefetchIterator` restores the overlap: a daemon thread drains the
source iterator into a bounded queue while the caller consumes from the
front. The heavy per-batch work (numpy slicing, the native augmentation
kernel in csrc/augment.cc, feature standardization) releases the GIL, so
a single background thread genuinely overlaps with the training loop's
dispatch work — the same design tf.data's prefetch node uses, with the
queue depth as the `buffer_size` knob.

Ordering is preserved exactly (single worker, FIFO queue), so training
remains bit-deterministic with prefetch on or off; exceptions and
exhaustion propagate to the consumer at the position they occurred.

`DevicePrefetchIterator` adds the second half of the tf.data analogue —
`prefetch_to_device`: the worker thread also *commits each batch to the
accelerator* (`jax.device_put`) before enqueueing, so with the default
buffer_size=2 the transfer of batch i+1 overlaps the device step on
batch i (classic double buffering) and the pull drops out of the
steady-state step. Shutdown is leak-audited: `close()` mid-search
(the Estimator's SIGTERM drain path) releases every
device-committed buffer still parked in the queue and the worker's
in-flight item, so neither the feeder thread nor a pinned device buffer
outlives the iterator (tests/test_prefetch.py mocks the seam).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, Optional


class PrefetchIterator:
    """Iterator pulling from `source` on a background thread.

    Args:
      source: the iterable to drain (consumed lazily, FIFO).
      buffer_size: max batches buffered ahead of the consumer.
    """

    _END = ("end", None)

    def __init__(self, source: Iterable, buffer_size: int = 2):
        if buffer_size < 1:
            raise ValueError("buffer_size must be >= 1")
        self._queue: "queue.Queue" = queue.Queue(maxsize=buffer_size)
        self._stop = threading.Event()
        self._exhausted = False
        self._thread = threading.Thread(
            target=self._fill, args=(iter(source),), daemon=True
        )
        self._thread.start()

    def _prepare(self, item):
        """Per-item worker-side hook before enqueue (identity here);
        `DevicePrefetchIterator` commits the batch to a device. Runs
        inside `_fill`'s try so a failure propagates to the consumer at
        the position it occurred."""
        return item

    def _release(self, item) -> None:
        """Disposal hook for a prepared item that will never reach the
        consumer (queue drained by close(), or enqueue aborted by a
        concurrent close()). Identity items need no disposal."""

    def _put(self, item) -> bool:
        """Blocking put that aborts when close() was requested."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _fill(self, source: Iterator) -> None:
        try:
            for item in source:
                prepared = self._prepare(item)
                if not self._put(("item", prepared)):
                    # close() raced the enqueue: the prepared item is
                    # ours to dispose of — nobody else will see it.
                    self._release(prepared)
                    return
        except BaseException as exc:  # propagated to the consumer
            self._put(("error", exc))
            return
        self._put(self._END)

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        kind, payload = self._queue.get()
        if kind == "item":
            return payload
        self._exhausted = True
        if kind == "error":
            raise payload
        raise StopIteration

    def _drain(self) -> None:
        """Empties the queue, releasing every unconsumed prepared item."""
        try:
            while True:
                kind, payload = self._queue.get_nowait()
                if kind == "item":
                    self._release(payload)
        except queue.Empty:
            pass

    def close(self) -> None:
        """Stops the worker; safe to call multiple times.

        Abandoning a consumed-mid-stream iterator without close() leaves
        a daemon thread parked on a full queue; callers that replace
        iterators (the Estimator train loop) close the old one.
        """
        self._stop.set()
        # Unblock a worker waiting on a full queue, releasing any
        # prepared (possibly device-committed) payloads that will now
        # never be consumed.
        self._drain()
        # A worker already inside queue.put() when stop was set can land
        # its in-flight item in the slot the drain just freed. Wait for
        # the worker to exit (it observes stop within one put timeout),
        # then drain again so that raced-in payload is released too —
        # the SIGTERM audit: no pinned device buffer outlives close().
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=5.0)
        self._drain()
        self._exhausted = True
        # Wake a consumer blocked in __next__'s queue.get(): with the
        # queue just drained and the worker exiting via _put's stop check,
        # nothing else would ever be enqueued. The queue was emptied above
        # so there is room; if another thread raced an item in, the
        # consumer is not blocked and the sentinel is simply surplus.
        try:
            self._queue.put_nowait(self._END)
        except queue.Full:
            pass


class DevicePrefetchIterator(PrefetchIterator):
    """Prefetch + device commit: hands back DEVICE arrays.

    The worker thread runs `jax.device_put` on every batch before
    enqueueing, so the host→device transfer of batch i+1 proceeds while
    the consumer's step on batch i runs — with `buffer_size=2` (the
    default) this is classic double buffering and the steady-state step
    no longer pays for the pull.

    `device` is forwarded to `jax.device_put`: None (commit to the
    default device), a `Device`, a `Sharding`, or a pytree of them —
    whatever the consumer's jitted step expects. Arrays already
    committed correctly are passed through by `device_put` at no cost.

    Shutdown contract (the SIGTERM mid-search drain): `close()` releases
    every device-committed batch still in the queue and the worker's
    in-flight batch via `jax.Array.delete()`, returning the pinned
    device memory without waiting for the GC; the feeder thread exits
    via the stop event like the host iterator. A `device_put` failure
    (e.g. device OOM) propagates to the consumer at the position it
    occurred, exactly like a source exception.
    """

    def __init__(
        self,
        source: Iterable,
        buffer_size: int = 2,
        device: Optional[object] = None,
    ):
        self._device = device
        super().__init__(source, buffer_size=buffer_size)

    def _prepare(self, item):
        import jax

        if self._device is None:
            return jax.device_put(item)
        return jax.device_put(item, self._device)

    def _release(self, item) -> None:
        import jax

        for leaf in jax.tree_util.tree_leaves(item):
            delete = getattr(leaf, "delete", None)
            if delete is None:
                continue
            try:
                delete()
            except Exception:
                # Already-deleted / donated buffers: releasing twice is
                # not an error worth surfacing on the shutdown path.
                pass
