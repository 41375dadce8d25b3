"""Asynchronous host→device input pipeline.

The r02 benchmark showed ~14× between the compute-only ceiling and the
system number — lost to synchronous host collation (SURVEY §7 "the host
must not bottleneck — double-buffer to device"). This module closes that
gap: a background thread drains the host batch generator,
computes any host-side statistics, and issues ``jax.device_put`` ahead of
need so a depth-``depth`` buffer of device-resident batches is always ready
when the training loop asks for the next one.

The reference has no analog (its DataLoader workers feed a synchronous
Lightning loop); this is TPU-native design: ``device_put`` is asynchronous,
so the transfer of batch N+1 overlaps the compute of batch N, and collation
of batch N+2 overlaps both.

Resume semantics are untouched: prefetching wraps the generator without
changing its rng stream, so the ``skip_batches`` mid-epoch resume contract of
`JaxDataset.batches` holds bit-for-bit.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator

_SENTINEL = object()


class DevicePrefetcher:
    """Iterates ``(device_batch, host_stats)`` with background collation.

    Args:
        batches: host batch iterable (e.g. ``JaxDataset.batches(...)``).
        place_fn: host batch → device batch (e.g. ``shard_batch(b, mesh)``);
            called in the worker thread. ``jax.device_put`` is async, so this
            only *enqueues* the transfer.
        host_stats_fn: optional host batch → picklable stats, computed in the
            worker **before** transfer so the training loop never syncs the
            device to read e.g. the event count.
        depth: number of device batches buffered ahead (2 = double buffering).

    The iterator re-raises worker exceptions at the consuming site and stops
    its thread on `close` (also called on destruction and generator exit).
    """

    def __init__(
        self,
        batches: Iterable,
        place_fn: Callable[[Any], Any],
        host_stats_fn: Callable[[Any], Any] | None = None,
        depth: int = 2,
    ):
        # State used by close() is assigned before any validation so a
        # failed construction still destructs cleanly via __del__.
        self._stop = threading.Event()
        self._thread = None
        # Streaming sources (e.g. the sharded-ETL feed) may expose close();
        # held so close() can tell a stalled source to stop producing
        # instead of abandoning the worker mid-`__next__` every time.
        self._source = batches
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1; got {depth}")
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._thread = threading.Thread(
            target=self._worker,
            args=(iter(batches), place_fn, host_stats_fn),
            daemon=True,
        )
        self._thread.start()

    def _worker(self, it: Iterator, place_fn, host_stats_fn) -> None:
        try:
            for host_batch in it:
                if self._stop.is_set():
                    return
                stats = host_stats_fn(host_batch) if host_stats_fn is not None else None
                device_batch = place_fn(host_batch)
                self._put((device_batch, stats))
            self._put(_SENTINEL)
        except BaseException as e:  # noqa: BLE001 — must surface in consumer
            self._put(e)

    def _put(self, item) -> None:
        """Blocking put that wakes on close() instead of deadlocking."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self):
        return self

    def __next__(self):
        # A closed (or exhausted) prefetcher terminates iteration instead of
        # blocking forever on an empty queue; the timeout loop also covers a
        # close() racing a blocked get().
        while not self._stop.is_set():
            try:
                item = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if item is _SENTINEL:
                self.close()
                raise StopIteration
            if isinstance(item, BaseException):
                self.close()
                raise item
            return item
        raise StopIteration

    def close(self, join_timeout: float = 5.0) -> None:
        self._stop.set()
        if getattr(self, "_queue", None) is None:
            return
        # A streaming source with its own lifecycle (shard workers, file
        # handles) gets told to stop FIRST: a worker blocked inside the
        # source's __next__ can't see the stop flag, so without this the
        # bounded join below would always burn its full timeout on a
        # stalled shard. Generators refuse cross-thread close() while
        # executing — that (or any source-side failure) must not break
        # teardown, so errors are swallowed and the bounded join still
        # guarantees close() returns.
        src_close = getattr(getattr(self, "_source", None), "close", None)
        if src_close is not None:
            try:
                src_close()
            except Exception:
                pass
        # Drain so a blocked worker put() can observe the stop flag.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        # Join the worker (bounded): teardown must not leave a thread racing
        # a live device_put against e.g. pytest's fixture cleanup or the
        # preemption drain. The worker polls the stop flag every 0.1s, so a
        # healthy thread exits well inside the timeout; a wedged device_put
        # is abandoned as a daemon rather than hanging the process.
        t = getattr(self, "_thread", None)
        if t is not None and t is not threading.current_thread() and t.is_alive():
            t.join(timeout=join_timeout)
        # The worker may have completed one last put() between the first
        # drain and its stop-flag check — including the case where it
        # already exited before the liveness check above — so the final
        # drain is unconditional: no device buffers may linger in the dead
        # queue.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        self.close(join_timeout=1.0)


def prefetch_to_device(
    batches: Iterable,
    place_fn: Callable[[Any], Any],
    host_stats_fn: Callable[[Any], Any] | None = None,
    depth: int = 2,
) -> DevicePrefetcher:
    """Convenience constructor; see `DevicePrefetcher`."""
    return DevicePrefetcher(batches, place_fn, host_stats_fn=host_stats_fn, depth=depth)
