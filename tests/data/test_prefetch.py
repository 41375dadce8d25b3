"""Tests for the asynchronous host→device input pipeline.

Pins the contracts VERDICT r02 #2 requires: batches arrive in order and
bitwise-equal to the synchronous path, host stats are computed without device
syncs, the rng-exact ``skip_batches`` resume contract survives prefetching,
worker exceptions surface at the consumer, and closing mid-stream stops the
worker thread.
"""

import threading
import time

import jax
import numpy as np
import pytest

from eventstreamgpt_tpu.data.prefetch import DevicePrefetcher, prefetch_to_device


def _tree_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


class TestDevicePrefetcher:
    def test_order_and_equality(self):
        batches = [{"x": np.full((4, 4), i)} for i in range(10)]
        out = list(prefetch_to_device(iter(batches), jax.device_put))
        assert len(out) == 10
        for i, (b, stats) in enumerate(out):
            assert stats is None
            assert np.array_equal(np.asarray(b["x"]), batches[i]["x"])

    def test_host_stats(self):
        batches = [{"x": np.full((2,), i)} for i in range(5)]
        out = list(
            prefetch_to_device(iter(batches), jax.device_put, host_stats_fn=lambda b: int(b["x"].sum()))
        )
        assert [s for _, s in out] == [0, 2, 4, 6, 8]

    def test_exception_propagates(self):
        def gen():
            yield {"x": np.zeros(2)}
            raise RuntimeError("boom in collation")

        it = prefetch_to_device(gen(), jax.device_put)
        next(it)
        with pytest.raises(RuntimeError, match="boom in collation"):
            next(it)

    def test_close_stops_worker(self):
        started = threading.Event()

        def gen():
            for i in range(10_000):
                started.set()
                yield {"x": np.zeros(2)}

        it = prefetch_to_device(gen(), jax.device_put, depth=2)
        started.wait(timeout=5)
        next(it)
        it.close()
        # The daemon worker must observe the stop flag and exit.
        deadline = time.monotonic() + 5
        while it._thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not it._thread.is_alive()

    def test_close_joins_worker_synchronously(self):
        """close() returns only after the worker thread is joined: teardown
        (fixture cleanup, preemption drain, pytest exit) must never race a
        live device_put from a leaked thread."""

        def gen():
            for i in range(10_000):
                yield {"x": np.zeros(2)}
                time.sleep(0.001)  # keep the worker mid-stream at close time

        it = prefetch_to_device(gen(), jax.device_put, depth=2)
        next(it)
        assert it._thread.is_alive()
        it.close()
        # No polling: the bounded join inside close() already reaped it.
        assert not it._thread.is_alive()
        # Idempotent, including after the thread is gone.
        it.close()

    def test_close_drains_late_put(self):
        """A put() racing between close()'s drain and the worker's stop-flag
        check must not strand device buffers in the dead queue."""
        release = threading.Event()

        def gen():
            yield {"x": np.zeros(2)}
            release.wait(timeout=5)  # hold the worker mid-iteration
            yield {"x": np.ones(2)}

        it = prefetch_to_device(gen(), jax.device_put, depth=2)
        next(it)
        release.set()
        it.close()
        assert not it._thread.is_alive()
        assert it._queue.empty()

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            DevicePrefetcher([], jax.device_put, depth=0)

    def test_close_bounded_with_stalled_shard_source(self):
        """r11 streaming-source contract: a slow/raising shard worker must
        not hang close(). The worker thread is blocked inside the source's
        __next__ (it cannot see the stop flag), so close() must (a) tell a
        closeable source to stop, and (b) return within its bounded join
        either way."""
        stalled = threading.Event()
        closed = threading.Event()

        class StalledShardStream:
            """A streaming source whose next shard never arrives."""

            def __iter__(self):
                return self

            def __next__(self):
                stalled.set()
                # Released only by close() — a stalled shard worker.
                closed.wait(timeout=30)
                raise StopIteration

            def close(self):
                closed.set()

        it = prefetch_to_device(StalledShardStream(), jax.device_put, depth=2)
        assert stalled.wait(timeout=5)
        t0 = time.monotonic()
        it.close(join_timeout=5.0)
        assert time.monotonic() - t0 < 5.0, "close() burned its full join timeout"
        assert closed.is_set(), "close() must propagate to the streaming source"
        deadline = time.monotonic() + 5
        while it._thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not it._thread.is_alive()

    def test_close_bounded_when_source_close_raises(self):
        """A source whose close() itself fails (e.g. a generator mid-frame
        raising ValueError) must not break teardown; the bounded join still
        returns."""
        entered = threading.Event()

        class BadCloseSource:
            def __iter__(self):
                return self

            def __next__(self):
                entered.set()
                time.sleep(0.05)
                return {"x": np.zeros(2)}

            def close(self):
                raise ValueError("already executing")

        it = prefetch_to_device(BadCloseSource(), jax.device_put, depth=2)
        assert entered.wait(timeout=5)
        it.close(join_timeout=5.0)  # must not raise
        assert it._queue.empty()

    def test_skip_batches_resume_exact_through_prefetch(self, tmp_path):
        """Prefetched batch N+1.. equals an uninterrupted epoch's batches."""
        import shutil
        from pathlib import Path

        from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig

        from tests import SAMPLE_DIR as ref
        for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
            shutil.copy(ref / name, tmp_path / name)
        shutil.copytree(ref / "DL_reps", tmp_path / "DL_reps")
        ds = JaxDataset(PytorchDatasetConfig(save_dir=tmp_path, max_seq_len=8), "tuning")

        full = [b for b, _ in prefetch_to_device(ds.batches(2, shuffle=True, seed=7), jax.device_put)]
        resumed = [
            b
            for b, _ in prefetch_to_device(
                ds.batches(2, shuffle=True, seed=7, skip_batches=2), jax.device_put
            )
        ]
        assert len(resumed) == len(full) - 2
        for a, b in zip(full[2:], resumed):
            assert _tree_equal(a, b)
