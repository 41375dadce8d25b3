"""Small shared helpers: count/proportion cutoffs, seeding, phase timing.

TPU-native rebuild of scattered utilities from
``/root/reference/EventStream/utils.py:24-121`` and the external ``ml-mixins``
package the reference depends on (``SeedableMixin``, ``TimeableMixin``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import defaultdict
from contextlib import contextmanager
from functools import wraps
from pathlib import Path
from typing import Any, Callable, Union

import numpy as np

COUNT_OR_PROPORTION = Union[int, float]


def atomic_write_json(fp: Path | str, obj: Any, **json_kwargs: Any) -> None:
    """Atomically publishes ``obj`` as JSON at ``fp`` (tmp + fsync + rename).

    The one durable-sidecar writer (checkpoint metadata, integrity
    manifests, divergence diagnostics): a crash mid-write must never leave a
    truncated JSON file where a reader expects a valid one, and a crash
    right after must still find the bytes on disk — hence the fsync before
    the rename. The tmp name is per-process unique so concurrent writers on
    a shared filesystem (pod-scale multi-host runs) cannot truncate each
    other's in-flight tmp and publish a torn file through the rename.
    """
    fp = Path(fp)
    tmp = fp.with_name(f"{fp.name}.{os.getpid()}.tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f, **json_kwargs)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, fp)
    # The rename itself lives in the directory entry: without fsyncing the
    # parent, a power loss can make the just-published file vanish (and a
    # vanished integrity manifest silently downgrades verification).
    try:
        dirfd = os.open(fp.parent, os.O_RDONLY)
    except OSError:  # platforms/filesystems without directory opens
        return
    try:
        os.fsync(dirfd)
    except OSError:
        pass
    finally:
        os.close(dirfd)


def count_or_proportion(N: int | None, cnt_or_prop: COUNT_OR_PROPORTION) -> int:
    """Resolves a cutoff that may be an absolute count or a fraction of a whole.

    Equivalent contract to ``/root/reference/EventStream/utils.py:24``.

    Examples:
        >>> count_or_proportion(100, 0.1)
        10
        >>> count_or_proportion(None, 11)
        11
        >>> count_or_proportion(100, 0.116)
        12
    """
    match cnt_or_prop:
        case bool():
            raise TypeError(f"{cnt_or_prop} must be a positive integer or a float between 0 or 1")
        case int() if cnt_or_prop > 0:
            return cnt_or_prop
        case int():
            raise ValueError(f"{cnt_or_prop} must be positive if it is an integer")
        case float() if 0 < cnt_or_prop < 1:
            if not isinstance(N, int):
                raise TypeError(f"{N} must be an integer when cnt_or_prop is a float!")
            return int(round(cnt_or_prop * N))
        case float():
            raise ValueError(f"{cnt_or_prop} must be between 0 and 1 if it is a float")
        case _:
            raise TypeError(f"{cnt_or_prop} must be a positive integer or a float between 0 or 1")


def lt_count_or_proportion(
    N_obs: int, cnt_or_prop: COUNT_OR_PROPORTION | None, N_total: int | None = None
) -> bool:
    """True iff ``N_obs`` falls below the resolved cutoff; ``None`` cutoff → False.

    Examples:
        >>> lt_count_or_proportion(10, 0.1, 100)
        False
        >>> lt_count_or_proportion(10, 0.11, 100)
        True
        >>> lt_count_or_proportion(10, None)
        False
    """
    if cnt_or_prop is None:
        return False
    return N_obs < count_or_proportion(N_total, cnt_or_prop)


def num_initial_spaces(s: str) -> int:
    """Number of leading spaces of ``s``.

    Examples:
        >>> num_initial_spaces("  a")
        2
    """
    return len(s) - len(s.lstrip(" "))


class SeedableMixin:
    """Deterministic seeding support for host-side (numpy) randomness.

    Replaces the external ``ml-mixins`` ``SeedableMixin`` the reference uses
    (imported at ``/root/reference/EventStream/data/dataset_base.py:21``).
    Device-side randomness in this framework always flows through explicit
    ``jax.random`` keys instead.
    """

    def _seed(self, seed: int | None = None, key: str | None = None) -> int:
        if seed is None:
            seed = int(np.random.SeedSequence().entropy % (2**31))
        self._past_seeds = getattr(self, "_past_seeds", [])
        self._past_seeds.append((key, seed))
        np.random.seed(seed)
        return seed

    @staticmethod
    def WithSeed(fn: Callable) -> Callable:
        """Decorator: seeds numpy from the ``seed`` kwarg before running ``fn``."""

        @wraps(fn)
        def wrapped(self, *args, seed: int | None = None, **kwargs):
            self._seed(seed=seed, key=fn.__name__)
            return fn(self, *args, **kwargs)

        return wrapped


class TimeableMixin:
    """Accumulates wall-clock durations for named phases.

    Replaces the external ``ml-mixins`` ``TimeableMixin`` (used pervasively in
    the reference ETL, e.g. ``dataset_base.py:606-1062``); kept first-class per
    SURVEY.md §5.1 so every pipeline phase stays measurable.
    """

    @property
    def _timings(self) -> dict[str, list[float]]:
        if not hasattr(self, "_timings_dict"):
            self._timings_dict = defaultdict(list)
        return self._timings_dict

    @contextmanager
    def _time_as(self, key: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self._timings[key].append(time.perf_counter() - start)

    @staticmethod
    def TimeAs(fn: Callable) -> Callable:
        """Decorator form of `_time_as`, keyed on the function name."""

        @wraps(fn)
        def wrapped(self, *args, **kwargs):
            with self._time_as(fn.__name__):
                return fn(self, *args, **kwargs)

        return wrapped

    def _duration_stats(self) -> dict[str, tuple[float, int]]:
        """Returns ``{phase: (total_seconds, n_calls)}`` for all timed phases."""
        return {k: (sum(v), len(v)) for k, v in self._timings.items()}

    def timing_summary(self) -> str:
        """Formatted per-phase wall-clock table, longest phases first.

        SURVEY §5.1: the reference decorates every ETL phase but never reports
        the timings; this surfaces them (printed by scripts/build_dataset).
        """
        stats = sorted(self._duration_stats().items(), key=lambda kv: -kv[1][0])
        if not stats:
            return "(no timed phases)"
        width = max(len(k) for k, _ in stats)
        lines = [f"{'phase':<{width}}  total_s  calls"]
        for k, (total, n) in stats:
            lines.append(f"{k:<{width}}  {total:7.2f}  {n:5d}")
        return "\n".join(lines)


class ImportClock:
    """``startup/import`` of the host record (``utils/scopes.py``) for a package
    whose ``__init__`` makes one as its first statement and calls `done` as its
    last: the start is taken here, where JAX need not be in yet, so that JAX's
    own import lies inside the span, and what the package pulls in nests in it."""

    def __init__(self):
        self.began = time.perf_counter()

    def done(self) -> None:
        from .scopes import record  # brings JAX in where the package has not

        record("startup/import", self.began, id="startup")


def to_dict_flat(obj: Any, prefix: str = "") -> dict[str, Any]:
    """Flattens a (possibly nested dataclass/dict) object into dotted keys.

    Used by the sweep launcher to map nested configs onto flat W&B-style
    parameter names (reference analog: ``scripts/launch_wandb_hp_sweep.py:24``).

    Examples:
        >>> to_dict_flat({"a": {"b": 1}, "c": 2})
        {'a.b': 1, 'c': 2}
    """
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        obj = {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}
    out: dict[str, Any] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            kk = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, dict) or (dataclasses.is_dataclass(v) and not isinstance(v, type)):
                out.update(to_dict_flat(v, kk))
            else:
                out[kk] = v
        return out
    out[prefix] = obj
    return out
