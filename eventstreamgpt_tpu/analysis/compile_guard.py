"""Recompilation sentinel: fail fast when a step function recompiles.

A mid-epoch recompile is the silent TPU killer: a drifting batch shape or a
weak-typed constant retraces the step, XLA spends tens of seconds per
recompile, and the run "works" at a tenth of its throughput. The compile is
a static event, so it can be *gated*, not profiled:

* ``CompileGuard(watch=[step_fn])`` snapshots each watched jitted function's
  trace-cache size (``PjitFunction._cache_size``) when armed and raises
  `RecompileError` from :meth:`check` / ``__exit__`` if any watched function
  grew a new executable. Per-function and noise-free: eager helper ops
  compiling elsewhere don't trip it.
* ``CompileGuard()`` (no watch) falls back to the process-global count of
  backend compiles in the program's host record (``utils/scopes.py``, whose
  listener on JAX's compile events is the process's one) — coarser (any
  compile in the window trips it) but works for "this region must dispatch
  only cached programs" assertions in tests.

Used by ``training/pretrain.py`` (armed from the second epoch, checked after
every full-shape dispatch; ``trainer_config.guard_recompiles=False`` opts
out) and by ``tests/training/test_compile_guard.py`` to pin the
compile-exactly-once contract across epoch boundaries and mid-epoch resume.
"""

from __future__ import annotations

import warnings
from typing import Callable, Sequence

__all__ = ["CompileGuard", "RecompileError", "backend_compile_count"]


def backend_compile_count() -> int:
    """Backend compiles observed process-wide: the ``compile/backend`` spans
    of the program's host record, fed by the process's one listener on JAX's
    compile events (``utils/scopes.py``)."""
    from ..utils import scopes

    return scopes.compile_totals()["backend"]


def _cache_size(fn) -> int | None:
    getter = getattr(fn, "_cache_size", None)
    if getter is None:
        return None
    try:
        return int(getter())
    except Exception:
        return None


class RecompileError(RuntimeError):
    """A guarded region compiled more executables than its budget allows."""


class CompileGuard:
    """Context manager / armable sentinel over jit compile activity.

    Args:
        watch: jitted callables whose trace caches are monitored. Empty ⇒
            fall back to the process-global backend-compile counter.
        max_compiles: new executables tolerated inside the guarded region.
        label: names the guarded region in the error message.
        on_violation: ``"raise"`` (default) or ``"warn"``.
    """

    def __init__(
        self,
        watch: Sequence[Callable] = (),
        max_compiles: int = 0,
        label: str = "guarded region",
        on_violation: str = "raise",
    ):
        if on_violation not in ("raise", "warn"):
            raise ValueError(f"on_violation must be 'raise' or 'warn', got {on_violation!r}")
        self.watch = list(watch)
        self.max_compiles = int(max_compiles)
        self.label = label
        self.on_violation = on_violation
        self.armed = False
        self._baseline_caches: list[int | None] = []
        self._baseline_global = 0
        # Watched fns without a cache-size probe (API drift) degrade to the
        # global counter rather than silently guarding nothing.
        self._use_global = not self.watch or any(
            _cache_size(fn) is None for fn in self.watch
        )

    # ------------------------------------------------------------- lifecycle
    def arm(self) -> "CompileGuard":
        """Snapshots compile state; subsequent ``check()`` diffs against it."""
        if self._use_global:
            self._baseline_global = backend_compile_count()
        else:
            self._baseline_caches = [_cache_size(fn) for fn in self.watch]
        self.armed = True
        return self

    @property
    def compiles(self) -> int:
        """New executables since ``arm()`` (0 when unarmed)."""
        if not self.armed:
            return 0
        if self._use_global:
            return backend_compile_count() - self._baseline_global
        total = 0
        for fn, base in zip(self.watch, self._baseline_caches):
            now = _cache_size(fn)
            if now is not None and base is not None:
                total += max(now - base, 0)
        return total

    def check(self) -> None:
        """Raises (or warns) if the region exceeded its compile budget."""
        if not self.armed:
            return
        n = self.compiles
        if n > self.max_compiles:
            what = (
                ", ".join(getattr(f, "__name__", str(f)) for f in self.watch)
                if self.watch and not self._use_global
                else "the process"
            )
            msg = (
                f"{self.label}: {n} new compile(s) of {what} "
                f"(budget {self.max_compiles}). A steady-state step recompiled — "
                "look for drifting batch shapes, weak-typed constants, or python "
                "scalars captured as tracers."
            )
            if self.on_violation == "warn":
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
                # re-baseline so one drift doesn't warn on every later check
                self.arm()
            else:
                raise RecompileError(msg)

    def disarm(self) -> None:
        self.armed = False

    def __enter__(self) -> "CompileGuard":
        return self.arm()

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.check()
        self.disarm()
