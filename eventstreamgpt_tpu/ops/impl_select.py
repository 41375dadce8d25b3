"""Shared implementation selection for the package's Pallas kernels.

Every Pallas op in ``ops/`` (``vocab_gather``, ``dep_graph_attention``,
``fused_categorical``) exposes the same ``impl`` vocabulary:

* ``None`` / ``"auto"`` — the Pallas kernel on TPU backends, the XLA
  formulation everywhere else (traces stay portable: a checkpoint compiled
  on a CPU test mesh never requires Mosaic);
* ``"pallas"`` — the compiled kernel (TPU only);
* ``"pallas_interpret"`` — the same kernel code in Pallas interpreter
  mode, any backend — how CPU CI exercises every kernel in tier-1;
* ``"xla"`` — the pure-XLA fallback formulation.

Before this round each op resolved ``auto`` privately; the logic now lives
here so one environment override retargets *all* kernels at once:

    ESGPT_PALLAS_IMPL=pallas_interpret python -m pytest ...

forces every auto-selected op onto the named impl (explicit per-call
``impl`` arguments still win — the override only replaces the ``auto``
default). The variable is read per call, not cached at import, so test
fixtures can monkeypatch it.
"""

from __future__ import annotations

import os

ENV_VAR = "ESGPT_PALLAS_IMPL"
IMPLS = ("pallas", "pallas_interpret", "xla")

LANE = 128


def round_up(x: int, m: int) -> int:
    """The smallest multiple of ``m`` >= ``x`` (tile padding)."""
    return (x + m - 1) // m * m


def resolve_impl(impl: str | None, op_name: str = "pallas op") -> str:
    """Resolves an ``impl`` argument to one of `IMPLS`.

    ``None``/``"auto"`` consults ``ESGPT_PALLAS_IMPL`` first, then picks
    ``"pallas"`` on TPU backends and ``"xla"`` elsewhere. Anything else is
    validated and passed through.
    """
    if impl in (None, "auto"):
        impl = os.environ.get(ENV_VAR) or None
    if impl in (None, "auto"):
        import jax

        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in IMPLS:
        raise ValueError(
            f"unknown {op_name} impl {impl!r}; expected one of {IMPLS} "
            f"(or 'auto'/None, optionally via ${ENV_VAR})"
        )
    return impl
