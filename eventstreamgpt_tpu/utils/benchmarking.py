"""Device timing helpers: the readback-subtraction protocol.

JAX dispatches asynchronously, so a timing has to end in a barrier. Two
exist: ``jax.block_until_ready`` and a **host readback** of computed data
(``float(x)`` / ``np.asarray(x)``). This module was written around the
second, for a backend on which the first returned at dispatch; the protocol
it implements:

1. ``readback_echo_ms`` — measure the constant readback round trip.
2. ``sustained_step_ms`` — dispatch ``k`` dependent steps back-to-back,
   force ONE readback at the end, subtract the round trip, divide by ``k``;
   size ``k`` from a calibration run so residual jitter is amortized to a
   few percent; repeat and take the minimum.

What the chip tool's machine shows (PR 22, ``chip_smoke.py`` timing phase,
one TPU v5e): ``block_until_ready`` DOES wait for the computation there —
32 chained 4096x4096 bf16 matmuls blocked in 23.5 ms (187 TFLOP/s, at the
chip's peak) and a one-element readback after it added 2 ms. So both
barriers are sound on that machine and the subtraction is no longer needed;
deleting it is the benchmark PR's job (ROADMAP D1), not a silent change
here. ``require_tpu`` / ``DEVICE_PEAKS`` are the measuring paths' start
gate: a timing taken on another backend is not a device metric.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np

__all__ = [
    "DEVICE_PEAKS",
    "require_tpu",
    "dispatch_echo_ms",
    "readback_echo_ms",
    "drain",
    "sustained_step_ms",
    "wait_for_quiet",
]

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``. One
# table for every measuring path; a device that is not in it is an error,
# never a default. Source: Google Cloud documentation, "TPU v5e" system
# architecture page (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def require_tpu() -> dict:
    """The measuring paths' start gate: a TPU with a known peak, or raise.

    Prints and returns ``{"platform", "kind", "count", "bf16_flops_per_s",
    "hbm_bytes_per_s"}`` for the attached device. Raises ``RuntimeError``
    when JAX found no TPU (a timing taken on the CPU backend is not a
    device metric) and ``KeyError`` when the ``device_kind`` has no entry
    in `DEVICE_PEAKS`.
    """
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this is a device-measurement path and JAX found platform "
            f"{dev.platform!r} ({dev.device_kind!r}); it runs only on a TPU "
            "and does not fall back to another backend."
        )
    if dev.device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device_kind {dev.device_kind!r} in "
            "utils.benchmarking.DEVICE_PEAKS; add it with its source."
        )
    info = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        **DEVICE_PEAKS[dev.device_kind],
    }
    print(f"device: {info}", flush=True)
    return info


# One definition of "quiet" for every measurement artifact (bench.py,
# scripts/probe_scale.py): quiet dispatch echo is 0.02-1 ms; sustained
# contention windows measure 10-130+ ms.
QUIET_THRESHOLD_MS = 2.0
QUIET_RETRIES = 2
QUIET_WAIT_S = 20.0


def wait_for_quiet(
    threshold_ms: float = QUIET_THRESHOLD_MS,
    retries: int = QUIET_RETRIES,
    wait_s: float = QUIET_WAIT_S,
) -> tuple[float, bool]:
    """Retries the dispatch echo until quiet (or retries exhausted).

    Returns ``(echo_ms, contended)`` — the final pre-flight echo and
    whether it still exceeded the threshold.
    """
    echo = dispatch_echo_ms()
    for _ in range(retries):
        if echo <= threshold_ms:
            break
        time.sleep(wait_s)
        echo = dispatch_echo_ms()
    return echo, bool(echo > threshold_ms)


def drain(x) -> float:
    """Forces completion of ``x``'s computation via a true host readback.

    Returns the scalar-sum payload (so callers can also use it as a value
    barrier). See the module docstring for how this relates to
    ``jax.block_until_ready``.
    """
    import jax.numpy as jnp

    return float(jnp.asarray(x).sum())


def dispatch_echo_ms(n: int = 20) -> float:
    """Min-of-n *dispatch* round trip (fake-block echo): a contention gate.

    A host-side dispatch-latency reading for a tiny program; it is not a
    measurement of any workload's compute time.
    """
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready(f(x))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))  # graftcheck: allow GC001 -- measuring the sync latency is the point
        best = min(best, time.perf_counter() - t0)
    return 1000.0 * best


def readback_echo_ms(n: int = 5) -> float:
    """Min-of-n true data-plane round trip: dispatch + compute + readback of
    a tiny program. The constant ``sustained_step_ms`` subtracts."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((8, 8), jnp.float32)
    float(f(x))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        float(f(x))  # graftcheck: allow GC001 -- measuring the readback latency is the point
        best = min(best, time.perf_counter() - t0)
    return 1000.0 * best


def sustained_step_ms(
    step_fn: Callable,
    state: Any,
    batch: Any,
    rng,
    target_window_ms: float = 3000.0,
    k_min: int = 8,
    k_max: int = 512,
    repeats: int = 2,
) -> tuple[float, Any, dict]:
    """Sustained per-step time of ``step_fn(state, batch, rng) -> (state, loss)``.

    Dispatches ``k`` dependent steps (the returned state feeds the next
    step, so the device cannot overlap them), forces one readback, and
    subtracts the measured readback RTT. ``k`` is sized so the measured
    window is ~``target_window_ms`` — large enough that RTT jitter
    (~±40 ms observed) contributes only a few percent. The minimum over
    ``repeats`` windows is returned (contention can only inflate a window).

    Returns ``(step_ms, state, info)`` where info carries the chosen ``k``,
    the readback RTT, and each window's raw estimate.
    """

    def run(k: int, st):
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            st, loss = step_fn(st, batch, rng)
        drain(loss)
        return 1000.0 * (time.perf_counter() - t0), st

    rtt = readback_echo_ms()
    # Calibration window: small k; its own bias (rtt/k_min) only affects
    # the k chosen, not the reported number.
    t_cal, state = run(k_min, state)
    est = max((t_cal - rtt) / k_min, 0.01)
    k = int(min(max(target_window_ms / est, k_min), k_max))

    estimates = []
    for _ in range(repeats):
        rtt_i = readback_echo_ms()
        t, state = run(k, state)
        estimates.append(max(t - rtt_i, 0.0) / k)
    info = {
        "k": k,
        "readback_rtt_ms": round(rtt, 2),
        "window_estimates_ms": [round(e, 4) for e in estimates],
    }
    return min(estimates), state, info
