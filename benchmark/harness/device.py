"""The device under test: it has to be a TPU, and its peaks are in one table."""

from __future__ import annotations

# Published peaks per chip, keyed by ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
# A device that is not in the table is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> dict:
    """The ``device`` object of the result line; raises `NoChip` off-TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the benchmark runs on a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise NoChip(f"no peaks on record for device kind {kind!r}")
    return {"platform": "tpu", "kind": kind, "count": chips}


def peaks(kind: str) -> dict:
    return DEVICE_PEAKS[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes held on the fullest of the chips used: the peak of the
    live arrays (``peak_bytes_in_use``) plus the peak of what the runtime
    reserved for compiled programs' temporaries (``peak_bytes_reserved``).
    On this runtime a step's activations live in that reservation and never
    show in ``peak_bytes_in_use`` (my chip run 2, PR 25: 2.34 GB in use beside
    4.60 GB reserved for the CI step)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        held = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        peak = max(peak, held)
    return peak
