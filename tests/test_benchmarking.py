"""Timing utilities (`utils/benchmarking.py`).

On CPU block/readback agree exactly; the tests pin the protocol's mechanics
— true-readback barriers, round-trip subtraction, calibration-sized windows
— and the measuring paths' start gate (`require_tpu`).
"""

import jax
import jax.numpy as jnp
import numpy as np

from eventstreamgpt_tpu.utils.benchmarking import (
    dispatch_echo_ms,
    drain,
    readback_echo_ms,
    sustained_step_ms,
)


def test_echoes_positive_and_small_on_cpu():
    d = dispatch_echo_ms(n=3)
    r = readback_echo_ms(n=3)
    assert 0 < d < 1000
    assert 0 < r < 1000


def test_drain_forces_value():
    x = jnp.arange(4.0)
    assert drain(x) == 6.0


def test_sustained_step_ms_measures_a_real_step():
    """The sustained estimate approximates the true per-step cost of a
    deliberately non-trivial jitted step (CPU: block semantics are exact,
    so wall-clock per-step is a valid cross-check)."""

    @jax.jit
    def step(state, batch, rng):
        x = state
        for _ in range(8):
            x = jnp.tanh(x @ batch)
        return x, x.sum()

    batch = jnp.eye(256) * 0.5
    state = jnp.ones((256, 256))
    rng = jax.random.PRNGKey(0)
    state, loss = step(state, batch, rng)
    drain(loss)

    import time

    t0 = time.perf_counter()
    s2, l2 = state, None
    for _ in range(32):
        s2, l2 = step(s2, batch, rng)
    drain(l2)
    truth_ms = (time.perf_counter() - t0) / 32 * 1000.0

    est_ms, _, info = sustained_step_ms(step, state, batch, rng, target_window_ms=300.0)
    assert est_ms > 0
    assert info["k"] >= 8
    assert len(info["window_estimates_ms"]) == 2
    # Generous envelope: scheduling noise on a 1-core host.
    assert est_ms < truth_ms * 3 + 1.0
    assert est_ms > truth_ms / 3 - 1.0


def test_sustained_step_threads_state():
    """The returned state reflects all executed steps (donation-safe loop)."""

    @jax.jit
    def step(state, batch, rng):
        return state + 1, (state + 1).sum()

    state = jnp.zeros(())
    out_ms, out_state, info = sustained_step_ms(
        step, state, None, None, target_window_ms=1.0, k_min=4
    )
    # k_min calibration steps + 2 windows of k steps each.
    assert float(out_state) == 4 + 2 * info["k"]
    assert np.isfinite(out_ms)


class TestBenchTailCapture:
    """The driver keeps only the FINAL 2000 characters of bench stdout; the
    headline keys must therefore (a) sit after the headline-block marker in
    the print dict and (b) render small enough that the whole headline
    block fits the window. Statically checked against bench.py's source so
    a reordering or a bloated tail fails in tier-1, not in a lost artifact."""

    HEADLINE_MARKER = "---- headline block"
    # Every key the r09/r10 acceptance lists name, plus the historical
    # headline keys whose position the r06-r08 rounds already relied on.
    # The r10 width-ladder / fsdp / scan-flatness keys are pinned here so
    # the scale-up verdicts (per-rung step ms + MFU, the 4096 rung's
    # FSDP-only footprint, depth-flat compile ratios, the pod-scale
    # prediction) always land inside the driver's 2000-char tail capture.
    REQUIRED_TAIL_KEYS = [
        "width1024_remat_ab_ms",
        "width_ladder_step_ms",
        "width_ladder_mfu",
        "width_ladder_pod_step_ms_pred",
        "fsdp_width4096_state_gb",
        "scan_depth_flat",
        "na_fused_ab_probe_ms",
        "dep_graph_pallas_ab_ms",
        "engine_events_per_sec_per_chip",
        "sampling_fused_ab_ms",
        "kvq_engine_events_per_sec_per_chip",
        "kvq_slots_per_chip_ratio",
        # r20 composition/megakernel verdicts: the never-run quantized-NA
        # decode A/B ratio (per-rung capacity detail above the marker) and
        # the decode-megakernel A/B whose winner names the production
        # default `decode_step_impl='auto'` resolves to (parity gated in
        # tests/test_decode_megakernel.py).
        "kvq_na_vs_float_ratio",
        "decode_megakernel_ab_ms",
        "decode_step_impl_winner",
        # r13 speculative-decoding verdicts: draft-propose/one-pass-verify
        # vs one-event-per-forward decode on identical offline requests
        # (correctness pinned by greedy parity + the per-head chi-square in
        # tests/test_spec.py; these are the measured speed/acceptance
        # numbers), plus the Poisson-replay p95 on the engine arm's trace.
        "spec_engine_events_per_sec_per_chip",
        "spec_vs_engine_ratio",
        "spec_acceptance_rate",
        "spec_p95_latency_ms",
        "service_p95_latency_ms",
        # r12 serving-fleet verdicts: the 2-service router replay of the
        # service Poisson trace with a mid-trace hot checkpoint swap
        # (bit-exactness + zero-drop pinned in tier-1 / the fleet chunk);
        # swap_dropped_requests must render 0.
        "fleet_p95_latency_ms",
        "fleet_vs_service_p95_ratio",
        "swap_dropped_requests",
        # r15 fault-tolerant-serving verdicts: the same fleet trace with
        # one replica killed at the midpoint chunk — eviction + bound-key
        # session replay on the survivor (bit-identity and the zero-drop
        # scoreboard pinned in tests/test_serving_faults.py); these are
        # the measured degradation cost.
        "fleet_degraded_p95_latency_ms",
        "fleet_evicted_sessions_replayed",
        # r11 streaming-ETL A/B verdicts: the parallel host pipeline vs the
        # single-process r05 baseline on identical work (bit-identical
        # artifacts pinned in tier-1).
        "etl_parallel_events_per_sec",
        "etl_vs_serial_ratio",
        "zeroshot_auroc",
        # r16 paged-CoW fork verdicts: the zero-shot branching workload
        # through fork() vs per-(subject, sample) requests on identical
        # paged engines (bitwise-equal outputs pinned in
        # tests/test_paged_cache.py) — the shared-prefill speedup, the
        # admission-dedup scoreboard, and the measured capacity multiplier
        # from CoW prefix sharing.
        "zeroshot_fork_speedup",
        "paged_effective_slots_ratio",
        "fork_branches_per_prefill",
        "value",
    ]

    def _tail_keys(self):
        import pathlib
        import re

        src = (pathlib.Path(__file__).parent.parent / "bench.py").read_text()
        marker = src.index(self.HEADLINE_MARKER)
        tail_src = src[marker:]
        return re.findall(r'^\s+"([a-z0-9_]+)":', tail_src, flags=re.M)

    def test_required_keys_sit_in_the_headline_block_in_order(self):
        keys = self._tail_keys()
        positions = []
        for k in self.REQUIRED_TAIL_KEYS:
            assert k in keys, f"headline key {k!r} fell out of the tail block"
            positions.append(keys.index(k))
        assert positions == sorted(positions), "headline keys reordered"
        assert keys[-1] == "value", "the driver's metric key must print last"

    def test_headline_block_fits_the_2000_char_capture(self):
        """Render the tail with representative value widths: scalars ~8
        chars, the A/B dicts ~3 arms of rounded ms, rate lists ~3 epochs.
        The estimate must clear the window with margin for real values."""
        import json

        def fake_value(key):
            if key == "na_fused_ab_probe_ms":  # 4 arms since r09
                return {
                    "fused_narrow_default": 9999.99,
                    "unfused_attention": 9999.99,
                    "full_plane_heads": 9999.99,
                    "dep_graph_xla_fused": 9999.99,
                }
            if key.startswith("width_ladder_"):  # one entry per ladder rung
                return {"1024": 99999.99, "2048": 99999.99, "4096": 99999.99}
            if key == "scan_depth_flat":  # d8/d2 ratios, scan vs unrolled
                return {
                    "scan_hlo": 99.99,
                    "unrolled_hlo": 99.99,
                    "scan_compile": 99.99,
                    "unrolled_compile": 99.99,
                }
            if key.endswith("_ab_ms"):
                return {"first_arm_name_here": 9999.99, "second_arm_name": 9999.99}
            if key.endswith("_rates"):
                return [99999.9, 99999.9, 99999.9]
            if key in ("metric", "unit"):
                return "pretrain_events_per_sec_per_chip"
            if key.endswith(("_policy", "_winner")):
                return "save_attention"
            return 99999.999

        # The regex also catches the A/B dicts' inner arm keys; drop them
        # (their width is already counted through fake_value's dicts).
        keys = [k for k in self._tail_keys() if not k.endswith(("_arm", "_default", "_fused", "_tail", "_heads", "_attention"))]
        rendered = json.dumps({k: fake_value(k) for k in keys})
        assert len(rendered) < 1900, (
            f"headline block renders to ~{len(rendered)} chars; the driver "
            "captures 2000 — move detail keys above the marker"
        )


def test_require_tpu_refuses_cpu_backend():
    """The measuring paths' start gate: no fallback to another backend."""
    import pytest

    from eventstreamgpt_tpu.utils.benchmarking import DEVICE_PEAKS, require_tpu

    with pytest.raises(RuntimeError, match="does not fall back"):
        require_tpu()
    assert DEVICE_PEAKS["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


def test_compile_cache_is_one_fixed_place(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` wins untouched; otherwise one fixed path
    inside the checkout, never a tempfile/pid/time."""
    from pathlib import Path

    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set in code
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        expected = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert configure_compile_cache() == configure_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
