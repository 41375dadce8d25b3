"""CPU rehearsal of ``chip_smoke.py`` (the ``on-chip-measurement`` guide's
first rehearsal): every phase function at a tiny size with the Pallas
kernels in interpret mode, and the refusal to print ``ok`` off the chip.

This finds wrong paths, arguments and control flow before a chip call is
spent; it says nothing about the chip (tests/test_chip_compile.py asks the
chip's compiler, ``chiprun -- python chip_smoke.py`` runs on it).
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.pallas

TINY = chip_smoke.Sizes(
    n_train=48, n_tuning=8, n_event_types=6, n_labs=20, n_meds=8, mean_seq_len=24,
    data_max_seq_len=32, hidden=32, layers=2, heads=2, packed_seq_len=64, batch=4,
    chunk=2, steps=6, preempt_at=2, n_slots=2, serve_max_len=16, decode_chunk=2,
    requests=((4, 3), (6, 2), (8, 8)), na_hidden=32, na_heads=2, na_layers=2,
    na_batch=4, na_steps=4, gather_rows=16, sample_rows=8,
)


@pytest.fixture(scope="module")
def interpret_kernels():
    """Auto-resolved kernels run in interpret mode (the existing override)."""
    from eventstreamgpt_tpu.ops.impl_select import ENV_VAR

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(ENV_VAR, "pallas_interpret")
        yield


@pytest.fixture(scope="module")
def cohort(tmp_path_factory, interpret_kernels):
    out = tmp_path_factory.mktemp("chip_smoke")
    data_dir, train_ds = chip_smoke.phase_data(out, TINY)
    return out, data_dir, train_ds


@pytest.fixture(scope="module")
def trained_ci(cohort):
    out, data_dir, train_ds = cohort
    return chip_smoke.phase_train_ci(
        data_dir, train_ds, out, TINY, expect_kernels=False
    )


def test_refuses_to_print_ok_on_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_only_option_is_chips(capsys):
    with pytest.raises(SystemExit):
        chip_smoke.main(["--help"])
    import re

    options = set(re.findall(r"--[a-z][a-z_-]*", capsys.readouterr().out))
    assert options == {"--chips", "--help"}


def test_kernel_comparisons(interpret_kernels):
    chip_smoke.phase_kernels(TINY, "pallas_interpret")


def test_attention_parity_phase(interpret_kernels):
    """Off the chip both twins are the einsum path; this pins the phase's
    plumbing (configs, packed segments, comparison), not the kernels."""
    chip_smoke.phase_attention_parity(128, 32, expect_kernels=False)


def test_train_ci_preempt_resume(trained_ci):
    losses = chip_smoke.read_train_losses(trained_ci)
    assert [s for s, _ in losses] == [2, 4, 6]
    assert (trained_ci / "pretrained_weights").exists()
    # device_resident_data=true took DeviceDataset.create (no silent host feed)
    cfg = json.loads((trained_ci / "config.json").read_text())
    assert cfg["attention_implementation"] == "pallas_flash"


def test_serve_on_trained_params(trained_ci, cohort):
    chip_smoke.phase_serve(trained_ci, cohort[2], TINY)


def test_train_na(cohort):
    out, data_dir, train_ds = cohort
    chip_smoke.phase_train_na(data_dir, train_ds, out, TINY, expect_kernels=False)


def test_multichip_phase_on_virtual_devices(cohort):
    """The second rehearsal: ``--chips 4``'s phase on the CPU mesh (the
    conftest's 8 virtual devices stand in for the host's chips)."""
    import jax

    out, data_dir, _ = cohort
    sz = chip_smoke.dataclasses.replace(TINY, batch=8)
    chip_smoke.phase_multichip(data_dir, out, sz, len(jax.devices()))


def test_timing_sanity_prints(capsys):
    chip_smoke.phase_timing(n=128, chain=2)
    assert "block_until_ready" in capsys.readouterr().out
