"""CPU rehearsal of every cell at a tiny size (kernels interpreted), the way
tests/test_chip_smoke.py rehearses the smoke, and the command's refusal to run
off the TPU."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from .tiny import WORKLOADS, run_tiny, tiny_cell

REPO = Path(__file__).resolve().parents[2]
RECORD_KEYS = {
    "correct", "compared", "attempted", "failed", "window", "memory_peak_bytes",
    "end_to_end", "end_to_end_units", "counters", "model_sizes",
}


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One tiny run per cell, shared by the tests of this file."""
    done = {}

    def get(name):
        if name not in done:
            with pytest.MonkeyPatch.context() as patch:
                patch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
                cell = tiny_cell(name)
                done[name] = cell, run_tiny(cell, tmp_path_factory.mktemp("run"))
        return done[name]

    return get


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_runs_and_agrees_with_the_reference(name, rehearsed):
    _cell, record = rehearsed(name)
    assert RECORD_KEYS <= set(record)
    assert record["correct"] is True
    assert record["attempted"] > 0 and record["failed"] == 0
    assert set(record["end_to_end"]) == set(record["end_to_end_units"])
    compared = record["compared"]
    # float32 on both sides: the program and the reference agree to rounding
    assert compared["loss_gap"]["value"] < 1e-5
    assert compared["grad_norm_gap"]["value"] < 1e-4
    assert compared["param_change_gap"]["value"] < 1e-3
    assert compared["grad_diff_gap"]["value"] < 1e-4
    assert compared["compiles_in_window"]["value"] == 0
    for number in compared.values():
        assert "value" in number and "limit" in number


@pytest.mark.parametrize("name", WORKLOADS)
def test_result_line_has_the_contracts_keys(name, rehearsed):
    from benchmark import run as bench_run

    cell, record = rehearsed(name)
    dev = {"platform": "cpu", "kind": "rehearsal", "count": 1}
    line = bench_run.result_line(cell, record, dev, tracing=False, setup_s=1.0)
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all({"value", "unit"} == set(m) for m in line["metrics"].values())
    json.dumps(line)


def test_the_command_refuses_to_run_off_the_tpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", WORKLOADS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": "/tmp"},
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr
