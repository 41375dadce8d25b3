"""Tiny sizes for the CPU rehearsal of the benchmark's cells: the same files
as the chip runs, every size cut so that a test can hold it, float32 so that
the program and the plain reference agree to rounding."""

from __future__ import annotations

from pathlib import Path

from benchmark.harness import loader

WORKLOADS = sorted(p.stem for p in (loader.ROOT / "workloads").glob("*.json"))


def tiny_cell(name: str, precision: str = "fp32") -> dict:
    cell = loader.load_cell(name)
    cell["model"]["config"].update(
        hidden_size=32, head_dim=8, num_attention_heads=4, num_hidden_layers=2,
        intermediate_size=64, seq_window_size=4, precision=precision,
    )
    cell["cohort"].update(
        n_subjects=64, n_event_types=5, n_labs=20, n_meds=6, mean_seq_len=10,
        max_seq_len=16, mean_obs_per_event=4, max_obs_per_event=6,
    )
    feed = cell["feed"]
    feed.update(batch_size=4, data_max_seq_len=16)
    if feed["packed"]:
        feed["seq_len"] = 32
        cell["model"]["config"]["max_seq_len"] = 32
    cell["check"]["rows_per_block"] = 2
    cell["trace_seconds"] = 1
    return cell


def run_tiny(cell: dict, work_dir: Path, seed: int = 3000000019, seconds: float = 0.5) -> dict:
    """One run of the job module below the harness's look for a chip."""
    from benchmark import run as bench_run

    env = bench_run.RunEnv(cell, 1, False, work_dir)
    return loader.load_job(cell).run(cell, seed, seconds, False, env)
