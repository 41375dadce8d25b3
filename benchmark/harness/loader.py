"""Finds configurations, cells and per-layer metrics by file name.

Nothing here keeps a list: a cell is ``workloads/<name>.json``, a
configuration ``configs/<name>.json``, a per-layer metric
``metrics/<name>.py``, a job kind ``harness/<job>.py`` and a plain reference
``reference/<name>.py``. A later PR adds any of them by adding the file and an
entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]  # the benchmark/ directory


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Imports one file as a module (names may hold dots, so not by import path)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location("bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's workload file with its configuration file under ``model``."""
    cell = _read_json(root / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["model"] = _read_json(root / "configs" / f"{cell['config']}.json")
    return cell


def load_job(cell: dict, root: Path = ROOT):
    return load_module(root / "harness" / f"{cell['job']}.py")


def load_reference(cell: dict, root: Path = ROOT):
    return load_module(root / "reference" / f"{cell['model']['reference']}.py")


def metric_readers(root: Path = ROOT) -> dict:
    """Every per-layer metric's reader, by the metric's name. A reader is
    given every traced run's record and returns nothing where it finds
    nothing to read, so no file lists cells."""
    return {path.stem: load_module(path) for path in sorted((root / "metrics").glob("*.py"))}
