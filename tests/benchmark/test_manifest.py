"""`BENCHMARK.json` against the rules a manifest is refused by before any run:
its keys, its names and units, its lengths, and that everything it names is a
file under its own directories."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from benchmark.harness import loader

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert len(MANIFEST["command"]) <= 32 and all(_line(word) for word in MANIFEST["command"])
    assert any(word.startswith(path + "/") for word in MANIFEST["command"] for path in MANIFEST["paths"])


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and _line(config["source"]) and _line(config["why"])
    assert config["source"].startswith("https://")
    assert any(config["file"].startswith(path + "/") for path in MANIFEST["paths"])
    held = json.loads((REPO / config["file"]).read_text())
    assert held["source"] == config["source"] and held["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16 and all(NAME.match(key) for key in config["reduced"])
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and _line(cell["why"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    assert cell["chips"] in (1, 4)
    held = loader.load_cell(cell["name"])
    assert (held["config"], held["chips"], held["why"]) == (cell["config"], cell["chips"], cell["why"])


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | ({"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in SOURCES and _line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]} - {"setup_s"}
        if "roofline" in metric["name"]:
            assert metric["name"].endswith("_roofline") and metric["unit"] == "%"


def test_names_are_unique_and_setup_is_there():
    for group in ("configs", "workloads"):
        names = [entry["name"] for entry in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(pairs) // 4)
    assert any("mfu" in re.split(r"[._\-]", name) for name in metrics)


def test_files_under_paths_are_named_from_a_names_characters():
    for path in MANIFEST["paths"]:
        for file in (REPO / path).rglob("*"):
            if file.is_file() and "__pycache__" not in file.parts:
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(file.relative_to(REPO))), file
