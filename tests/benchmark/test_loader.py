"""A configuration, a cell and a per-layer metric are found by file name: a
new one dropped into a copy of ``benchmark/`` is found with no file edited."""

from __future__ import annotations

import json
import shutil

from benchmark.harness import loader


def test_new_files_are_found_without_an_edit(tmp_path):
    root = tmp_path / "benchmark"
    shutil.copytree(loader.ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    config = json.loads((root / "configs" / "ci_w1024.json").read_text())
    config["config"]["num_hidden_layers"] = 6
    (root / "configs" / "ci_w1024_l6.json").write_text(json.dumps(config))
    cell = json.loads((root / "workloads" / "ci_w1024.pretrain_packed.json").read_text())
    cell["config"] = "ci_w1024_l6"
    cell["feed"]["batch_size"] = 8
    (root / "workloads" / "ci_w1024_l6.pretrain_small.json").write_text(json.dumps(cell))
    (root / "metrics" / "dispatches_per_s.py").write_text(
        'LAYER = "feed"\nUNIT = "1/s"\nMOVES = "train_events_per_s"\nSOURCE = "program_counter"\n\n\n'
        "def read(record):\n"
        '    n = record["counters"].get("dispatches")\n'
        '    return n / (record["window"][1] - record["window"][0]) if n else None\n'
    )

    found = loader.load_cell("ci_w1024_l6.pretrain_small", root=root)
    assert found["model"]["config"]["num_hidden_layers"] == 6
    assert found["feed"]["batch_size"] == 8 and found["name"] == "ci_w1024_l6.pretrain_small"
    assert hasattr(loader.load_job(found, root=root), "run")
    assert hasattr(loader.load_reference(found, root=root), "init_params")
    readers = loader.metric_readers(root=root)
    assert "dispatches_per_s" in readers and "mfu.train" in readers
    record = {"counters": {"dispatches": 10}, "window": (0.0, 5.0)}
    assert readers["dispatches_per_s"].read(record) == 2.0
    assert readers["dispatches_per_s"].read({"counters": {}, "window": (0.0, 5.0)}) is None
    assert all(p.read_bytes() == data for p, data in before.items())


def test_every_listed_metric_has_a_reader_that_says_the_same():
    """What `BENCHMARK.json` lists is found by name and agrees with its file.
    A file may be there and not listed (the NA cell's, until it is proven)."""
    manifest = json.loads((loader.ROOT.parent / "BENCHMARK.json").read_text())
    readers = loader.metric_readers()
    for entry in manifest["per_layer"]:
        module = readers[entry["name"]]
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"],
        )
    for w in manifest["workloads"]:
        cell = loader.load_cell(w["name"])
        assert (cell["config"], cell["why"]) == (w["config"], w["why"])
        assert hasattr(loader.load_job(cell), "run")
