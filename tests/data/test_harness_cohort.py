"""The benchmark's own cohort, read back by `JaxDataset`: the program's flat
arrays equal the cohort's, which the benchmark's plain reference collates from.

The harness (``benchmark/harness/cohort.py``) is imported and used as it is:
it makes a packed cell's cohort, cut to a few subjects, and writes it as the
deep-learning cache the program reads.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import cohort as cohort_lib
from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig

WORKLOADS = Path(__file__).resolve().parents[2] / "benchmark" / "workloads"


@pytest.mark.parametrize(
    "cell, seed",
    [("ci_w1024.pretrain_packed", 123), ("nemotron_twotower_ep16.pretrain_packed", 3_600_000_001)],
)
def test_cache_reads_back_as_the_cohort(cell, seed, tmp_path):
    spec = dict(json.loads((WORKLOADS / f"{cell}.json").read_text())["cohort"], n_subjects=48)
    cohort = cohort_lib.make_cohort(spec, seed)
    cohort_lib.write_dl_cache(cohort, spec, tmp_path)
    ds = JaxDataset(
        PytorchDatasetConfig(save_dir=tmp_path, max_seq_len=spec["max_seq_len"], min_seq_len=spec["min_seq_len"]),
        "train",
    )
    d, filled = ds.data, cohort.measurements != 0

    np.testing.assert_array_equal(d.subject_event_offsets, cohort.offsets)
    assert d.time_delta.dtype == np.float32 and d.time_delta.tobytes() == cohort.time_delta.tobytes()
    np.testing.assert_array_equal(np.diff(d.event_data_offsets), filled.sum(1))
    np.testing.assert_array_equal(d.dynamic_indices, cohort.indices[filled])
    np.testing.assert_array_equal(d.dynamic_measurement_indices, cohort.measurements[filled])
    np.testing.assert_array_equal(d.dynamic_values_observed, cohort.observed[filled])
    assert d.dynamic_values.tobytes() == cohort.values[filled].tobytes()  # 0 where not observed, as the cohort holds it
    np.testing.assert_array_equal(d.static_offsets, np.arange(len(cohort.offsets)))
    np.testing.assert_array_equal(d.static_indices, cohort.static_indices[:, 0])
    assert ds.subject_ids == list(range(len(cohort.offsets) - 1))
    assert ds.max_n_dynamic == int(filled.sum(1).max()) and ds.max_n_static == 1

    last = np.zeros(cohort.n_events, bool)
    last[cohort.offsets[1:] - 1] = True
    logs = np.log(cohort.time_delta[~last])
    assert (ds.mean_log_inter_event_time_min, ds.std_log_inter_event_time_min) == (float(logs.mean()), float(logs.std(ddof=1)))
