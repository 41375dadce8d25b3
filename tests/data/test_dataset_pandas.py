"""ETL engine tests: golden end-to-end on the reference raw sample + units.

The end-to-end test runs the full pipeline (schema ingestion → range/event
splitting → 1h datapoint-anchored aggregation → split → preprocess →
save/load → DL cache) on ``sample_data/raw`` with the
reference's own ``dataset.yaml`` knobs, and checks fitted vocabularies
against the reference's shipped processed artifacts where the input data
overlap makes them comparable (eye_color, department). Unit tests pin the
numeric-fitting semantics (bounds, value-type inference, outlier/normalizer,
vocab naming) from ``dataset_polars.py:437-1097``.
"""

import tempfile
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.data.config import (
    DatasetConfig,
    DatasetSchema,
    InputDFSchema,
    MeasurementConfig,
)
from eventstreamgpt_tpu.data.dataset_pandas import Dataset
from eventstreamgpt_tpu.data.preprocessing import StandardScaler, StddevCutoffOutlierDetector
from eventstreamgpt_tpu.data.time_dependent_functor import AgeFunctor
from eventstreamgpt_tpu.data.types import (
    DataModality,
    InputDataType,
    InputDFType,
    NumericDataModalitySubtype,
    TemporalityType,
)

from tests import SAMPLE_RAW_DIR as RAW  # noqa: E402  (the committed raw CSVs)


def build_sample_dataset(save_dir: Path) -> Dataset:
    """The reference sample_data/dataset.yaml pipeline, constructed directly."""
    static_schema = InputDFSchema(
        input_df=str(RAW / "subjects.csv"),
        type=InputDFType.STATIC,
        subject_id_col="MRN",
        data_schema={
            "eye_color": InputDataType.CATEGORICAL,
            "dob": (InputDataType.TIMESTAMP, "%m/%d/%Y"),
        },
    )
    admissions_schema = InputDFSchema(
        input_df=str(RAW / "admit_vitals.csv"),
        type=InputDFType.RANGE,
        event_type=("OUTPATIENT_VISIT", "ADMISSION", "DISCHARGE"),
        start_ts_col="admit_date",
        end_ts_col="disch_date",
        ts_format="%m/%d/%Y, %H:%M:%S",
        data_schema={"department": InputDataType.CATEGORICAL},
    )
    vitals_schema = InputDFSchema(
        input_df=str(RAW / "admit_vitals.csv"),
        type=InputDFType.EVENT,
        event_type="VITALS",
        ts_col="vitals_date",
        ts_format="%m/%d/%Y, %H:%M:%S",
        data_schema={"HR": InputDataType.FLOAT, "temp": InputDataType.FLOAT},
    )
    schema = DatasetSchema(static=static_schema, dynamic=[admissions_schema, vitals_schema])

    config = DatasetConfig(
        measurement_configs={
            "eye_color": MeasurementConfig(
                temporality=TemporalityType.STATIC,
                modality=DataModality.SINGLE_LABEL_CLASSIFICATION,
            ),
            "age": MeasurementConfig(
                temporality=TemporalityType.FUNCTIONAL_TIME_DEPENDENT,
                functor=AgeFunctor(dob_col="dob"),
            ),
            "department": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC,
                modality=DataModality.MULTI_LABEL_CLASSIFICATION,
            ),
            "HR": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC, modality=DataModality.UNIVARIATE_REGRESSION
            ),
            "temp": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC, modality=DataModality.UNIVARIATE_REGRESSION
            ),
        },
        min_events_per_subject=3,
        agg_by_time_scale="1h",
        min_valid_column_observations=5,
        min_valid_vocab_element_observations=5,
        min_true_float_frequency=0.1,
        min_unique_numerical_observations=20,
        outlier_detector_config={"cls": "stddev_cutoff", "stddev_cutoff": 1.5},
        normalizer_config={"cls": "standard_scaler"},
        save_dir=save_dir,
    )
    return Dataset(config=config, input_schema=schema)


@pytest.fixture(scope="module")
def built_dataset(tmp_path_factory):
    save_dir = tmp_path_factory.mktemp("etl") / "sample"
    ESD = build_sample_dataset(save_dir)
    ESD.split([0.8, 0.1], seed=1)
    ESD.preprocess()
    ESD.save(do_overwrite=True)
    ESD.cache_deep_learning_representation(do_overwrite=True)
    return ESD


class TestEndToEnd:
    def test_construction(self, built_dataset):
        ESD = built_dataset
        assert len(ESD.subjects_df) == 120  # the committed raw CSVs
        assert len(ESD.events_df) > 5_000
        # Aggregated event types are sorted unique unions joined with '&'.
        assert "ADMISSION&VITALS" in ESD.event_types
        assert set(ESD.split_subjects) == {"train", "tuning", "held_out"}
        sizes = {k: len(v) for k, v in ESD.split_subjects.items()}
        assert sizes == {"train": 96, "tuning": 12, "held_out": 12}

    def test_fit_vocabularies_match_reference_artifacts(self, built_dataset):
        """eye_color/department derive from the same raw inputs the committed
        processed artifact (sample_data/processed/sample) was built from — the
        fitted vocabularies must hold the same elements, UNK first (their
        frequency order depends on the train split drawn)."""
        import json

        from tests import SAMPLE_DIR

        shipped = json.loads((SAMPLE_DIR / "inferred_measurement_configs.json").read_text())
        cfgs = built_dataset.measurement_configs
        for name in ("eye_color", "department"):
            vocab = cfgs[name].vocabulary.vocabulary
            assert vocab[0] == "UNK"
            assert set(vocab) == set(shipped[name]["vocabulary"]["vocabulary"])
        assert len(cfgs["eye_color"].vocabulary.vocabulary) == 5

    def test_numeric_fit(self, built_dataset):
        md = built_dataset.measurement_configs["age"].measurement_metadata
        assert md["value_type"] == NumericDataModalitySubtype.FLOAT
        assert set(md["outlier_model"]) == {"thresh_large_", "thresh_small_"}
        assert set(md["normalizer"]) == {"mean_", "std_"}
        assert md["outlier_model"]["thresh_small_"] < md["normalizer"]["mean_"]
        assert md["normalizer"]["std_"] > 0

    def test_unified_vocabulary_structure(self, built_dataset):
        vc = built_dataset.vocabulary_config
        # event_type offset 1; measurements alphabetical thereafter.
        assert list(vc.vocab_offsets_by_measurement) == [
            "event_type",
            "HR",
            "age",
            "department",
            "eye_color",
            "temp",
        ]
        assert vc.vocab_offsets_by_measurement["event_type"] == 1
        assert vc.measurements_idxmap["event_type"] == 1
        # Offsets are cumulative vocab sizes.
        offs = list(vc.vocab_offsets_by_measurement.values())
        assert all(b > a for a, b in zip(offs, offs[1:]))
        assert vc.total_vocab_size > offs[-1]

    def test_save_load_round_trip(self, built_dataset):
        ESD2 = Dataset.load(Path(built_dataset.config.save_dir))
        assert len(ESD2.events_df) == len(built_dataset.events_df)
        assert ESD2._is_fit
        assert set(ESD2.measurement_configs) == set(built_dataset.measurement_configs)
        assert ESD2.split_subjects == built_dataset.split_subjects

    def test_dl_cache_consumed_by_jax_dataset(self, built_dataset):
        save_dir = Path(built_dataset.config.save_dir)
        for split in ("train", "tuning", "held_out"):
            assert (save_dir / "DL_reps" / f"{split}_0.parquet").exists()

        ds = JaxDataset(
            PytorchDatasetConfig(save_dir=save_dir, max_seq_len=32, min_seq_len=2), "train"
        )
        assert len(ds) == 96
        b = next(ds.batches(4, shuffle=True, seed=0))
        assert np.asarray(b.event_mask).shape == (4, 32)
        assert np.asarray(b.event_mask).sum() > 0
        # Indices are in unified-vocab range.
        di = np.asarray(b.dynamic_indices)
        assert di.max() < built_dataset.vocabulary_config.total_vocab_size

    def test_dl_cache_times_are_minutes_from_start(self, built_dataset):
        df = pd.read_parquet(Path(built_dataset.config.save_dir) / "DL_reps" / "train_0.parquet")
        row = df.iloc[0]
        t = np.asarray(row["time"], dtype=float)
        assert t[0] == 0.0
        assert np.all(np.diff(t) > 0)


class TestSplitAndFilter:
    def _tiny(self, tmp_path, min_events=None):
        subjects = pd.DataFrame({"subject_id": [0, 1, 2], "eye_color": ["BLUE", "BROWN", "BLUE"]})
        events = pd.DataFrame(
            {
                "event_id": np.arange(5),
                "subject_id": [0, 0, 1, 1, 2],
                "timestamp": pd.to_datetime(
                    ["2020-01-01", "2020-01-02", "2020-01-01", "2020-01-03", "2020-01-01"]
                ),
                "event_type": ["A", "B", "A", "A", "B"],
            }
        )
        measurements = pd.DataFrame(
            {"measurement_id": np.arange(5), "event_id": np.arange(5), "lab": list("vwxyz")}
        )
        config = DatasetConfig(
            measurement_configs={
                "lab": MeasurementConfig(
                    temporality=TemporalityType.DYNAMIC,
                    modality=DataModality.MULTI_LABEL_CLASSIFICATION,
                )
            },
            min_events_per_subject=min_events,
            agg_by_time_scale=None,
            save_dir=tmp_path,
        )
        return Dataset(
            config=config,
            subjects_df=subjects,
            events_df=events,
            dynamic_measurements_df=measurements,
        )

    def test_split_fracs_validation(self, tmp_path):
        ESD = self._tiny(tmp_path)
        with pytest.raises(ValueError, match="split_fracs invalid"):
            ESD.split([0.5, 0.7])
        ESD.split([0.5, 0.5], seed=0)
        assert sum(len(v) for v in ESD.split_subjects.values()) == 3

    def test_remainder_split(self, tmp_path):
        ESD = self._tiny(tmp_path)
        ESD.split([0.4, 0.3], seed=0)  # remainder 0.3 becomes the third split
        assert len(ESD.split_subjects) == 3

    def test_filter_subjects(self, tmp_path):
        ESD = self._tiny(tmp_path, min_events=2)
        ESD.split([0.5, 0.5], seed=0)
        ESD._filter_subjects()
        # Subject 2 has one event and is dropped.
        assert 2 not in set(ESD.events_df["subject_id"])
        assert 2 not in set(ESD.subjects_df["subject_id"])


class TestAggByTime:
    def test_datapoint_anchored_buckets(self, tmp_path):
        """Buckets anchor at each subject's first event, not calendar hours
        (polars groupby_dynamic start_by='datapoint' semantics)."""
        events = pd.DataFrame(
            {
                "event_id": np.arange(4),
                "subject_id": [0, 0, 0, 0],
                "timestamp": pd.to_datetime(
                    [
                        "2020-01-01 00:30:00",
                        "2020-01-01 01:00:00",  # within 1h of first → same bucket
                        "2020-01-01 01:35:00",  # next bucket (>= 00:30 + 1h)
                        "2020-01-01 02:29:00",  # still second bucket
                    ]
                ),
                "event_type": ["A", "B", "A", "A"],
            }
        )
        measurements = pd.DataFrame(
            {"measurement_id": np.arange(4), "event_id": np.arange(4), "lab": list("wxyz")}
        )
        config = DatasetConfig(
            measurement_configs={
                "lab": MeasurementConfig(
                    temporality=TemporalityType.DYNAMIC,
                    modality=DataModality.MULTI_LABEL_CLASSIFICATION,
                )
            },
            agg_by_time_scale="1h",
            save_dir=tmp_path,
        )
        ESD = Dataset(
            config=config,
            subjects_df=pd.DataFrame({"subject_id": [0]}),
            events_df=events,
            dynamic_measurements_df=measurements,
        )
        assert len(ESD.events_df) == 2
        assert ESD.events_df["event_type"].tolist() == ["A&B", "A"]
        assert ESD.events_df["timestamp"].tolist() == [
            pd.Timestamp("2020-01-01 00:30:00"),
            pd.Timestamp("2020-01-01 01:30:00"),
        ]
        # Measurements re-pointed to the new event ids.
        remapped = ESD.dynamic_measurements_df["event_id"].tolist()
        assert remapped == [0, 0, 1, 1]


class TestNumericSemantics:
    def test_drop_or_censor(self):
        vals = np.asarray([1.0, 5.0, 10.0, 15.0, 20.0])
        out = Dataset.drop_or_censor_np(
            vals,
            {
                "drop_lower_bound": np.full(5, 2.0),
                "drop_lower_bound_inclusive": np.full(5, False),
                "drop_upper_bound": np.full(5, 18.0),
                "drop_upper_bound_inclusive": np.full(5, True),
                "censor_lower_bound": np.full(5, 6.0),
                "censor_upper_bound": np.full(5, 12.0),
            },
        )
        # 1 < 2 → dropped; 5 < 6 → censored to 6; 10 in range; 15 > 12 →
        # censored to 12; 20 ≥ 18 (inclusive) → dropped.
        assert np.isnan(out[0])
        assert out[1] == 6.0
        assert out[2] == 10.0
        assert out[3] == 12.0
        assert np.isnan(out[4])

    def _fit_dataset(self, tmp_path, values, keys=None, **config_kwargs):
        n = len(values)
        meas = pd.DataFrame(
            {
                "measurement_id": np.arange(n),
                "event_id": np.arange(n),
                "lab": keys if keys is not None else ["k"] * n,
                "lab_val": values,
            }
        )
        events = pd.DataFrame(
            {
                "event_id": np.arange(n),
                "subject_id": np.zeros(n, dtype=int),
                "timestamp": pd.date_range("2020-01-01", periods=n, freq="2h"),
                "event_type": ["A"] * n,
            }
        )
        config = DatasetConfig(
            measurement_configs={
                "lab": MeasurementConfig(
                    temporality=TemporalityType.DYNAMIC,
                    modality=DataModality.MULTIVARIATE_REGRESSION,
                    values_column="lab_val",
                )
            },
            agg_by_time_scale=None,
            **config_kwargs,
            save_dir=tmp_path,
        )
        ESD = Dataset(
            config=config,
            subjects_df=pd.DataFrame({"subject_id": [0]}),
            events_df=events,
            dynamic_measurements_df=meas,
        )
        ESD.split_subjects = {"train": {0}, "tuning": set(), "held_out": set()}
        ESD.fit_measurements()
        return ESD

    def test_integer_value_type_inference(self, tmp_path):
        values = [float(x) for x in range(1, 41)]  # all integral, 40 unique
        ESD = self._fit_dataset(
            tmp_path, values, min_true_float_frequency=0.1, min_unique_numerical_observations=20
        )
        md = ESD.measurement_configs["lab"].measurement_metadata
        assert md.loc["k", "value_type"] == NumericDataModalitySubtype.INTEGER

    def test_float_value_type_inference(self, tmp_path):
        rng = np.random.default_rng(0)
        values = rng.normal(size=40).tolist()
        ESD = self._fit_dataset(
            tmp_path, values, min_true_float_frequency=0.1, min_unique_numerical_observations=20
        )
        md = ESD.measurement_configs["lab"].measurement_metadata
        assert md.loc["k", "value_type"] == NumericDataModalitySubtype.FLOAT

    def test_categorical_integer_inference_and_vocab(self, tmp_path):
        values = [1.0, 2.0, 3.0] * 20  # integral, 3 unique of 60 → categorical int
        ESD = self._fit_dataset(
            tmp_path, values, min_true_float_frequency=0.1, min_unique_numerical_observations=20
        )
        cfg = ESD.measurement_configs["lab"]
        md = cfg.measurement_metadata
        assert md.loc["k", "value_type"] == NumericDataModalitySubtype.CATEGORICAL_INTEGER
        # Vocabulary keys become key__EQ_<int>.
        vocab = set(cfg.vocabulary.vocabulary)
        assert {"k__EQ_1", "k__EQ_2", "k__EQ_3"}.issubset(vocab)

    def test_all_categorical_keys_with_outlier_detector(self, tmp_path):
        """When every key is inferred categorical, no numeric rows reach the
        outlier/normalizer fits — the (empty) grouped fit must not crash and
        the value types must survive (regression: the vectorized param
        alignment indexed columns of an empty params frame)."""
        values = [1.0, 2.0, 3.0] * 20  # categorical-integer by cardinality
        ESD = self._fit_dataset(
            tmp_path,
            values,
            min_true_float_frequency=0.1,
            min_unique_numerical_observations=20,
            outlier_detector_config={"cls": "stddev_cutoff", "stddev_cutoff": 4.0},
            normalizer_config={"cls": "standard_scaler"},
        )
        md = ESD.measurement_configs["lab"].measurement_metadata
        assert md.loc["k", "value_type"] == NumericDataModalitySubtype.CATEGORICAL_INTEGER

    def test_single_value_keys_dropped(self, tmp_path):
        values = [7.0] * 30
        ESD = self._fit_dataset(tmp_path, values)
        md = ESD.inferred_measurement_configs["lab"].measurement_metadata
        assert md.loc["k", "value_type"] == NumericDataModalitySubtype.DROPPED

    def test_outlier_and_normalizer_fit_values(self, tmp_path):
        values = [1.0, 2.0, 3.0, 4.0, 5.0, 100.0] + np.linspace(1, 5, 34).tolist()
        ESD = self._fit_dataset(
            tmp_path,
            values,
            outlier_detector_config={"cls": "stddev_cutoff", "stddev_cutoff": 2.0},
            normalizer_config={"cls": "standard_scaler"},
        )
        md = ESD.measurement_configs["lab"].measurement_metadata
        om = md.loc["k", "outlier_model"]
        nm = md.loc["k", "normalizer"]
        arr = np.asarray(values)
        np.testing.assert_allclose(om["thresh_large_"], arr.mean() + 2 * arr.std(ddof=1))
        # The normalizer is fit AFTER outlier removal (100.0 excluded).
        inliers = arr[(arr <= om["thresh_large_"]) & (arr >= om["thresh_small_"])]
        np.testing.assert_allclose(nm["mean_"], inliers.mean())
        np.testing.assert_allclose(nm["std_"], inliers.std(ddof=1))

    def test_originally_missing_categorical_values_stay_null(self, tmp_path):
        """A categorical-typed key with a missing value keeps a null key after
        transform (reference: polars string-concat with null is null), while
        bound-dropped values re-key to __EQ_-1 → UNK."""
        values = [1.0, 2.0, 3.0] * 20 + [np.nan]
        ESD = self._fit_dataset(
            tmp_path, values, min_true_float_frequency=0.1, min_unique_numerical_observations=20
        )
        ESD.transform_measurements()
        dmd = ESD.dynamic_measurements_df.sort_values("measurement_id")
        # The last row had a missing value → its key must be null, not UNK.
        last = dmd.iloc[-1]
        assert pd.isna(last["lab"])
        # Observed rows are re-keyed to k__EQ_<int>.
        assert dmd.iloc[0]["lab"] == "k__EQ_1"

    def test_transform_unk_and_normalization(self, tmp_path):
        rng = np.random.default_rng(0)
        values = rng.normal(10.0, 2.0, size=40).tolist()
        ESD = self._fit_dataset(
            tmp_path,
            values,
            normalizer_config={"cls": "standard_scaler"},
        )
        ESD.transform_measurements()
        dmd = ESD.dynamic_measurements_df
        # Values are normalized to ~zero mean.
        assert abs(np.nanmean(dmd["lab_val"].to_numpy(dtype=float))) < 0.2
        assert (dmd["lab"] == "k").all()


class TestPreprocessors:
    def test_standard_scaler(self):
        S = StandardScaler()
        p = S.fit(np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]))
        assert p["mean_"] == 3.0
        np.testing.assert_allclose(p["std_"], np.std([1, 2, 3, 4, 5], ddof=1))
        per_row = {k: np.full(5, v) for k, v in p.items()}
        out = S.predict(np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]), per_row)
        np.testing.assert_allclose(out.mean(), 0.0, atol=1e-12)

    def test_stddev_cutoff(self):
        S = StddevCutoffOutlierDetector(stddev_cutoff=1.0)
        p = S.fit(np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]))
        per_row = {k: np.full(5, v) for k, v in p.items()}
        out = S.predict(np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]), per_row)
        assert out.tolist() == [True, False, False, False, True]


class TestParallelETL:
    """n_workers > 1 must produce byte-identical outputs to the serial path.

    The subject-sharded DL cache and the per-measurement transform pool
    (dataset_base.py `_fork_map`) exist for multi-core hosts (the reference
    gets the analogous parallelism from Polars' Rust threadpool); on any
    worker count the artifacts must match the serial build exactly.
    """

    @pytest.fixture(scope="class")
    def two_datasets(self, tmp_path_factory):
        built = []
        for tag, n_workers in (("serial", 1), ("pooled", 3)):
            save_dir = tmp_path_factory.mktemp(f"etl_{tag}") / "sample"
            ESD = build_sample_dataset(save_dir)
            ESD.split([0.8, 0.1], seed=1)
            ESD.preprocess(n_workers=n_workers)
            ESD.save(do_overwrite=True)
            ESD.cache_deep_learning_representation(do_overwrite=True, n_workers=n_workers)
            built.append(ESD)
        return built

    def test_transformed_frames_identical(self, two_datasets):
        serial, pooled = two_datasets
        for attr in ("subjects_df", "events_df", "dynamic_measurements_df"):
            a, b = getattr(serial, attr), getattr(pooled, attr)
            pd.testing.assert_frame_equal(a, b)

    def test_dl_cache_identical(self, two_datasets):
        serial, pooled = two_datasets
        s_dir = Path(serial.config.save_dir) / "DL_reps"
        p_dir = Path(pooled.config.save_dir) / "DL_reps"
        s_files = sorted(fp.name for fp in s_dir.glob("*.parquet"))
        p_files = sorted(fp.name for fp in p_dir.glob("*.parquet"))
        assert s_files == p_files and s_files
        for name in s_files:
            pd.testing.assert_frame_equal(
                pd.read_parquet(s_dir / name), pd.read_parquet(p_dir / name)
            )

    def test_sharded_build_matches_direct(self, two_datasets):
        serial, _ = two_datasets
        direct = serial.build_DL_cached_representation()
        sharded = serial._build_dl_rep_sharded(None, n_workers=3)
        pd.testing.assert_frame_equal(direct, sharded)
