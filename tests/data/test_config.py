"""Tests for data config objects: schemas, measurement/dataset configs."""

from datetime import datetime
from pathlib import Path

import pandas as pd
import pytest

from eventstreamgpt_tpu.data import (
    AgeFunctor,
    DataModality,
    DatasetConfig,
    DatasetSchema,
    InputDataType,
    InputDFSchema,
    InputDFType,
    MeasurementConfig,
    PytorchDatasetConfig,
    TemporalityType,
    TimeOfDayFunctor,
    Vocabulary,
    VocabularyConfig,
)


def test_input_df_schema_static_validation():
    s = InputDFSchema(
        input_df="subjects.csv",
        type=InputDFType.STATIC,
        subject_id_col="subject_id",
        data_schema={"dob": ("timestamp", "%m/%d/%Y"), "eye_color": "categorical"},
    )
    assert s.is_static
    cols = dict(s.columns_to_load)
    assert "dob" in cols and "eye_color" in cols

    with pytest.raises(ValueError, match="subject_id_col"):
        InputDFSchema(input_df="x", type=InputDFType.STATIC, data_schema={})
    with pytest.raises(ValueError, match="input_df"):
        InputDFSchema(type=InputDFType.STATIC, subject_id_col="sid")


def test_input_df_schema_event():
    s = InputDFSchema(
        input_df="events.csv",
        type=InputDFType.EVENT,
        event_type="LAB",
        ts_col="ts",
        data_schema={"lab_name": InputDataType.CATEGORICAL, "lab_value": InputDataType.FLOAT},
    )
    assert not s.is_static
    assert ("ts", InputDataType.TIMESTAMP) in s.columns_to_load
    assert s.unified_schema["lab_name"] == ("lab_name", InputDataType.CATEGORICAL)

    with pytest.raises(ValueError, match="ts_col"):
        InputDFSchema(input_df="x", type=InputDFType.EVENT, event_type="LAB")
    with pytest.raises(TypeError, match="string"):
        InputDFSchema(input_df="x", type=InputDFType.EVENT, event_type=("a", "b", "c"), ts_col="ts")


def test_input_df_schema_range_event_type_expansion():
    s = InputDFSchema(
        input_df="adm.csv",
        type=InputDFType.RANGE,
        event_type="ADMISSION",
        start_ts_col="admit_ts",
        end_ts_col="disch_ts",
        data_schema={"department": InputDataType.CATEGORICAL},
    )
    assert s.event_type == ("ADMISSION", "ADMISSION_START", "ADMISSION_END")
    eq, st, end = s.unified_schema
    assert eq["department"] == ("department", InputDataType.CATEGORICAL)
    cols = dict(s.columns_to_load)
    assert "admit_ts" in cols and "disch_ts" in cols


def test_input_df_schema_column_remap():
    s = InputDFSchema(
        input_df="e.csv",
        type=InputDFType.EVENT,
        event_type="VITAL",
        ts_col="ts",
        data_schema={"HR_raw": ("HR", InputDataType.FLOAT)},
    )
    assert s.unified_schema["HR_raw"] == ("HR", InputDataType.FLOAT)


def test_dataset_schema():
    static = InputDFSchema(
        input_df="subj.csv",
        type=InputDFType.STATIC,
        subject_id_col="sid",
        data_schema={"eye_color": InputDataType.CATEGORICAL},
    )
    dyn = InputDFSchema(
        input_df="ev.csv",
        type=InputDFType.EVENT,
        event_type="LAB",
        ts_col="ts",
        data_schema={"lab": InputDataType.CATEGORICAL},
    )
    schema = DatasetSchema(static=static, dynamic=[dyn])
    # subject_id_col propagates to dynamic schemas.
    assert schema.dynamic[0].subject_id_col == "sid"
    with pytest.raises(ValueError, match="static"):
        DatasetSchema(static=None, dynamic=[dyn])


def test_vocabulary_config_total_size():
    vc = VocabularyConfig(
        vocab_sizes_by_measurement={"m1": 10, "m2": 3},
        vocab_offsets_by_measurement={"m1": 5, "m2": 15, "m3": 18},
    )
    assert vc.total_vocab_size == 19


def test_vocabulary_config_json_roundtrip(tmp_path: Path):
    vc = VocabularyConfig(
        vocab_sizes_by_measurement={"event_type": 9},
        vocab_offsets_by_measurement={"event_type": 1},
        measurements_idxmap={"event_type": 1},
        measurements_per_generative_mode={DataModality.SINGLE_LABEL_CLASSIFICATION: ["event_type"]},
        event_types_idxmap={"LAB": 1},
    )
    fp = tmp_path / "vocab.json"
    vc.to_json_file(fp)
    loaded = VocabularyConfig.from_json_file(fp)
    assert loaded.vocab_sizes_by_measurement == {"event_type": 9}
    assert loaded.total_vocab_size == 10


def test_reference_vocabulary_config_parses():
    """The committed serialized artifact (reference schema) must parse unchanged.

    Artifact: sample_data/processed/sample/vocabulary_config.json
    """
    from tests import SAMPLE_DIR

    ref_fp = SAMPLE_DIR / "vocabulary_config.json"
    if not ref_fp.exists():
        pytest.skip("reference sample data unavailable")
    vc = VocabularyConfig.from_json_file(ref_fp)
    assert vc.total_vocab_size == 27  # the committed artifact's (the reference's own sample: 45)
    assert vc.measurements_idxmap["event_type"] == 1


def test_pytorch_dataset_config_validation():
    cfg = PytorchDatasetConfig(save_dir="/tmp/x", max_seq_len=10, min_seq_len=2)
    assert isinstance(cfg.save_dir, Path)
    d = cfg.to_dict()
    assert d["seq_padding_side"] == "right"
    rt = PytorchDatasetConfig.from_dict(d)
    assert rt == cfg

    with pytest.raises(ValueError):
        PytorchDatasetConfig(save_dir="/tmp/x", max_seq_len=1, min_seq_len=5)
    with pytest.raises(ValueError):
        PytorchDatasetConfig(save_dir="/tmp/x", train_subset_size=-1)
    with pytest.raises(ValueError):
        PytorchDatasetConfig(save_dir="/tmp/x", train_subset_size=1.2)
    with pytest.raises(ValueError):
        PytorchDatasetConfig(save_dir="/tmp/x", train_subset_seed=10)


def test_measurement_config_validation():
    with pytest.raises(ValueError, match="temporality"):
        MeasurementConfig(name="x")
    with pytest.raises(ValueError, match="functor"):
        MeasurementConfig(name="x", temporality=TemporalityType.FUNCTIONAL_TIME_DEPENDENT)
    with pytest.raises(ValueError, match="functor"):
        MeasurementConfig(
            name="x",
            temporality=TemporalityType.STATIC,
            modality=DataModality.SINGLE_LABEL_CLASSIFICATION,
            functor=TimeOfDayFunctor(),
        )
    with pytest.raises(ValueError, match="single_label_classification"):
        MeasurementConfig(
            name="x", temporality=TemporalityType.DYNAMIC,
            modality=DataModality.SINGLE_LABEL_CLASSIFICATION,
        )
    with pytest.raises(ValueError, match="values_column"):
        MeasurementConfig(
            name="x", temporality=TemporalityType.DYNAMIC, modality=DataModality.MULTIVARIATE_REGRESSION
        )

    cfg = MeasurementConfig(
        name="age",
        temporality=TemporalityType.FUNCTIONAL_TIME_DEPENDENT,
        functor=AgeFunctor(dob_col="dob"),
    )
    # Modality inferred from functor output modality.
    assert cfg.modality == DataModality.UNIVARIATE_REGRESSION


def test_measurement_config_drop():
    cfg = MeasurementConfig(
        name="lab",
        temporality=TemporalityType.DYNAMIC,
        modality=DataModality.MULTIVARIATE_REGRESSION,
        values_column="lab_value",
        vocabulary=Vocabulary(["UNK", "a"], [1, 1]),
    )
    assert cfg.is_numeric and not cfg.is_dropped
    cfg.drop()
    assert cfg.is_dropped and cfg.vocabulary is None


def test_measurement_config_metadata_roundtrip(tmp_path: Path):
    cfg = MeasurementConfig(
        name="lab",
        temporality=TemporalityType.DYNAMIC,
        modality=DataModality.MULTIVARIATE_REGRESSION,
        values_column="lab_value",
    )
    cfg.add_missing_mandatory_metadata_cols()
    md = cfg.measurement_metadata
    assert list(md.columns) == ["value_type", "outlier_model", "normalizer"]

    md = pd.DataFrame(
        {"value_type": ["float"], "outlier_model": [{"thresh_large_": 3.0}], "normalizer": [None]},
        index=pd.Index(["HR"], name="lab"),
    )
    cfg.measurement_metadata = md
    d = cfg.to_dict()
    rt = MeasurementConfig.from_dict(d)
    assert rt.measurement_metadata.loc["HR", "value_type"] == "float"

    # CSV cache roundtrip
    fp = tmp_path / "lab.csv"
    cfg.cache_measurement_metadata(fp)
    assert isinstance(cfg._measurement_metadata, str)
    md2 = cfg.measurement_metadata
    assert md2.loc["HR", "value_type"] == "float"
    assert md2.loc["HR", "outlier_model"] == {"thresh_large_": 3.0}
    cfg.uncache_measurement_metadata()
    assert isinstance(cfg._measurement_metadata, pd.DataFrame)


def test_dataset_config_validation():
    with pytest.raises(ValueError, match="differs from dict key"):
        DatasetConfig(
            measurement_configs={
                "m1": MeasurementConfig(
                    name="other", temporality=TemporalityType.DYNAMIC,
                    modality=DataModality.MULTI_LABEL_CLASSIFICATION,
                )
            }
        )
    with pytest.raises(TypeError):
        DatasetConfig(min_valid_column_observations="nope")
    with pytest.raises(ValueError, match="cls"):
        DatasetConfig(outlier_detector_config={"bad": 1})

    cfg = DatasetConfig(
        measurement_configs={
            "m1": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC, modality=DataModality.MULTI_LABEL_CLASSIFICATION
            )
        },
        min_valid_column_observations=5,
        outlier_detector_config={"cls": "stddev_cutoff", "stddev_cutoff": 1.5},
        normalizer_config={"cls": "standard_scaler"},
        save_dir="/tmp/ds",
    )
    assert cfg.measurement_configs["m1"].name == "m1"
    rt = DatasetConfig.from_dict(cfg.to_dict())
    assert rt == cfg


def test_reference_dataset_config_parses():
    """The reference's serialized config.json must parse unchanged."""
    from tests import SAMPLE_DIR

    ref_fp = SAMPLE_DIR / "config.json"
    if not ref_fp.exists():
        pytest.skip("reference sample data unavailable")
    import json

    cfg = DatasetConfig.from_dict(json.loads(ref_fp.read_text()))
    assert cfg.agg_by_time_scale == "1h"
    assert cfg.measurement_configs["age"].functor is not None
    assert cfg.measurement_configs["HR"].modality == DataModality.UNIVARIATE_REGRESSION
    assert cfg.measurement_configs["department"].modality == DataModality.MULTI_LABEL_CLASSIFICATION


def test_functors():
    f = AgeFunctor(dob_col="dob")
    ts = pd.Series([datetime(2020, 1, 1), datetime(2021, 1, 1)])
    st = pd.DataFrame({"dob": [datetime(1990, 1, 1), datetime(1995, 1, 1)]})
    ages = f.compute(ts, st).tolist()
    assert abs(ages[0] - 29.9986) < 1e-3
    assert abs(ages[1] - 26.0014) < 1e-3

    tod = TimeOfDayFunctor()
    ts = pd.Series(
        [datetime(2020, 1, 1, 0), datetime(2020, 1, 1, 6), datetime(2020, 1, 1, 12),
         datetime(2020, 1, 1, 18), datetime(2020, 1, 1, 23, 59)]
    )
    assert tod.compute(ts, None).tolist() == ["EARLY_AM", "AM", "PM", "PM", "LATE_PM"]

    # Serialization roundtrip
    d = f.to_dict()
    assert d == {"class": "AgeFunctor", "params": {"dob_col": "dob"}}
    f2 = AgeFunctor.from_dict(d)
    assert f == f2
