"""CLI entry-point tests: the full pipeline through the scripts package.

Drives the reference workflow end-to-end in the reference's YAML dialect:
``build_dataset`` on the raw sample CSVs → ``pretrain`` → ``finetune`` →
``generate_trajectories``, plus the sweep/subset launchers' command
generation. Mirrors the reference's scripts/* surface (SURVEY §2.5).
"""

import json
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from scripts.build_dataset import main as build_dataset_main
from scripts.finetune import main as finetune_main
from scripts.generate_trajectories import main as generate_trajectories_main
from scripts.launch_hp_sweep import collapse_cfg, main as sweep_main, sample_param
from scripts.prepare_pretrain_subsets import main as subsets_main
from scripts.pretrain import main as pretrain_main

pytestmark = pytest.mark.slow  # full e2e; excluded from the fast core loop (-m "not slow")


from tests import SAMPLE_RAW_DIR as RAW  # noqa: E402  (the committed raw CSVs)

DATASET_YAML = """
do_overwrite: True
cohort_name: "sample"
subject_id_col: "MRN"
raw_data_dir: "{raw_dir}"
save_dir: "{save_dir}"

DL_chunk_size: null

inputs:
  subjects:
    input_df: "${{raw_data_dir}}/subjects.csv"
  admissions:
    input_df: "${{raw_data_dir}}/admit_vitals.csv"
    start_ts_col: "admit_date"
    end_ts_col: "disch_date"
    ts_format: "%m/%d/%Y, %H:%M:%S"
    event_type: ["OUTPATIENT_VISIT", "ADMISSION", "DISCHARGE"]
  vitals:
    input_df: "${{raw_data_dir}}/admit_vitals.csv"
    ts_col: "vitals_date"
    ts_format: "%m/%d/%Y, %H:%M:%S"

measurements:
  static:
    single_label_classification:
      subjects: ["eye_color"]
  functional_time_dependent:
    age:
      functor: AgeFunctor
      necessary_static_measurements: {{ "dob": ["timestamp", "%m/%d/%Y"] }}
      kwargs: {{ dob_col: "dob" }}
  dynamic:
    multi_label_classification:
      admissions: ["department"]
    univariate_regression:
      vitals: ["HR", "temp"]

outlier_detector_config:
  cls: stddev_cutoff
  stddev_cutoff: 1.5
normalizer_config:
  cls: standard_scaler
min_valid_vocab_element_observations: 5
min_valid_column_observations: 5
min_true_float_frequency: 0.1
min_unique_numerical_observations: 20
min_events_per_subject: 3
agg_by_time_scale: "1h"
"""


@pytest.fixture(scope="module")
def pipeline_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("cli_pipeline")
    save_dir = root / "processed" / "sample"
    yaml_fp = root / "dataset.yaml"
    yaml_fp.write_text(DATASET_YAML.format(raw_dir=RAW, save_dir=save_dir))
    return root, save_dir, yaml_fp


class TestBuildDataset:
    def test_build_from_reference_yaml_dialect(self, pipeline_dir):
        root, save_dir, yaml_fp = pipeline_dir
        ESD = build_dataset_main(["--config", str(yaml_fp)])
        assert (save_dir / "DL_reps" / "train_0.parquet").exists()
        assert (save_dir / "vocabulary_config.json").exists()
        # Range events expand to START/END types; the default event type for
        # the "vitals" source is its singularized upper name.
        assert any("ADMISSION" in et for et in ESD.event_types)
        assert any("VITAL" in et for et in ESD.event_types)

    def test_overrides_apply(self, pipeline_dir, tmp_path):
        root, _, yaml_fp = pipeline_dir
        alt = tmp_path / "alt"
        ESD = build_dataset_main(
            ["--config", str(yaml_fp), f"save_dir={alt}", "min_events_per_subject=5"]
        )
        assert ESD.config.min_events_per_subject == 5
        assert (alt / "DL_reps").exists()


class TestPretrainScript:
    def test_pretrain_cli(self, pipeline_dir):
        root, save_dir, yaml_fp = pipeline_dir
        if not (save_dir / "DL_reps" / "train_0.parquet").exists():
            build_dataset_main(["--config", str(yaml_fp)])
        pretrain_dir = root / "exp" / "pretrain"
        tuning_loss, tm, hm = pretrain_main(
            [
                f"data_config.save_dir={save_dir}",
                "data_config.max_seq_len=16",
                "data_config.min_seq_len=2",
                "config.hidden_size=32",
                "config.head_dim=8",
                "config.num_attention_heads=4",
                "config.num_hidden_layers=2",
                "config.intermediate_size=32",
                "optimization_config.init_lr=1e-3",
                "optimization_config.max_epochs=1",
                "optimization_config.batch_size=8",
                "optimization_config.validation_batch_size=8",
                "optimization_config.lr_frac_warmup_steps=0.5",
                f"save_dir={pretrain_dir}",
                "do_overwrite=true",
            ]
        )
        assert np.isfinite(tuning_loss)
        assert (pretrain_dir / "pretrained_weights").exists()
        assert (pretrain_dir / "pretrain_config.yaml").exists()

    def test_finetune_cli(self, pipeline_dir):
        root, save_dir, yaml_fp = pipeline_dir
        pretrain_dir = root / "exp" / "pretrain"
        assert pretrain_dir.exists(), "pretrain test must run first"

        # Build a binary task df.
        frames = [pd.read_parquet(f) for f in (save_dir / "DL_reps").glob("*.parquet")]
        raw = pd.concat(frames).drop_duplicates("subject_id")
        rows = []
        for _, row in raw.iterrows():
            t = np.asarray(row["time"], dtype=float)
            rows.append(
                {
                    "subject_id": row["subject_id"],
                    "start_time": pd.Timestamp(row["start_time"]),
                    "end_time": pd.Timestamp(row["start_time"])
                    + pd.Timedelta(minutes=float(t[-1])),
                    "label": bool(int(row["subject_id"]) % 2),
                }
            )
        (save_dir / "task_dfs").mkdir(exist_ok=True)
        pd.DataFrame(rows).to_parquet(save_dir / "task_dfs" / "mytask.parquet")

        tuning_loss, tm, hm = finetune_main(
            [
                f"load_from_model_dir={pretrain_dir}",
                "task_df_name=mytask",
                "data_config_overrides={}",
                "optimization_config.init_lr=1e-3",
                "optimization_config.max_epochs=1",
                "optimization_config.batch_size=8",
                "optimization_config.validation_batch_size=8",
                "optimization_config.lr_frac_warmup_steps=0.5",
                "do_overwrite=true",
            ]
        )
        assert np.isfinite(tuning_loss)
        assert (pretrain_dir / "finetuning" / "mytask" / "held_out_metrics.json").exists()

    def test_generate_trajectories_cli(self, pipeline_dir):
        root, save_dir, yaml_fp = pipeline_dir
        pretrain_dir = root / "exp" / "pretrain"
        assert pretrain_dir.exists(), "pretrain test must run first"
        out_dir = generate_trajectories_main(
            [
                f"load_from_model_dir={pretrain_dir}",
                "task_specific_params.num_samples=2",
                "task_specific_params.max_new_events=4",
                "optimization_config.validation_batch_size=8",
                "do_overwrite=true",
            ]
        )
        fps = sorted((out_dir / "tuning").glob("sample_*.parquet"))
        assert len(fps) == 2
        df = pd.read_parquet(fps[0])
        assert "dynamic_indices" in df.columns and len(df) > 0


class TestSweepLauncher:
    def test_collapse_cfg(self):
        assert collapse_cfg("bar", {"values": "vals"}) == {"bar": {"values": "vals"}}
        assert collapse_cfg(
            "foo", {"bar": {"baz": {"values": "v"}}, "biz": {"max": "MX"}}
        ) == {"foo.bar.baz": {"values": "v"}, "foo.biz": {"max": "MX"}}
        assert collapse_cfg("foo", {"bar": {"value": None}}) == {}
        with pytest.raises(TypeError, match="Misconfigured"):
            collapse_cfg("foo", None)

    def test_sample_param(self):
        rng = np.random.default_rng(0)
        assert sample_param({"value": 5}, rng) == 5
        assert sample_param({"value": "null"}, rng) is None
        assert sample_param({"values": [1, 2, 3]}, rng) in (1, 2, 3)
        assert 2 <= sample_param({"min": 2, "max": 8}, rng) <= 8
        v = sample_param({"min": 1e-6, "max": 1e-2, "distribution": "log_uniform_values"}, rng)
        assert 1e-6 <= v <= 1e-2

    def test_writes_commands(self, tmp_path):
        commands = sweep_main([f"sweep_dir={tmp_path}", "n_trials=3"])
        assert len(commands) == 3
        assert all("scripts.pretrain" in c for c in commands)
        trials = json.loads((tmp_path / "sweep_trials.json").read_text())
        assert len(trials) == 3
        assert (tmp_path / "sweep_commands.sh").exists()

    def test_generated_overrides_load_into_pretrain_config(self, tmp_path):
        """Every sampled trial's overrides must structure into PretrainConfig —
        guards against bogus key prefixes from defaults-list resolution."""
        import json as _json

        from eventstreamgpt_tpu.training import PretrainConfig
        from eventstreamgpt_tpu.utils.config_tool import load_config

        sweep_main([f"sweep_dir={tmp_path}", "n_trials=2"])
        trials = _json.loads((tmp_path / "sweep_trials.json").read_text())
        for trial in trials:
            overrides = [
                f"{k}={_json.dumps(v) if not isinstance(v, str) else v}"
                for k, v in trial.items()
                if v is not None
            ]
            cfg = load_config(PretrainConfig, overrides=overrides)
            assert "head_dim" in cfg.config
            assert 8 <= cfg.optimization_config.batch_size <= 128


class TestSubsetsPreparer:
    def test_generates_commands(self, tmp_path):
        initial = tmp_path / "initial"
        initial.mkdir()
        (initial / "pretrain_config.yaml").write_text(
            "experiment_dir: " + str(tmp_path / "exp") + "\nseed: 1\n"
        )
        commands = subsets_main(
            [
                f"initial_model_path={initial}",
                "subset_sizes=[10, 20]",
                "seeds=2",
                "experiment_name=subsets",
                "few_shot_commands.fine_tuning_task_names=[taskA]",
            ]
        )
        assert len(commands["pretrain"]) == 4  # 2 sizes × 2 seeds
        assert len(commands["finetune"]) == 4 * 8  # × subset size grid
        runs_dir = tmp_path / "exp" / "subsets"
        assert (runs_dir / "pretrain_commands.sh").exists()
        cfg = (runs_dir / "subset_10" / "seed_0" / "pretrain_config_source.yaml").read_text()
        assert "train_subset_size: 10" in cfg
