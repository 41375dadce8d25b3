"""Evaluation layer tests.

MCF/CRPS golden values are taken from the reference module's own doctests
(``/root/reference/EventStream/evaluation/MCF_evaluation.py``), so the pandas
rebuild is checked against the polars implementation's documented outputs.
The trajectory driver test runs generation end-to-end on the sample cache.
"""

import shutil
from pathlib import Path

import jax
import numpy as np
import pandas as pd
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.evaluation import (
    GenerateConfig,
    align_time_and_eval_predicates,
    crps,
    eval_range,
    generate_trajectories,
    get_MCF,
    get_MCF_coordinates,
    get_aligned_timestamps,
)
from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
from eventstreamgpt_tpu.training import build_model, save_pretrained

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)


class TestCRPS:
    def test_single_sample_is_abs_error(self):
        np.testing.assert_array_equal(crps(np.array([[-2]]), np.array([0])), [2])

    def test_reference_doctest_values(self):
        np.testing.assert_allclose(
            crps(np.array([[-2], [np.nan], [np.nan], [1], [2]]), np.array([0])), [0.77777778]
        )
        np.testing.assert_allclose(
            crps(np.array([[-2], [-1], [0], [1], [2]]), np.array([0])), [0.4]
        )
        true = np.array([-2, 0, -2, np.nan])
        samples = np.array(
            [
                [-1, 1, -1, -1],
                [1, -2, 1, 1],
                [2, -20, np.nan, 2],
                [0, 10, 0, 0],
                [3, 1, 3, 3],
                [1, 1, 1, 1],
            ]
        )
        np.testing.assert_allclose(
            crps(samples, true), [2.27777778, 1.41666667, 2.08, np.nan], rtol=1e-6
        )

    def test_shape_mismatch_raises(self):
        with pytest.raises(ValueError, match="must match"):
            crps(np.array([-2, -1, 0, 1, 2]), np.array([1.0, 2, 3, 4]))


class TestEvalRange:
    def test_reference_doctest_values(self):
        v = np.array([0.1])
        assert eval_range(True, v)[0]
        assert not eval_range(False, v)[0]
        assert not eval_range((1, 2), v)[0]
        assert eval_range((None, 2), v)[0]
        one = np.array([1.0])
        assert not eval_range((1, 2), one)[0]
        assert not eval_range(((1, False), 2), one)[0]
        assert eval_range(((1, True), 2), one)[0]
        three = np.array([3.0])
        assert not eval_range((1, 2), three)[0]
        assert eval_range((1, None), three)[0]


class TestAlignAndPredicates:
    def _df(self):
        return pd.DataFrame(
            {
                "subject_id": [1, 2, 3],
                "time": [[0.0, 10, 20], [0.0, 100], [0.0, 1, 2, 3]],
                "dynamic_indices": [
                    [[1, 2], [3, 3, 2], [4]],
                    [[1], [3]],
                    [[2, 3], [1], [8], [3, 1, 1]],
                ],
                "dynamic_values": [
                    [[None, 0], [-1, 4, 0.2], [None]],
                    [[None], [3]],
                    [[-0.1, 10], [None], [None], [6, None, None]],
                ],
                "align_time": [10, 100, 1.5],
            }
        )

    def test_reference_doctest_values(self):
        out = align_time_and_eval_predicates(self._df(), {3: (3.5, None), 1: True})
        assert out["subject_id"].tolist() == [1, 2, 3]
        assert out.iloc[0]["time"] == [-10.0, 0.0, 10.0]
        assert out.iloc[0]["pred_3"] == [False, True, False]
        assert out.iloc[0]["pred_1"] == [True, False, False]
        assert out.iloc[1]["time"] == [-100.0, 0.0]
        assert out.iloc[1]["pred_3"] == [False, False]
        assert out.iloc[1]["pred_1"] == [True, False]
        assert out.iloc[2]["time"] == [-1.5, -0.5, 0.5, 1.5]
        assert out.iloc[2]["pred_3"] == [True, False, False, True]
        assert out.iloc[2]["pred_1"] == [False, True, False, True]


class TestAlignedTimestamps:
    def test_union_and_downsample(self):
        control = [[-10.0, 0, 1, 2], [-105, 1, 4]]
        s1 = [[8, 21.1], [46, 132, 188, 200.0]]
        s2 = [[1.1], None]
        out = get_aligned_timestamps(control, s1, s2)
        assert out == [-105.0, -10.0, 0.0, 1.0, 1.1, 2.0, 4.0, 8.0, 21.1, 46.0, 132.0, 188.0, 200.0]
        np.random.seed(1)
        small = get_aligned_timestamps(control, s1, s2, n_timestamps=4)
        assert len(small) == 4 and small == sorted(small)


class TestGetMCF:
    def test_reference_doctest_values(self):
        df_1 = pd.DataFrame(
            {
                "subject_id": [1, 2],
                "time": [[-3.2, -2, 0, 10.2], [0.0, 1.0]],
                "pred_1": [[False, True, True, False], [True, True]],
                "pred_2": [[True, False, False, True], [False, False]],
            }
        )
        df_2 = pd.DataFrame(
            {
                "subject_id": [1, 2],
                "time": [[-1.9, 0.0, 0.2], [-10.0, 0.0, 2.3]],
                "pred_1": [[False, True, False], [True, True, False]],
                "pred_2": [[True, False, True], [True, False, False]],
            }
        )
        censor, mcf = get_MCF([-3, 3, 6, 10], ["pred_1", "pred_2"], df_1, df_2)
        np.testing.assert_array_equal(
            censor,
            [
                [[True, True, True, True, True], [True, True, False, False, False]],
                [[True, True, False, False, False], [True, True, False, False, False]],
            ],
        )
        expected_mcf = np.array(
            [
                [
                    [[0.0, 1.0], [2.0, 0.0], [0.0, 0.0], [0.0, 0.0], [0.0, 1.0]],
                    [[np.nan, np.nan], [2.0, 0.0], [0.0, 0.0], [0.0, 0.0], [np.nan, np.nan]],
                ],
                [
                    [[np.nan, np.nan], [1.0, 2.0], [0.0, 0.0], [0.0, 0.0], [0.0, 0.0]],
                    [[1.0, 1.0], [1.0, 0.0], [0.0, 0.0], [0.0, 0.0], [0.0, 0.0]],
                ],
            ]
        )
        np.testing.assert_allclose(mcf, expected_mcf)


class TestGetMCFCoordinates:
    def test_reference_doctest_shapes(self):
        control_df = pd.DataFrame(
            {
                "subject_id": [1, 2, 3],
                "control_align_idx": [1, 1, 0],
                "time": [[0.0, 10, 20], [0.0, 100], [0.0, 1, 2, 3]],
                "dynamic_indices": [
                    [[1, 2], [3, 3, 2], [4]],
                    [[1], [3]],
                    [[2, 3], [1], [8], [3, 1, 1]],
                ],
                "dynamic_values": [
                    [[None, 0], [-1, 4, 0.2], [None]],
                    [[None], [3]],
                    [[-0.1, 10], [None], [None], [6, None, None]],
                ],
            }
        )
        sample_df_1 = pd.DataFrame(
            {
                "subject_id": [2, 1, 3],
                "time": [[200, 300, 400], [18, 24, 33], [2.1, 3, 4.1]],
                "dynamic_indices": [[[1], [3], [1, 2]], [[3], [2], [1]], [[2, 3], [], [3, 3]]],
                "dynamic_values": [
                    [[None], [3.1], [None, 0.03]],
                    [[0], [0.21], [None]],
                    [[-0.1, 10], [], [6, -1]],
                ],
            }
        )
        sample_df_2 = pd.DataFrame(
            {
                "subject_id": [3, 1, 2],
                "time": [[5.1, 6, 7.1], [11, 14, 23], [110, 202, 250]],
                "dynamic_indices": [[[], [1, 2], [1]], [[1, 2], [1], [1]], [[1], [3], [3, 3]]],
                "dynamic_values": [
                    [[], [None, 0.1], [None]],
                    [[None, -0.04], [None], [None]],
                    [[None], [13.1], [0.5, 0.3]],
                ],
            }
        )
        out = get_MCF_coordinates(
            control_df, [sample_df_1, sample_df_2], {3: (3.5, None), 1: True}
        )
        subject_ids, Ts, dyn_idx, c_censor, c_mcf, s_censor, s_mcf = out
        assert subject_ids == [1, 2, 3]
        # The reference doctest reports 20 timestamps, silently missing
        # sample_df_1/subject-3's aligned times (2.1, 4.1) — inconsistent
        # with its own documented "union of all observed times" contract
        # (an old-polars join artifact). This build honors the contract:
        # the full union of aligned control+sample times, 22 values.
        assert len(Ts) == 22
        expected = [-100.0, -10.0, 0.0, 1.0, 2.0, 2.1, 3.0, 4.0, 4.1, 5.1, 6.0,
                    7.1, 8.0, 10.0, 13.0, 14.0, 23.0, 100.0, 102.0, 150.0, 200.0, 300.0]
        np.testing.assert_allclose(Ts, expected)
        assert dyn_idx == [3, 1]
        assert c_censor.shape == (1, 3, 23)
        assert c_mcf.shape == (1, 3, 23, 2)
        assert s_censor.shape == (2, 3, 23)
        assert s_mcf.shape == (2, 3, 23, 2)


class TestConvertToDLDF:
    def test_reference_doctest_values(self):
        from eventstreamgpt_tpu.data.types import EventStreamBatch

        batch = EventStreamBatch(
            event_mask=np.array(
                [[True, True, True], [True, True, False], [True, False, False], [False, False, False]]
            ),
            time_delta=np.array(
                [[1.0, 2.0, 3.0], [1.0, 5.0, 0.0], [2.3, 0.0, 0.0], [0.0, 0.0, 0.0]]
            ),
            static_indices=np.array([[0, 1], [1, 2], [1, 3], [0, 5]]),
            static_measurement_indices=np.array([[0, 1], [1, 1], [1, 1], [0, 2]]),
            dynamic_indices=np.array(
                [
                    [[0, 1], [1, 2], [2, 3]],
                    [[0, 1], [1, 5], [0, 0]],
                    [[0, 2], [0, 0], [0, 0]],
                    [[0, 0], [0, 0], [0, 0]],
                ]
            ),
            dynamic_measurement_indices=np.array(
                [
                    [[0, 1], [1, 2], [2, 3]],
                    [[0, 1], [1, 2], [0, 0]],
                    [[0, 2], [0, 0], [0, 0]],
                    [[0, 0], [0, 0], [0, 0]],
                ]
            ),
            dynamic_values=np.array(
                [
                    [[0.0, 1.0], [1.0, 2.0], [0.0, 0.0]],
                    [[0.0, 1.0], [1.0, 0.0], [0.0, 0.0]],
                    [[0.0, 1.0], [0.0, 0.0], [0.0, 0.0]],
                    [[0.0, 0.0], [0.0, 0.0], [0.0, 0.0]],
                ]
            ),
            dynamic_values_mask=np.array(
                [
                    [[False, True], [True, True], [False, False]],
                    [[False, True], [True, False], [False, False]],
                    [[False, True], [False, False], [False, False]],
                    [[False, False], [False, False], [False, False]],
                ]
            ),
            start_time=np.array([0.0, 10.0, 3.0, 2.2]),
        )
        df = batch.convert_to_DL_DF()
        assert df["time_delta"].tolist() == [[1.0, 2.0, 3.0], [1.0, 5.0], [2.3], []]
        assert df["static_indices"].tolist() == [[1], [1, 2], [1, 3], [5]]
        assert df["static_measurement_indices"].tolist() == [[1], [1, 1], [1, 1], [2]]
        assert df["dynamic_indices"].tolist() == [
            [[1], [1, 2], [2, 3]],
            [[1], [1, 5]],
            [[2]],
            [],
        ]
        assert df["dynamic_values"].tolist() == [
            [[1.0], [1.0, 2.0], [None, None]],
            [[1.0], [1.0, None]],
            [[1.0]],
            [],
        ]
        assert df["start_time"].tolist() == [0.0, 10.0, 3.0, pytest.approx(2.2)]


class TestTrajectoryDriver:
    def test_end_to_end(self, tmp_path):
        dst = tmp_path / "traj_sample"
        dst.mkdir()
        for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
            shutil.copy(REF_SAMPLE / name, dst / name)
        shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
        shutil.copytree(
            REF_SAMPLE / "inferred_measurement_metadata", dst / "inferred_measurement_metadata"
        )
        shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / "train_0.parquet")

        data_config = PytorchDatasetConfig(
            save_dir=dst, max_seq_len=12, min_seq_len=2, seq_padding_side="left"
        )
        ds = JaxDataset(data_config, "train")
        config = StructuredTransformerConfig(
            hidden_size=32,
            head_dim=8,
            num_attention_heads=4,
            num_hidden_layers=2,
            intermediate_size=32,
            TTE_generation_layer_type="exponential",
        )
        config.set_to_dataset(ds)
        config.max_seq_len = 16  # 4 generated events
        model = build_model(config)
        batch = next(ds.batches(4, shuffle=False))
        params = model.init(jax.random.PRNGKey(0), batch)
        model_dir = dst / "model"
        save_pretrained(model_dir, params, config=config)
        data_config.to_json_file(model_dir / "data_config.json", do_overwrite=True)

        cfg = GenerateConfig(
            load_from_model_dir=model_dir,
            optimization_config=OptimizationConfig(
                init_lr=1e-3, batch_size=4, validation_batch_size=4,
                max_training_steps=1, lr_num_warmup_steps=0, lr_frac_warmup_steps=None,
            ),
            task_specific_params={"num_samples": 2, "max_new_events": None},
            do_overwrite=True,
        )
        assert cfg.config.task_specific_params["max_new_events"] == 4

        out_dir = generate_trajectories(cfg)
        for split in ("tuning", "held_out"):
            fps = sorted((out_dir / split).glob("sample_*_local_rank_0.parquet"))
            assert len(fps) == 2, split
            df = pd.read_parquet(fps[0])
            assert len(df) == 12  # every tuning/held-out subject of the committed sample
            assert {"time_delta", "dynamic_indices", "dynamic_values", "subject_id"} <= set(
                df.columns
            )
            # Generated continuations extend beyond the prompt window.
            lens = df["time_delta"].map(len)
            assert lens.max() > 12