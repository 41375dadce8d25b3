"""Fine-tuning model + harness tests.

Covers: pooling/loss semantics parity against torch recomputation (reference
``fine_tuning_model.py:54-91``), the pretrained-encoder graft, FinetuneConfig
bootstrap from a pretrain save_dir, and the end-to-end finetune driver on the
reference sample cache with a synthetic binary task df.
"""

import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pandas as pd
import pytest
import torch

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
from eventstreamgpt_tpu.models.fine_tuning_model import ESTForStreamClassification
from eventstreamgpt_tpu.training import build_model, load_pretrained, save_pretrained
from eventstreamgpt_tpu.training.fine_tuning import (

    FinetuneConfig,
    StreamClassificationMetrics,
    init_from_pretrained_encoder,
    train,
)

pytestmark = pytest.mark.slow  # full e2e; excluded from the fast core loop (-m "not slow")

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)

MODEL_KWARGS = dict(
    hidden_size=32,
    head_dim=8,
    num_attention_heads=4,
    num_hidden_layers=2,
    intermediate_size=32,
    TTE_generation_layer_type="log_normal_mixture",
    TTE_lognormal_generation_num_components=2,
)


@pytest.fixture(scope="module")
def pretrain_dir(tmp_path_factory):
    """A sample dataset dir + a minimal 'pretrained' model save_dir inside it,
    plus a synthetic binary task df."""
    dst = tmp_path_factory.mktemp("ft_sample")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    for split in ("train",):
        shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / f"{split}_0.parquet")

    # Binary task df over all subjects (parity across splits).
    frames = []
    for split_file in sorted((dst / "DL_reps").glob("*.parquet")):
        frames.append(pd.read_parquet(split_file))
    raw = pd.concat(frames).drop_duplicates("subject_id")
    task_rows = []
    for _, row in raw.iterrows():
        start = pd.Timestamp(row["start_time"])
        times = np.asarray(row["time"], dtype=np.float64)
        task_rows.append(
            {
                "subject_id": row["subject_id"],
                "start_time": start,
                "end_time": start + pd.Timedelta(minutes=float(times[-1])),
                "label": bool(int(row["subject_id"]) % 2),
            }
        )
    (dst / "task_dfs").mkdir()
    pd.DataFrame(task_rows).to_parquet(dst / "task_dfs" / "mytask.parquet")

    # "Pretrain" a generative model for one init and save the contract dir.
    data_config = PytorchDatasetConfig(save_dir=dst, max_seq_len=16, min_seq_len=2)
    ds = JaxDataset(data_config, "train")
    config = StructuredTransformerConfig(**MODEL_KWARGS)
    config.set_to_dataset(ds)
    model = build_model(config)
    batch = next(ds.batches(4, shuffle=False))
    params = model.init(jax.random.PRNGKey(0), batch)

    model_dir = dst / "pretrained_model"
    save_pretrained(model_dir, params, config=config)
    data_config.to_json_file(model_dir / "data_config.json", do_overwrite=True)
    return dst, model_dir


def make_ft_batch(ds, n=4):
    return next(ds.batches(n, shuffle=False))


class TestModel:
    @pytest.fixture(scope="class")
    def ft_setup(self, pretrain_dir):
        dst, model_dir = pretrain_dir
        cfg = FinetuneConfig(
            load_from_model_dir=model_dir,
            task_df_name="mytask",
            data_config_overrides={},
        )
        ds = JaxDataset(cfg.data_config, "tuning")
        cfg.config.set_to_dataset(ds)
        return cfg, ds

    @pytest.mark.parametrize("pooling", ["cls", "last", "max", "mean"])
    def test_pooling_and_loss_match_torch(self, ft_setup, pooling):
        cfg, ds = ft_setup
        config = cfg.config
        config.task_specific_params = {"pooling_method": pooling}
        model = ESTForStreamClassification(config)
        batch = make_ft_batch(ds)
        params = model.init(jax.random.PRNGKey(0), batch)
        out = model.apply(params, batch)

        assert np.isfinite(float(out.loss))
        # Binary task → scalar logits per subject.
        assert np.asarray(out.preds).shape == (batch.batch_size,)

        # Torch-recomputed BCE on the same logits/labels.
        logits_t = torch.tensor(np.asarray(out.preds))
        labels_t = torch.tensor(np.asarray(out.labels), dtype=torch.float32)
        expected = torch.nn.BCEWithLogitsLoss()(logits_t, labels_t)
        np.testing.assert_allclose(float(out.loss), float(expected), rtol=1e-5)

    def test_multiclass_loss_matches_torch(self, ft_setup):
        cfg, ds = ft_setup
        config = cfg.config
        # Rewire as a 3-class task.
        config.id2label = {0: "a", 1: "b", 2: "c"}
        config.num_labels = 3
        config.problem_type = "single_label_classification"
        try:
            model = ESTForStreamClassification(config)
            batch = make_ft_batch(ds)
            labels = np.asarray(batch.stream_labels["label"]).astype(np.int64) % 3
            batch = batch.replace(stream_labels={"label": labels})
            params = model.init(jax.random.PRNGKey(0), batch)
            out = model.apply(params, batch)
            logits_t = torch.tensor(np.asarray(out.preds))
            labels_t = torch.tensor(labels)
            expected = torch.nn.CrossEntropyLoss()(logits_t, labels_t)
            np.testing.assert_allclose(float(out.loss), float(expected), rtol=1e-5)
        finally:
            config.id2label = {0: False, 1: True}
            config.num_labels = 2

    def test_valid_mask_excludes_fill_rows(self, ft_setup):
        cfg, ds = ft_setup
        config = cfg.config
        config.task_specific_params = {"pooling_method": "mean"}
        model = ESTForStreamClassification(config)
        batch = make_ft_batch(ds)
        params = model.init(jax.random.PRNGKey(0), batch)

        full = model.apply(params, batch)
        # Mark the last row invalid: the loss must equal the valid-only mean.
        B = batch.batch_size
        valid = np.ones(B, dtype=bool)
        valid[-1] = False
        masked = batch.replace(valid_mask=valid)
        out = model.apply(params, masked)

        logits_t = torch.tensor(np.asarray(full.preds))[:-1]
        labels_t = torch.tensor(np.asarray(full.labels), dtype=torch.float32)[:-1]
        expected = torch.nn.BCEWithLogitsLoss()(logits_t, labels_t)
        np.testing.assert_allclose(float(out.loss), float(expected), rtol=1e-5)


class TestPretrainedGraft:
    def test_encoder_weights_transfer(self, pretrain_dir):
        dst, model_dir = pretrain_dir
        cfg = FinetuneConfig(
            load_from_model_dir=model_dir, task_df_name="mytask", data_config_overrides={}
        )
        ds = JaxDataset(cfg.data_config, "tuning")
        cfg.config.set_to_dataset(ds)
        model = ESTForStreamClassification(cfg.config)
        batch = make_ft_batch(ds)
        fresh = model.init(jax.random.PRNGKey(1), batch)
        grafted = init_from_pretrained_encoder(fresh, model_dir)

        pretrained, _ = load_pretrained(model_dir)
        a = jax.tree_util.tree_leaves(grafted["params"]["encoder"])
        b = jax.tree_util.tree_leaves(pretrained["params"]["encoder"])
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # Logit layer stays freshly initialized.
        np.testing.assert_array_equal(
            np.asarray(grafted["params"]["logit_layer"]["kernel"]),
            np.asarray(fresh["params"]["logit_layer"]["kernel"]),
        )


class TestFinetuneDriver:
    def test_end_to_end(self, pretrain_dir):
        dst, model_dir = pretrain_dir
        cfg = FinetuneConfig(
            load_from_model_dir=model_dir,
            task_df_name="mytask",
            data_config_overrides={},
            optimization_config=OptimizationConfig(
                init_lr=1e-3,
                max_epochs=2,
                batch_size=4,
                validation_batch_size=4,
                lr_frac_warmup_steps=0.5,
            ),
            do_overwrite=True,
        )
        tuning_loss, tuning_metrics, held_out_metrics = train(cfg)

        assert tuning_loss is not None and np.isfinite(tuning_loss)
        save_dir = Path(cfg.save_dir)
        assert save_dir == model_dir / "finetuning" / "mytask"
        for fname in (
            "config.json",
            "data_config.json",
            "optimization_config.json",
            "tuning_metrics.json",
            "held_out_metrics.json",
        ):
            assert (save_dir / fname).exists(), fname
        assert (save_dir / "pretrained_weights").exists()
        # Binary task metrics present.
        assert "tuning_AUROC" in tuning_metrics or "tuning_accuracy" in tuning_metrics
        assert any(k.startswith("held_out") for k in held_out_metrics)


class TestStreamClassificationMetrics:
    def test_binary_set(self):
        config = StructuredTransformerConfig(
            **MODEL_KWARGS,
            finetuning_task="t",
        )
        config.problem_type = "single_label_classification"
        config.num_labels = 2
        config.id2label = {0: False, 1: True}
        m = StreamClassificationMetrics(config, "tuning")
        assert set(m.metrics) == {"AUROC", "accuracy", "AUPRC"}

        from types import SimpleNamespace

        rng = np.random.default_rng(0)
        labels = rng.integers(0, 2, 64).astype(np.float32)
        preds = labels * 2 - 1 + rng.normal(0, 0.5, 64)
        m.update(SimpleNamespace(loss=0.5, preds=preds, labels=labels))
        out = m.compute()
        assert out["tuning_AUROC"] > 0.8
        assert out["tuning_loss"] == 0.5

    def test_multilabel_set(self):
        config = StructuredTransformerConfig(**MODEL_KWARGS)
        config.problem_type = "multi_label_classification"
        config.num_labels = 3
        m = StreamClassificationMetrics(config, "held_out")
        assert "micro_AUROC" in m.metrics and "macro_AUPRC" in m.metrics
