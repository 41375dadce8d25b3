"""Embedding extractor tests (reference ``lightning_modules/embedding.py``)."""

import shutil
from pathlib import Path

import jax
import numpy as np
import pandas as pd
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
from eventstreamgpt_tpu.training import build_model, load_pretrained, save_pretrained
from eventstreamgpt_tpu.training.embedding import EmbeddingsOnlyModel, embed_batch, get_embeddings
from eventstreamgpt_tpu.training.fine_tuning import FinetuneConfig

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)

from .. import _programs as programs

MODEL_KWARGS = dict(
    hidden_size=32,
    head_dim=8,
    num_attention_heads=4,
    num_hidden_layers=2,
    intermediate_size=32,
    TTE_generation_layer_type="exponential",
)


@pytest.fixture(scope="module")
def emb_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("emb_sample")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / "train_0.parquet")

    data_config = PytorchDatasetConfig(save_dir=dst, max_seq_len=16, min_seq_len=2)
    ds = JaxDataset(data_config, "train")
    config = StructuredTransformerConfig(**MODEL_KWARGS)
    config.set_to_dataset(ds)
    model = build_model(config)
    batch = next(ds.batches(4, shuffle=False))
    params = programs.init(model, jax.random.PRNGKey(0), batch)
    model_dir = dst / "model"
    save_pretrained(model_dir, params, config=config)
    data_config.to_json_file(model_dir / "data_config.json", do_overwrite=True)
    return dst, model_dir


@pytest.fixture(scope="module")
def embedder(emb_dir):
    """The embeddings-only model, a batch and its parameters, built once for the pooling cases."""
    dst, model_dir = emb_dir
    cfg = FinetuneConfig(load_from_model_dir=model_dir, task_df_name="t", data_config_overrides={})
    # No task df on disk — construct the dataset directly without a task.
    cfg.data_config.task_df_name = None
    ds = JaxDataset(cfg.data_config, "tuning")
    config = cfg.config
    config.set_to_dataset(ds)
    model = EmbeddingsOnlyModel(config)
    batch = next(ds.batches(4, shuffle=False))
    return model, programs.init(model, jax.random.PRNGKey(0), batch), config, batch


class TestEmbedBatch:
    @pytest.mark.parametrize("pooling", ["last", "max", "mean", "none"])
    def test_pooling_shapes(self, embedder, pooling):
        model, params, config, batch = embedder
        out = np.asarray(embed_batch(model, params, config, batch, pooling))
        H = config.hidden_size
        if pooling == "none":
            assert out.shape == (4, batch.sequence_length, H)
        else:
            assert out.shape == (4, H)
            assert np.isfinite(out).all()


class TestGetEmbeddings:
    def test_writes_all_splits(self, emb_dir):
        dst, model_dir = emb_dir
        cfg = FinetuneConfig(
            load_from_model_dir=model_dir,
            task_df_name="t",
            data_config_overrides={},
            optimization_config=OptimizationConfig(
                init_lr=1e-3, batch_size=4, validation_batch_size=4,
                max_training_steps=1, lr_num_warmup_steps=0, lr_frac_warmup_steps=None,
            ),
            do_overwrite=True,
        )
        cfg.data_config.task_df_name = None
        written = get_embeddings(cfg)
        assert set(written) == {"train", "tuning", "held_out"}
        for sp, fp in written.items():
            assert fp.exists(), sp
            emb = np.load(fp)
            ds = JaxDataset(cfg.data_config, sp)
            # One embedding per subject, even with a short final batch.
            assert emb.shape == (len(ds), cfg.config.hidden_size)
            assert np.isfinite(emb).all()
