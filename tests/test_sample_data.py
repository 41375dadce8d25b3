"""The shipped ``sample_data/`` quickstart artifact stays valid.

The repo ships a pre-built exemplar dataset (the analog of the reference's
``sample_data``; regenerable via
``scripts/make_sample_data.py``) that the tutorial anchors on. These tests
pin the artifact's contract: it parses with the production classes, feeds
the training stack to a finite loss, and its task dataframe + labeler file
load through the task machinery.
"""

import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from eventstreamgpt_tpu.data import Dataset, JaxDataset, PytorchDatasetConfig, VocabularyConfig

SAMPLE = Path(__file__).resolve().parent.parent / "sample_data"
PROCESSED = SAMPLE / "processed" / "sample"

pytestmark = pytest.mark.skipif(
    not PROCESSED.exists(), reason="sample_data artifact not built"
)


@pytest.fixture(scope="module")
def sample_copy(tmp_path_factory):
    """A throwaway copy — task-window caching writes for_task/ next to the
    DL cache, and tests must not mutate the committed artifact."""
    dst = tmp_path_factory.mktemp("sample_data_copy")
    shutil.copytree(PROCESSED, dst / "sample")
    return dst / "sample"


def test_artifact_parses_with_production_classes():
    vc = VocabularyConfig.from_json_file(PROCESSED / "vocabulary_config.json")
    assert vc.total_vocab_size > 10
    ESD = Dataset.load(PROCESSED)
    assert len(ESD.events_df) > 1000
    assert set(ESD.subjects_df.index.names) == {"subject_id"} or "subject_id" in (
        list(ESD.subjects_df.columns) + list(ESD.subjects_df.index.names)
    )


def test_raw_and_yaml_present():
    assert (SAMPLE / "raw" / "subjects.csv").is_file()
    assert (SAMPLE / "raw" / "admit_vitals.csv").is_file()
    assert (SAMPLE / "dataset.yaml").is_file()


def test_trains_one_step_to_finite_loss(sample_copy):
    import jax.numpy as jnp

    from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
    from eventstreamgpt_tpu.training import (
        TrainState,
        build_model,
        build_optimizer,
        data_parallel_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    ds = JaxDataset(
        PytorchDatasetConfig(save_dir=sample_copy, max_seq_len=32, min_seq_len=2), "train"
    )
    config = StructuredTransformerConfig(
        hidden_size=32,
        head_dim=8,
        num_attention_heads=4,
        num_hidden_layers=1,
        intermediate_size=32,
        TTE_generation_layer_type="log_normal_mixture",
        TTE_lognormal_generation_num_components=2,
    )
    config.set_to_dataset(ds)
    model = build_model(config)
    oc = OptimizationConfig(
        init_lr=1e-3, batch_size=8, max_training_steps=2,
        lr_num_warmup_steps=1, lr_frac_warmup_steps=None,
    )
    tx, _ = build_optimizer(oc)
    batch = next(ds.batches(8, shuffle=False))
    params = model.init(jax.random.PRNGKey(0), batch)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    mesh = data_parallel_mesh(8)
    state = replicate(state, mesh)
    step = make_train_step(model, tx)
    state, loss = step(state, shard_batch(batch, mesh), jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


def test_task_df_and_labeler_load(sample_copy):
    ds = JaxDataset(
        PytorchDatasetConfig(
            save_dir=sample_copy, max_seq_len=32, min_seq_len=2,
            task_df_name="high_utilization",
        ),
        "train",
    )
    batch = next(ds.batches(4, shuffle=False))
    assert "high_utilization" in batch.stream_labels
    labels = np.asarray(batch.stream_labels["high_utilization"])
    assert set(np.unique(labels)).issubset({0, 1})

    # The labeler file next to the task df imports and instantiates.
    from eventstreamgpt_tpu.training.zero_shot_evaluator import import_class_from_file

    labeler_cls = import_class_from_file(
        sample_copy / "task_dfs" / "high_utilization_labeler.py", "TaskLabeler"
    )
    assert labeler_cls.__name__ == "TaskLabeler"
