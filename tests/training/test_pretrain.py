"""End-to-end pretraining harness tests on the reference sample DL cache.

Uses the reference-built sample dataset artifacts (the interop fixture; the
tuning split doubles as a train split since the reference cache ships no
train files). Runs the full ``train()`` driver: config dumps, multi-device
data-parallel train steps (the conftest provisions an 8-device CPU mesh;
batch size 4 → 4-way sharding), tuning eval, checkpointing, save_pretrained,
final validation metric JSONs, and checkpoint resume.
"""

import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.config import (
    MetricsConfig,
    OptimizationConfig,
    StructuredTransformerConfig,
)
from eventstreamgpt_tpu.training import (
    PretrainConfig,
    TrainCheckpointManager,
    TrainState,
    build_model,
    data_parallel_mesh,
    load_pretrained,
    save_pretrained,
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
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    # The reference cache ships no train split; reuse tuning as train.
    shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / "train_0.parquet")
    return dst


def make_pretrain_config(sample_dir, save_dir, **opt_kwargs):
    opt_defaults = dict(
        init_lr=1e-3,
        max_epochs=2,
        batch_size=4,
        validation_batch_size=4,
        lr_frac_warmup_steps=0.5,
        patience=None,
    )
    opt_defaults.update(opt_kwargs)
    return PretrainConfig(
        seed=1,
        config=dict(MODEL_KWARGS),
        optimization_config=OptimizationConfig(**opt_defaults),
        data_config=PytorchDatasetConfig(save_dir=sample_dir, max_seq_len=16, min_seq_len=2),
        pretraining_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        final_validation_metrics_config=MetricsConfig(n_auc_thresholds=11),
        experiment_dir=str(save_dir),
        save_dir=str(save_dir / "pretrain"),
        trainer_config={"log_every_n_steps": 1, "checkpoint_every_n_steps": 100},
    )


class TestCheckpoint:
    def test_save_load_pretrained_round_trip(self, sample_dir, tmp_path):
        config = StructuredTransformerConfig(**MODEL_KWARGS)
        ds = JaxDataset(
            PytorchDatasetConfig(save_dir=sample_dir, max_seq_len=16, min_seq_len=2), "tuning"
        )
        config.set_to_dataset(ds)
        model = build_model(config)
        batch = next(ds.batches(2, shuffle=False))
        params = model.init(jax.random.PRNGKey(0), batch)

        save_pretrained(tmp_path / "model", params, config=config)
        assert (tmp_path / "model" / "config.json").exists()

        loaded, loaded_config = load_pretrained(tmp_path / "model", params_template=params)
        # Vocabulary re-normalization introduces ~1e-16 float jitter in
        # obs_frequencies on round-trip; compare everything else exactly.
        d1, d2 = config.to_dict(), loaded_config.to_dict()
        d1.pop("measurement_configs"), d2.pop("measurement_configs")
        assert d1 == d2
        assert set(loaded_config.measurement_configs) == set(config.measurement_configs)
        for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # Loaded params run the model identically.
        out_a = model.apply(params, batch)
        out_b = model.apply(loaded, batch)
        np.testing.assert_allclose(float(out_a.loss), float(out_b.loss), rtol=1e-6)

    def test_manager_resume_latest(self, tmp_path):
        mgr = TrainCheckpointManager(tmp_path / "ck", max_to_keep=2)
        state = {"step": np.asarray(0), "params": {"w": np.arange(4.0)}}
        assert mgr.latest_step() is None
        mgr.save(1, state, metadata={"epoch": 0})
        state2 = {"step": np.asarray(2), "params": {"w": np.arange(4.0) * 2}}
        mgr.save(2, state2, metadata={"epoch": 1})
        mgr.wait_until_finished()
        assert mgr.latest_step() == 2
        restored, step = mgr.restore(state)
        assert step == 2
        np.testing.assert_array_equal(restored["params"]["w"], np.arange(4.0) * 2)
        assert mgr.metadata(2) == {"epoch": 1}
        mgr.close()


class TestTrainDriver:
    def test_end_to_end(self, sample_dir, tmp_path):
        cfg = make_pretrain_config(sample_dir, tmp_path)
        tuning_loss, tuning_metrics, held_out_metrics = train(cfg)

        assert tuning_loss is not None and np.isfinite(tuning_loss)
        save_dir = Path(cfg.save_dir)
        for fname in (
            "config.json",
            "data_config.json",
            "optimization_config.json",
            "pretraining_metrics_config.json",
            "final_validation_metrics_config.json",
            "tuning_metrics.json",
            "held_out_metrics.json",
            "train_log.jsonl",
        ):
            assert (save_dir / fname).exists(), fname
        assert (save_dir / "pretrained_weights").exists()

        # Final validation produced quality metrics beyond the loss.
        assert "tuning_loss" in tuning_metrics
        assert any(k.endswith("_cls_NLL") for k in tuning_metrics), tuning_metrics
        assert "held_out_loss" in held_out_metrics

        # The train log recorded step-level throughput records.
        records = [json.loads(line) for line in (save_dir / "train_log.jsonl").open()]
        train_recs = [r for r in records if r["split"] == "train"]
        assert train_recs and "events_per_sec" in train_recs[0] and "lr" in train_recs[0]

        # The saved model reloads and evaluates.
        ds = JaxDataset(cfg.data_config, "tuning")
        config = StructuredTransformerConfig.from_json_file(save_dir / "config.json")
        model = build_model(config)
        batch = next(ds.batches(4, shuffle=False))
        template = model.init(jax.random.PRNGKey(0), batch)
        params, _ = load_pretrained(save_dir, params_template=template)
        out = model.apply(params, batch)
        assert np.isfinite(float(out.loss))

    def test_resume_from_checkpoint(self, sample_dir, tmp_path):
        cfg = make_pretrain_config(sample_dir, tmp_path, max_epochs=1)
        cfg.do_final_validation_on_metrics = False
        train(cfg)

        # Second run with more epochs resumes from the saved state instead of
        # restarting: it should pick up at epoch 1.
        cfg2 = make_pretrain_config(sample_dir, tmp_path, max_epochs=2)
        cfg2.do_final_validation_on_metrics = False
        cfg2.do_overwrite = True
        train(cfg2)

        records = [
            json.loads(line) for line in (Path(cfg2.save_dir) / "train_log.jsonl").open()
        ]
        epochs_seen = {r["epoch"] for r in records if r["split"] == "train"}
        assert 1 in epochs_seen
        # The resumed run must not re-run epoch 0 training steps after resume:
        # records are appended in order, so the last train record's epoch is 1.
        assert [r for r in records if r["split"] == "train"][-1]["epoch"] == 1

    def test_mid_epoch_preemption_resume(self, sample_dir, tmp_path):
        """Resume from a mid-epoch (preemption) checkpoint re-enters the same
        epoch and skips exactly the batches already trained on."""
        cfg = make_pretrain_config(sample_dir, tmp_path, max_epochs=1)
        cfg.do_final_validation_on_metrics = False
        cfg.trainer_config = {
            "log_every_n_steps": 1,
            "checkpoint_every_n_steps": 1,
            "max_checkpoints_to_keep": 50,
        }
        train(cfg)
        save_dir = Path(cfg.save_dir)

        # Simulate preemption after step 1: drop the later checkpoint so the
        # latest checkpoint is the mid-epoch one (epoch 0, 1 batch done).
        ck_root = save_dir / "model_checkpoints"
        for step_dir in ck_root.iterdir():
            if step_dir.is_dir() and step_dir.name.isdigit() and int(step_dir.name) > 1:
                shutil.rmtree(step_dir)
        meta1 = json.loads((ck_root / "metadata_1.json").read_text())
        assert meta1 == {"epoch": 0, "epoch_complete": False, "step_in_epoch": 1}
        (save_dir / "train_log.jsonl").unlink()

        cfg2 = make_pretrain_config(sample_dir, tmp_path, max_epochs=1)
        cfg2.do_final_validation_on_metrics = False
        cfg2.do_overwrite = True
        cfg2.trainer_config = {"log_every_n_steps": 1, "checkpoint_every_n_steps": 100}
        train(cfg2)

        records = [
            json.loads(line) for line in (save_dir / "train_log.jsonl").open()
        ]
        train_recs = [r for r in records if r["split"] == "train"]
        # Epoch 0 has 3 batches on the committed sample (96 train subjects);
        # 1 was done pre-preemption → exactly steps 2 and 3 remain.
        assert [(r["epoch"], r["step"]) for r in train_recs] == [(0, 2), (0, 3)]

    def test_early_stopping(self, sample_dir, tmp_path):
        cfg = make_pretrain_config(sample_dir, tmp_path, max_epochs=50, patience=0, init_lr=1e-12)
        # Negligible LR with patience 0: no improvement after epoch 1 → stop early.
        cfg.do_final_validation_on_metrics = False
        train(cfg)
        records = [
            json.loads(line) for line in (Path(cfg.save_dir) / "train_log.jsonl").open()
        ]
        tuning_recs = [r for r in records if r["split"] == "tuning"]
        assert len(tuning_recs) < 50

    def test_eval_is_deterministic_across_passes(self, sample_dir):
        """Random subsequence crops are pinned during eval, so repeated eval
        passes at the same params produce identical losses (early stopping
        and final-validation comparability)."""
        from eventstreamgpt_tpu.training import evaluate, make_eval_step

        config = StructuredTransformerConfig(**MODEL_KWARGS)
        # max_seq_len 4 forces subsequence sampling on nearly every subject.
        ds = JaxDataset(
            PytorchDatasetConfig(save_dir=sample_dir, max_seq_len=4, min_seq_len=2), "tuning"
        )
        config.set_to_dataset(ds)
        model = build_model(config)
        batch = next(ds.batches(4, shuffle=False))
        params = model.init(jax.random.PRNGKey(0), batch)
        es = make_eval_step(model)
        mc = MetricsConfig(do_skip_all_metrics=True)
        m1 = evaluate(es, params, ds, 4, config, mc, "tuning", key=jax.random.PRNGKey(0))
        m2 = evaluate(es, params, ds, 4, config, mc, "tuning", key=jax.random.PRNGKey(7))
        assert m1["tuning_loss"] == pytest.approx(m2["tuning_loss"], rel=1e-6)

    def test_multi_device_mesh_is_used(self):
        mesh = data_parallel_mesh(4, 4)
        assert mesh.devices.size == min(4, len(jax.devices()))

    def test_tensor_parallel_training(self, sample_dir, tmp_path):
        """train() with tensor_parallel_shards=2 runs on a dp×tp mesh and
        produces a finite tuning loss."""
        cfg = make_pretrain_config(sample_dir, tmp_path, max_epochs=1)
        cfg.do_final_validation_on_metrics = False
        cfg.trainer_config = {"log_every_n_steps": 1, "tensor_parallel_shards": 2}
        train(cfg)
        records = [
            json.loads(line) for line in (Path(cfg.save_dir) / "train_log.jsonl").open()
        ]
        tuning = [r for r in records if r["split"] == "tuning"]
        assert tuning and np.isfinite(tuning[-1]["tuning_loss"])
