"""Context-parallel (sequence-sharded) training e2e on the virtual mesh.

``trainer_config.context_parallel_shards=N`` + ``attention_implementation=
"ring"`` trains on packed long-context batches with the event axis sharded
over a ``context`` mesh axis and ring attention in the encoder — the
sequence-parallel story the reference lacks entirely (SURVEY §2.10). The e2e
test runs the production ``train()`` driver on sample data with a dp2×cp4
mesh and checks it converges to a finite loss with the full save contract.
"""

import shutil
from pathlib import Path

import numpy as np
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.config import MetricsConfig, OptimizationConfig
from eventstreamgpt_tpu.training import PretrainConfig, train

pytestmark = pytest.mark.slow  # full e2e; excluded from the fast core loop (-m "not slow")

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("cp_sample_ds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / "train_0.parquet")
    shutil.copy(dst / "DL_reps" / "tuning_0.parquet", dst / "DL_reps" / "held_out_0.parquet")
    return dst


MODEL_KWARGS = dict(
    hidden_size=32,
    head_dim=8,
    num_attention_heads=4,
    num_hidden_layers=2,
    intermediate_size=32,
    TTE_generation_layer_type="log_normal_mixture",
    TTE_lognormal_generation_num_components=2,
    attention_implementation="ring",
    attention_dropout=0.0,
    # Packed row length; must divide context_parallel_shards (4).
    max_seq_len=32,
)


def make_cfg(sample_dir, save_dir, **trainer_overrides):
    trainer = {
        "log_every_n_steps": 2,
        "checkpoint_every_n_steps": 1000,
        "context_parallel_shards": 4,
        **trainer_overrides,
    }
    return PretrainConfig(
        seed=1,
        config=dict(MODEL_KWARGS),
        optimization_config=OptimizationConfig(
            init_lr=1e-3,
            max_epochs=2,
            batch_size=2,
            validation_batch_size=4,
            lr_frac_warmup_steps=0.5,
            patience=None,
        ),
        data_config=PytorchDatasetConfig(save_dir=sample_dir, max_seq_len=16, min_seq_len=2),
        pretraining_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        final_validation_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        trainer_config=trainer,
        experiment_dir=str(save_dir),
        save_dir=str(save_dir / "pretrain"),
        do_overwrite=True,
        do_resume_from_checkpoint=False,
    )


class TestContextParallelTraining:
    def test_e2e_ring_packed_training(self, sample_dir, tmp_path):
        """The config.max_seq_len=32 packed rows shard 4-way over `context`;
        the model must train to a finite tuning loss end-to-end."""
        cfg = make_cfg(sample_dir, tmp_path)
        tuning_loss, tm, hm = train(cfg)
        assert tuning_loss is not None and np.isfinite(tuning_loss)
        assert (Path(cfg.save_dir) / "pretrained_weights").exists()
        # Trained on packed batches: the train log records real steps.
        assert (Path(cfg.save_dir) / "train_log.jsonl").exists()

    def test_cp_requires_ring_attention(self, sample_dir, tmp_path):
        cfg = make_cfg(sample_dir, tmp_path / "bad")
        cfg.config["attention_implementation"] = "einsum"
        with pytest.raises(ValueError, match="ring"):
            train(cfg)

    def test_cp_rejects_attention_dropout(self, sample_dir, tmp_path):
        cfg = make_cfg(sample_dir, tmp_path / "bad2")
        cfg.config["attention_dropout"] = 0.1
        with pytest.raises(ValueError, match="attention_dropout"):
            train(cfg)

    def test_cp_and_tp_compose_e2e(self, sample_dir, tmp_path):
        """tensor_parallel_shards=2 x context_parallel_shards=2 trains on a
        data2×context2×model2 mesh: Megatron layouts shard hidden/vocab over
        ``model`` while ring attention shards the event axis over ``context``."""
        cfg = make_cfg(
            sample_dir,
            tmp_path / "tpcp",
            context_parallel_shards=2,
            tensor_parallel_shards=2,
        )
        tuning_loss, _, _ = train(cfg)
        assert tuning_loss is not None and np.isfinite(tuning_loss)
        assert (Path(cfg.save_dir) / "pretrained_weights").exists()

    def test_tp_cp_step_matches_replicated(self):
        """One composed dp2×cp2×tp2 train step equals the replicated
        single-device step on the same model/batch (up to fp rounding):
        the TP/CP layouts change the schedule of the computation, not its
        value."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from __graft_entry__ import _make_model_and_batch
        from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
        from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
        from eventstreamgpt_tpu.parallel import ring_context
        from eventstreamgpt_tpu.training import (
            TrainState,
            build_optimizer,
            make_train_step,
        )
        from eventstreamgpt_tpu.training.pretrain import (
            replicate,
            shard_batch,
            shard_batch_cp,
        )
        from eventstreamgpt_tpu.training.sharding import shard_state

        model, batch = _make_model_and_batch(batch_size=4, seq_len=16)
        cfg = StructuredTransformerConfig.from_dict(
            {
                **model.config.to_dict(),
                "attention_implementation": "ring",
                "attention_dropout": 0.0,
            }
        )
        ring_model = CIPPTForGenerativeSequenceModeling(cfg)
        seg = np.zeros((4, 16), np.int64)
        seg[:, 8:] = 1  # two packed segments per row
        batch = batch.replace(segment_ids=jnp.asarray(seg))
        oc = OptimizationConfig(
            init_lr=1e-3,
            batch_size=4,
            max_training_steps=10,
            lr_num_warmup_steps=1,
            lr_frac_warmup_steps=None,
        )
        # Host copies: make_train_step donates its state, and device_put is
        # an aliasing no-op when the placement already matches — each state
        # must own its buffers.
        params = jax.device_get(ring_model.init(jax.random.PRNGKey(0), batch))

        def fresh_state(tx):
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
            )

        tx, _ = build_optimizer(oc)
        mesh3 = Mesh(
            np.asarray(jax.devices()).reshape(2, 2, 2), ("data", "context", "model")
        )
        state3 = shard_state(fresh_state(tx), mesh3)
        step3 = make_train_step(ring_model, tx)
        with ring_context(mesh3):
            state3, loss3 = step3(state3, shard_batch_cp(batch, mesh3), jax.random.PRNGKey(7))

        mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        tx1, _ = build_optimizer(oc)
        state1 = replicate(fresh_state(tx1), mesh1)
        step1 = make_train_step(ring_model, tx1)
        # The ring model must trace WITHOUT a ring context here (einsum
        # fallback) so the comparison crosses implementations.
        state1, loss1 = step1(state1, shard_batch(batch, mesh1), jax.random.PRNGKey(7))

        np.testing.assert_allclose(float(loss3), float(loss1), rtol=2e-5)
        p3 = jax.device_get(state3.params)
        p1 = jax.device_get(state1.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-6), p3, p1
        )

    def test_packed_training_without_cp(self, sample_dir, tmp_path):
        """use_packed_batches alone (no context sharding) also trains."""
        cfg = make_cfg(
            sample_dir, tmp_path / "packed_only", context_parallel_shards=1, use_packed_batches=True
        )
        cfg.config["attention_implementation"] = "einsum"
        tuning_loss, _, _ = train(cfg)
        assert tuning_loss is not None and np.isfinite(tuning_loss)
