"""End-to-end CI model tests on the reference's sample dataset (Milestone A).

Covers: config.set_to_dataset wiring, a jitted forward pass with finite
losses, a short optax training loop with decreasing loss, and generation-mode
forwards — the minimum end-to-end slice of SURVEY.md §7.5.
"""

import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return JaxDataset(PytorchDatasetConfig(save_dir=dst, max_seq_len=24), "tuning")


@pytest.fixture(scope="module")
def model_and_params(dataset):
    config = StructuredTransformerConfig(
        max_seq_len=24,
        hidden_size=32,
        head_dim=8,
        num_attention_heads=4,
        num_hidden_layers=2,
        intermediate_size=32,
        TTE_generation_layer_type="log_normal_mixture",
        TTE_lognormal_generation_num_components=2,
    )
    config.set_to_dataset(dataset)
    model = CIPPTForGenerativeSequenceModeling(config)
    batch = dataset.collate_indices(np.arange(min(2, len(dataset))))
    params = model.init(jax.random.PRNGKey(0), batch)
    return config, model, params


class TestEndToEnd:
    def test_set_to_dataset(self, dataset, model_and_params):
        config, _, _ = model_and_params
        assert config.vocab_size == 27  # the committed artifact's
        assert config.max_seq_len == 24
        assert config.mean_log_inter_event_time_min == dataset.mean_log_inter_event_time_min
        assert set(config.measurements_idxmap) == set(dataset.vocabulary_config.measurements_idxmap)

    def test_forward_loss_finite(self, dataset, model_and_params):
        _, model, params = model_and_params
        batch = dataset.collate_indices(np.arange(min(4, len(dataset))))
        out = jax.jit(model.apply)(params, batch)
        assert np.isfinite(float(out.loss))
        for k, v in out.losses.classification.items():
            assert np.isfinite(float(v)), k
        for k, v in out.losses.regression.items():
            assert np.isfinite(float(v)), k
        assert np.isfinite(float(out.losses.time_to_event))

    def test_training_loss_decreases(self, dataset, model_and_params):
        _, model, params = model_and_params
        batch = dataset.collate_indices(np.arange(min(4, len(dataset))))

        tx = optax.adamw(3e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            def loss_fn(p):
                return model.apply(p, batch).loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(30):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], f"Loss did not decrease: {losses[0]} -> {losses[-1]}"
        assert all(np.isfinite(l) for l in losses)

    def test_generation_mode_forward(self, dataset, model_and_params):
        _, model, params = model_and_params
        batch = dataset.collate_indices(np.arange(min(2, len(dataset))))
        out = model.apply(params, batch, is_generation=True)
        assert out.loss is None
        tte = out.preds.time_to_event
        key = jax.random.PRNGKey(0)
        sample = tte.sample(key)
        assert sample.shape == batch.event_mask.shape
        assert (np.asarray(sample) > 0).all()

    def test_use_cache_returns_caches(self, dataset, model_and_params):
        _, model, params = model_and_params
        batch = dataset.collate_indices(np.arange(min(2, len(dataset))))
        out = model.apply(params, batch, use_cache=True)
        assert out.past_key_values is not None and len(out.past_key_values) == 2
        assert int(out.past_key_values[0].length) == batch.sequence_length
