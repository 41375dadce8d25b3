"""Mixed-precision (bf16) training-path tests.

The ``precision="bf16"`` knob (VERDICT r02 #1) must keep fp32 parameters and
fp32 loss math while running activations/matmuls in bfloat16. These tests pin
the discipline on CPU: identical fp32 parameters fed through the bf16 path
must produce losses within a documented tolerance of the fp32 path, and one
optimizer step must keep parameters in fp32.
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
from eventstreamgpt_tpu.models.na_model import NAPPTForGenerativeSequenceModeling

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)

# bf16 has ~3 decimal digits; after fp32 softmax/loss math the end-to-end
# loss disagreement stays comfortably within a relative 2%.
LOSS_RTOL = 2e-2


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds_bf16")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return JaxDataset(PytorchDatasetConfig(save_dir=dst, max_seq_len=24), "tuning")


def _ci_config(dataset, precision):
    config = StructuredTransformerConfig(
        max_seq_len=24,
        hidden_size=32,
        head_dim=8,
        num_attention_heads=4,
        num_hidden_layers=2,
        intermediate_size=32,
        TTE_generation_layer_type="log_normal_mixture",
        TTE_lognormal_generation_num_components=2,
        precision=precision,
    )
    config.set_to_dataset(dataset)
    return config


class TestPrecisionConfig:
    def test_rejects_unknown_precision(self):
        with pytest.raises(ValueError, match="precision"):
            StructuredTransformerConfig(precision="fp16")

    def test_compute_dtype(self):
        assert StructuredTransformerConfig().compute_dtype == jnp.float32
        assert StructuredTransformerConfig(precision="bf16").compute_dtype == jnp.bfloat16

    def test_round_trips_through_dict(self):
        cfg = StructuredTransformerConfig(precision="bf16")
        assert StructuredTransformerConfig.from_dict(cfg.to_dict()).precision == "bf16"


class TestCIMixedPrecision:
    @pytest.mark.slow  # dual-model traces; the cheap contracts above stay in the core loop
    def test_params_stay_fp32_and_losses_agree(self, dataset):
        batch = dataset.collate_indices(np.arange(min(4, len(dataset))))

        cfg32 = _ci_config(dataset, "fp32")
        cfg16 = _ci_config(dataset, "bf16")
        model32 = CIPPTForGenerativeSequenceModeling(cfg32)
        model16 = CIPPTForGenerativeSequenceModeling(cfg16)

        params = model32.init(jax.random.PRNGKey(0), batch)
        # bf16 keeps fp32 parameters, so the fp32 init is directly usable.
        p16 = model16.init(jax.random.PRNGKey(0), batch)
        for leaf in jax.tree_util.tree_leaves(p16):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32

        out32 = model32.apply(params, batch)
        out16 = model16.apply(params, batch)

        assert out16.loss.dtype == jnp.float32
        l32, l16 = float(out32.loss), float(out16.loss)
        assert np.isfinite(l16)
        assert abs(l16 - l32) <= LOSS_RTOL * abs(l32), (l32, l16)
        # Per-head losses agree too (fp32 loss math on bf16 activations).
        for head in ("classification", "regression"):
            d32, d16 = getattr(out32.losses, head), getattr(out16.losses, head)
            for k in d32:
                assert abs(float(d16[k]) - float(d32[k])) <= LOSS_RTOL * max(
                    abs(float(d32[k])), 1.0
                ), (head, k)

    @pytest.mark.slow  # dual-model traces; the cheap contracts above stay in the core loop
    def test_train_step_keeps_fp32_params(self, dataset):
        batch = dataset.collate_indices(np.arange(min(4, len(dataset))))
        cfg16 = _ci_config(dataset, "bf16")
        model16 = CIPPTForGenerativeSequenceModeling(cfg16)
        params = model16.init(jax.random.PRNGKey(0), batch)
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(lambda p: model16.apply(p, batch).loss)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        params, opt_state, loss = step(params, opt_state)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(params):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32

    def test_generation_mode_bf16(self, dataset):
        batch = dataset.collate_indices(np.arange(min(2, len(dataset))))
        cfg16 = _ci_config(dataset, "bf16")
        model16 = CIPPTForGenerativeSequenceModeling(cfg16)
        params = model16.init(jax.random.PRNGKey(0), batch)
        out = model16.apply(params, batch, is_generation=True)
        sample = out.preds.time_to_event.sample(jax.random.PRNGKey(0))
        assert (np.asarray(sample) > 0).all()

    def test_cached_decode_bf16(self, dataset):
        """KV caches default to the compute dtype, so cached decoding works."""
        batch = dataset.collate_indices(np.arange(min(2, len(dataset))))
        cfg16 = _ci_config(dataset, "bf16")
        model16 = CIPPTForGenerativeSequenceModeling(cfg16)
        params = model16.init(jax.random.PRNGKey(0), batch)
        out = model16.apply(params, batch, use_cache=True)
        assert out.past_key_values[0].key.dtype == jnp.bfloat16


class TestNAMixedPrecision:
    def test_na_forward_agrees(self):
        from tests.models.test_na_model import make_batch, make_config

        batch = make_batch()
        cfg32 = make_config()
        cfg16 = make_config(precision="bf16")

        model32 = NAPPTForGenerativeSequenceModeling(cfg32)
        model16 = NAPPTForGenerativeSequenceModeling(cfg16)
        params = model32.init(jax.random.PRNGKey(0), batch)

        l32 = float(model32.apply(params, batch).loss)
        l16 = float(model16.apply(params, batch).loss)
        assert np.isfinite(l16)
        assert abs(l16 - l32) <= LOSS_RTOL * abs(l32), (l32, l16)
