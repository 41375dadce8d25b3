"""The kinds block on the normal path: `scripts.pretrain` builds it from
``config.*`` overrides, trains it through the device-resident packed feed and
the chunked step with the health sentinel, writes the routing counters to
``train_log.jsonl``, checkpoints and resumes; `generate()` and the serving
engine refuse it with one clear error."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scripts.pretrain import main as pretrain_main
from tests import SAMPLE_DIR as REF_SAMPLE

KINDS = [
    "config.hidden_size=32", "config.num_attention_heads=4", "config.num_hidden_layers=3",
    "config.intermediate_size=48", 'config.seq_attention_types=["global"]', "config.mixer_types=latent",
    'config.ffn_types=[[["swiglu"],1],[["routed"],46]]', "config.norm_type=rms_norm",
    "config.activation_function=silu", "config.layer_norm_epsilon=1e-05",
    "config.q_lora_rank=16", "config.kv_lora_rank=8", "config.qk_nope_head_dim=8", "config.qk_rope_head_dim=4",
    "config.v_head_dim=12", "config.rope_theta=1000000", "config.moe_intermediate_size=24",
    "config.moe_router_width=16", "config.n_routed_experts=4", "config.moe_expert_offset=4",
    "config.n_shared_experts=1", "config.num_experts_per_tok=4", "config.routed_scaling_factor=1.8",
    "config.resid_dropout=0.0", "config.input_dropout=0.0", "config.attention_dropout=0.0",
    "config.gradient_checkpointing=block",
]


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds_kinds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return dst


def _overrides(sample_dir, save_dir, steps):
    return KINDS + [
        f"data_config.save_dir={sample_dir}", "data_config.max_seq_len=8", "data_config.min_seq_len=2",
        "optimization_config.init_lr=1e-3", "optimization_config.max_epochs=20",
        f"optimization_config.max_training_steps={steps}", "optimization_config.lr_num_warmup_steps=1",
        "optimization_config.lr_frac_warmup_steps=null",
        "optimization_config.batch_size=4", "optimization_config.validation_batch_size=4",
        "trainer_config.device_resident_data=true", "trainer_config.steps_per_execution=2",
        "trainer_config.use_packed_batches=true", "trainer_config.packed_seq_len=16",
        "trainer_config.log_every_n_steps=2", "trainer_config.checkpoint_every_n_steps=2",
        "do_final_validation_on_metrics=false", f"save_dir={save_dir}",
    ]


def test_scripts_pretrain_trains_checkpoints_and_resumes(sample_dir, tmp_path):
    save_dir = tmp_path / "pretrain"
    pretrain_main(_overrides(sample_dir, save_dir, 4) + ["do_overwrite=true"])
    assert (save_dir / "pretrained_weights").exists()
    steps = sorted(int(p.name) for p in (save_dir / "model_checkpoints").iterdir() if p.name.isdigit())
    assert steps and steps[-1] == 4
    log = [json.loads(line) for line in (save_dir / "train_log.jsonl").read_text().splitlines()]
    train = [rec for rec in log if rec.get("split") == "train" and "train_loss" in rec]
    assert train and all(np.isfinite(rec["train_loss"]) for rec in train)
    # two routed layers, four choices of 16 a row, experts 4-7 held: a pair a row and layer at even routing
    assert all(0 < rec["moe_pairs_per_step"] <= 2 * 4 * 4 * 16 for rec in train)
    assert all(0 < rec["moe_load_max"] <= 4 * 16 for rec in train)
    # the same directory again, two steps further: the run restores step 4's checkpoint and goes on
    pretrain_main(_overrides(sample_dir, save_dir, 6))
    steps = sorted(int(p.name) for p in (save_dir / "model_checkpoints").iterdir() if p.name.isdigit())
    assert steps[-1] == 6


def test_generate_and_the_engine_refuse_the_backbone():
    from eventstreamgpt_tpu.generation.generation_utils import generate
    from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
    from eventstreamgpt_tpu.serving import GenerationEngine
    from tests.models.test_layer_kinds import KINDS as KINDS_KWARGS
    from tests.test_generation import BASE_KWARGS, MEASUREMENT_CONFIGS, make_prompt

    classic = {k: v for k, v in BASE_KWARGS.items() if k not in ("hidden_size", "head_dim", "num_attention_heads", "num_hidden_layers", "intermediate_size", "seq_attention_types")}
    config = StructuredTransformerConfig(measurement_configs=dict(MEASUREMENT_CONFIGS), **classic, **KINDS_KWARGS)
    model = CIPPTForGenerativeSequenceModeling(config)
    prompt = make_prompt()
    params = model.init(jax.random.PRNGKey(0), prompt)
    assert jnp.isfinite(model.apply(params, prompt).loss)  # the training forward runs
    with pytest.raises(NotImplementedError, match="no decode cache yet"):
        generate(model, params, prompt, config, jax.random.PRNGKey(1), max_new_events=2)
    with pytest.raises(NotImplementedError, match="no decode cache yet"):
        GenerationEngine(model, params, config, template=prompt, n_slots=2, max_len=8)
    with pytest.raises(NotImplementedError, match="no decode cache yet"):
        model.apply(params, prompt, use_cache=True, is_generation=True)
