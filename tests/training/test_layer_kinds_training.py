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


# The nemotron_h stack: one part a layer, MEMEM*EME's first five letters (Mamba-2, routed relu^2, grouped-query attention).
HYBRID = [
    "config.hidden_size=32", "config.num_attention_heads=4", "config.num_key_value_heads=2", "config.head_dim=16",
    "config.num_hidden_layers=6", "config.intermediate_size=48", 'config.seq_attention_types=["global"]',
    'config.mixer_types=["ssm","none","ssm","none","ssm","mha"]', 'config.ffn_types=["none","routed","none","routed","none","none"]',
    "config.norm_type=rms_norm", "config.layer_norm_epsilon=1e-05",
    "config.mamba_num_heads=4", "config.mamba_head_dim=8", "config.mamba_n_groups=2", "config.ssm_state_size=16",
    "config.mamba_chunk_size=8", "config.moe_expert_form=relu2", "config.moe_intermediate_size=24",
    "config.moe_shared_expert_intermediate_size=40", "config.moe_router_width=16", "config.n_routed_experts=4",
    "config.moe_expert_offset=4", "config.n_shared_experts=1", "config.num_experts_per_tok=6",
    "config.routed_scaling_factor=2.5", "config.resid_dropout=0.0", "config.input_dropout=0.0",
    "config.attention_dropout=0.0", "config.gradient_checkpointing=block",
]


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds_kinds")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return dst


def _overrides(sample_dir, save_dir, steps, kinds=KINDS):
    return kinds + [
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


def test_scripts_pretrain_trains_the_hybrid_stack_with_its_counters(sample_dir, tmp_path):
    """Mamba-2, routed relu^2 and grouped-query attention layers through
    `scripts.pretrain`, the device-resident packed feed (rows of 16 events at
    a chunk of 8: segments start inside chunks and on their edges) and
    `make_chunked_train_step` with the health sentinel and the routing
    counters; a checkpoint is written and restored."""
    save_dir = tmp_path / "pretrain"
    pretrain_main(_overrides(sample_dir, save_dir, 4, HYBRID) + ["do_overwrite=true"])
    log = [json.loads(line) for line in (save_dir / "train_log.jsonl").read_text().splitlines()]
    train = [rec for rec in log if rec.get("split") == "train" and "train_loss" in rec]
    assert train and all(np.isfinite(rec["train_loss"]) for rec in train)
    # two routed layers, six choices of 16 a row, experts 4-7 held: a pair and a half a row and layer at even routing
    assert all(0 < rec["moe_pairs_per_step"] <= 2 * 4 * 4 * 16 for rec in train)
    assert all(0 < rec["moe_load_max"] <= 4 * 16 for rec in train)
    pretrain_main(_overrides(sample_dir, save_dir, 6, HYBRID))
    steps = sorted(int(p.name) for p in (save_dir / "model_checkpoints").iterdir() if p.name.isdigit())
    assert steps[-1] == 6


def test_train_log_holds_the_share_of_chunk_pairs_the_flash_op_visits(sample_dir, tmp_path):
    """``attn_blocks_visited_share``: host arithmetic on the packed plans at
    the log flush (`ops.pallas_flash.visited_share`), beside the routing
    counters, wherever the configuration asks for ``pallas_flash`` and the
    rows are whole chunks. Off the chip the einsum path runs; the plans'
    segments are the same."""
    save_dir = tmp_path / "pretrain"
    overrides = [o for o in _overrides(sample_dir, save_dir, 2) if "packed_seq_len" not in o]
    with pytest.warns(UserWarning, match="taking the einsum path"):
        pretrain_main(
            overrides
            + ["trainer_config.packed_seq_len=512", "config.attention_implementation=pallas_flash", "do_overwrite=true"]
        )
    log = [json.loads(line) for line in (save_dir / "train_log.jsonl").read_text().splitlines()]
    train = [rec for rec in log if rec.get("split") == "train" and "train_loss" in rec]
    # rows of two 256-event chunks: the diagonal pairs always, the third where a history spans the chunks' border
    assert train and all(0.5 <= rec["attn_blocks_visited_share"] <= 0.75 for rec in train)


def test_padded_plans_give_one_segment_a_row_with_its_padding_tail():
    from types import SimpleNamespace

    from eventstreamgpt_tpu.data.config import SeqPaddingSide
    from eventstreamgpt_tpu.ops.pallas_flash import visited_share
    from eventstreamgpt_tpu.data.device_dataset import padded_segment_ids, plan_kept_lengths

    def _plan_segment_ids(plans, dataset):
        return padded_segment_ids(plan_kept_lengths(plans, dataset), dataset)

    offsets = np.asarray([0, 100, 400, 420])  # histories of 100, 300 and 20 events
    plans = {"subject_indices": np.asarray([[0, 1], [2, 0]]), "valid_mask": np.asarray([[True, True], [True, False]])}
    for side, real_first in ((SeqPaddingSide.RIGHT, True), (SeqPaddingSide.LEFT, False)):
        dataset = SimpleNamespace(
            data=SimpleNamespace(subject_event_offsets=offsets), max_seq_len=256, seq_padding_side=side
        )
        seg = _plan_segment_ids(plans, dataset)
        assert seg.shape == (2, 2, 256)
        assert ((seg == 0).sum(-1) == [[100, 256], [20, 0]]).all()  # cropped at the row; an invalid row is padding
        assert (seg[0, 0, 0] == 0) == real_first
    # a padded query sees its padded predecessors, so only the causal half is skipped: 3 of 4 pairs a row
    assert visited_share(seg := _plan_segment_ids(plans, SimpleNamespace(
        data=SimpleNamespace(subject_event_offsets=offsets), max_seq_len=256, seq_padding_side=SeqPaddingSide.RIGHT
    )), 128) == 12 / 16


@pytest.mark.parametrize("stack", ["latent", "hybrid"])
def test_generate_and_the_engine_refuse_the_backbone(stack):
    from eventstreamgpt_tpu.generation.generation_utils import generate
    from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
    from eventstreamgpt_tpu.serving import GenerationEngine
    if stack == "latent":
        from tests.models.test_layer_kinds import KINDS as KINDS_KWARGS
    else:  # Mamba-2, routed relu^2 and grouped-query attention layers: no decode state for any of the three
        from tests.models.test_hybrid_kinds import HYBRID as KINDS_KWARGS
    from tests.test_generation import BASE_KWARGS, MEASUREMENT_CONFIGS, make_prompt

    classic = {k: v for k, v in BASE_KWARGS.items() if k not in KINDS_KWARGS and k != "head_dim"}
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
    from eventstreamgpt_tpu.models.transformer import NO_DECODE_STATE

    # the message names what is missing, by mechanism
    for missing in ("latent paged cache", "recurrent state", "grouped key/value heads"):
        assert missing in NO_DECODE_STATE
