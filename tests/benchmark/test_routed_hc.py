"""Job kind ``pretrain_routed_hc`` (`xing40_a4b_ep8.pretrain_packed`): the
whole model against the plain reference in float32, the reference with a
fault planted in its residual streams, its FLOP and bytes functions against
counts by hand, one head width for the flash roofline's needs, its two
readers on records built by hand, the configuration's file against the
catalog's row, and its `correct` with a fault or the fp8 control in the
program's place (`test_faults.py` selects cells by the job name ``pretrain``
and does not see this one)."""

from __future__ import annotations

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops, flops_hc, flops_routed, loader, scopes
from benchmark.harness import pretrain as base

from .test_faults import _first_plans
from .test_scopes import BWD, FWD, RAW, SOURCES
from .tiny import run_tiny, tiny_cell

CELL = "xing40_a4b_ep8.pretrain_packed"
NEW_READERS = ("hc_device_ms", "hc_roofline")
# The accepted metrics that read in this cell and list it (`test_hybrid.py` pins the last entry of three of
# the lists to its own cell, so this cell stands before that one there; nothing here pins a position).
LISTED = ("flash_attn_roofline", "moe_device_ms", "moe_dispatch_device_ms", "mla_device_ms", "moe_experts_roofline",
          "moe_load_max_over_mean")
MODEL = {
    "hidden_size": 8, "num_attention_heads": 4, "intermediate_size": 24, "ffn_layers": ["swiglu", "routed", "routed"],
    "q_lora_rank": 6, "kv_lora_rank": 5, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 3,
    "moe_intermediate_size": 16, "moe_router_width": 64, "n_routed_experts": 8, "n_shared_experts": 1,
    "hc_mult": 4, "tte_components": 3,
}
VOCAB = {
    "vocab_size": 48, "vocab_sizes": {"event_type": 5, "lab": 20, "med": 6, "demo": 16},
    "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
    "multivariate_regression": ["lab"],
}
# The catalog's row (`model-configs` guide, architectures.jsonl, "Xing4.0-29B-A4B"), its `config` key for key.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
    "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}


# --------------------------------------------------------------- needs by hand
def test_forward_flops_by_hand():
    # Phi's product, a sublayer: 2 * (4 * 8) * (4 + 4 + 16) = 1536; the mixes: pre 2 * 4 * 8 = 64,
    # post/res 2 * (16 + 4) * 8 = 320 -> 384; six sublayers: 6 * 1920 = 11520 on top of the routed count
    assert flops_hc.hc_maps_flops(MODEL) == 1536
    assert flops_hc.hc_mix_flops(MODEL) == 384
    routed = flops_routed.forward_flops_per_event(MODEL, VOCAB, global_keys=10, pairs_per_event=0.5)
    assert flops_hc.forward_flops_per_event(MODEL, VOCAB, global_keys=10, pairs_per_event=0.5) == routed + 11520
    # the routed count itself, at a key width of 6 beside a value width of 3:
    # latent attention: 2 * (8*6 + 6*4*6 + 8*7 + 5*4*7 + 4*3*8) = 968; QK^T 2*4*6*10 = 480, PV 2*4*3*10 = 240 -> 1688
    assert flops_routed.latent_attention_flops(MODEL, 10) == 1688


def test_hc_needs_by_hand():
    need = flops_hc.hc_needs(events=1000, model=MODEL, itemsize=2)
    # an event and sublayer: the maps read 4 planes, the pre-mix reads 4 and writes 1, the post/res mix reads
    # 4 + 1 and writes 4: 18 * 8 values of 2 bytes = 288 bytes; Phi's product and the mixes 1536 + 384 = 1920
    # operations; six sublayers; the backward twice the forward
    assert need == {
        "fwd_flops": 1000 * 6 * 1920, "bwd_flops": 2 * 1000 * 6 * 1920,
        "fwd_bytes": 1000 * 6 * 288, "bwd_bytes": 2 * 1000 * 6 * 288,
    }
    published = {"hidden_size": 3584, "hc_mult": 4, "ffn_layers": ["swiglu"] + ["routed"] * 4}
    # 129 KB an event and sublayer: 1.06 GB a sublayer and pass at 8,192 events (ISSUE 34), the mixes 100 KB of it
    assert flops_hc.hc_needs(1, published, 2)["fwd_bytes"] == 10 * 129024
    assert 8192 * 129024 == pytest.approx(1.06e9, rel=0.01) and 14 * 3584 * 2 == 100352


def test_one_head_width_of_160_counts_what_192_and_128_need():
    """`metrics/flash_attn_roofline.py` hands `attention_needs` one head width.
    It is linear in it, so at the mean of the key width and the value width it
    returns what the two halves need counted apart: QK^T, dQ and dK (and the
    planes q, k, dq, dk) at 192, PV, dV and dP (and v, o, do, dv) at 128."""
    queries, keys, heads, dk, dv, itemsize = 5000.0, 137.5, 32, 192, 128, 2
    got = flops.attention_needs(queries, keys, heads, (dk + dv) / 2, itemsize)
    product = lambda width: 2 * heads * width * queries * keys  # noqa: E731
    plane = lambda width: queries * heads * width * itemsize  # noqa: E731
    apart = {
        "fwd_flops": product(dk) + product(dv),  # QK^T; PV
        "bwd_flops": 2 * product(dk) + 2 * product(dv),  # dQ, dK; dV, dP
        "fwd_bytes": 2 * plane(dk) + 2 * plane(dv),  # q, k; v, o
        "bwd_bytes": 4 * plane(dk) + 4 * plane(dv),  # q, k, dq, dk; v, o (through do), do, dv
    }
    assert got == apart
    cell = loader.load_cell(CELL)
    c = cell["model"]["config"]
    assert (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]) / 2 == 160


# ---------------------------------------------------------------- the readers
HC_RAW = RAW + [
    ("%fusion.40 = f32[...] fusion(...)", 1000, 300),
    ("%fusion.41 = f32[...] fusion(...)", 1300, 200),
    ("%fusion.42 = bf16[...] fusion(...)", 1500, 400),
    ("%fusion.43 = bf16[...] fusion(...)", 1900, 900),
    ("%fusion.44 = bf16[...] fusion(...)", 2800, 100),
    ("%fusion.45 = f32[...] fusion(...)", 2900, 250),
]
HC_NAMES = {
    "fusion.40": FWD + "h0/mixer_hc/es.hc_maps/dot_general",
    "fusion.41": BWD + "checkpoint/rematted_computation/h0/mixer_hc/es.hc_maps/div",
    "fusion.42": FWD + "h0/es.hc_mix/mul",
    "fusion.43": BWD + "checkpoint/h1/es.hc_mix/reduce_sum",
    "fusion.44": FWD + "es.hc_mix/add",
    "fusion.45": FWD + "h0/es.norm/reduce_sum",  # the pre-mix's read of the streams, under the sublayer's norm
}


def _record(model=MODEL) -> dict:
    return {
        "counters": {"steps": 4, "events": 2000}, "end_to_end": {"train_events_per_s": 1.0},
        "model_sizes": model, "device_kind": "TPU v5 lite",
    }


def _traced(monkeypatch, raw, names):
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", lambda d: scopes.scoped(raw, names, {}))


def test_the_new_readers_read_the_new_scopes(monkeypatch):
    _traced(monkeypatch, HC_RAW, SOURCES[0] | HC_NAMES)
    readers = loader.metric_readers()
    got = {name: readers[name].read(_record()) for name in NEW_READERS}
    per_step = 1e6 * 4
    assert got["hc_device_ms"] == pytest.approx((300 + 200 + 400 + 900 + 100) / per_step)
    need = flops_hc.hc_needs(2000, MODEL, 2)
    t_bytes = (need["fwd_bytes"] + need["bwd_bytes"]) / 819e9
    assert t_bytes > (need["fwd_flops"] + need["bwd_flops"]) / 197e12  # the bound is bytes
    # the maps' and the mixes' needs over 1,900 ns under their two scopes and 250 under the norm's: a fusion
    # carries one name, and XLA writes the mixes into the maps' fusions and the pre-mix's read into the norm's
    assert got["hc_roofline"] == pytest.approx(100 * t_bytes / 2150e-9)
    for name in NEW_READERS:
        assert (readers[name].LAYER, readers[name].MOVES) == ("encoder residual streams", "train_events_per_s")


def test_the_new_readers_find_nothing_without_their_scopes(monkeypatch):
    """A trace with scopes and none of the streams' (the accepted cells; the
    parent's program under this PR's benchmark files), a model of one stream,
    no trace at all: nothing, never 0, no raise."""
    _traced(monkeypatch, RAW, SOURCES[0])
    one_stream = {k: v for k, v in MODEL.items() if k != "hc_mult"}
    for record in (_record(), _record(one_stream)):
        for name in NEW_READERS:
            assert loader.metric_readers()[name].read(record) is None
    _traced(monkeypatch, HC_RAW, SOURCES[0] | HC_NAMES)
    assert loader.metric_readers()["hc_roofline"].read(_record(one_stream)) is None
    _traced(monkeypatch, HC_RAW[-1:], SOURCES[0] | HC_NAMES)  # a norm and no stream scope: nothing, not the norm's time
    assert loader.metric_readers()["hc_roofline"].read(_record()) is None
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: None)  # a CPU rehearsal
    for name in NEW_READERS:
        assert loader.metric_readers()[name].read(_record()) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    manifest = json.loads((loader.ROOT.parent / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "encoder residual streams"
    for name in LISTED:
        assert CELL in listed[name]["workloads"]
    for name in ("ssm_device_ms", "ssm_scan_roofline", "relu2_experts_roofline", "routed_load_max_over_mean"):
        assert CELL not in listed[name]["workloads"]
    assert [w["chips"] for w in manifest["workloads"] if w["name"] == CELL] == [1]
    config = loader.load_cell(CELL)["config"]
    assert [c["reduced"] for c in manifest["configs"] if c["name"] == config] == [loader.load_cell(CELL)["model"]["reduced"]]


# ---------------------------------------------------------- the configuration
def test_the_configuration_file_holds_the_catalogs_row_and_states_the_cut():
    import jax

    from benchmark.harness import cohort as cohort_lib

    cell = loader.load_cell(CELL)
    model, config = cell["model"], cell["model"]["config"]
    assert model["reduced"] == ["num_hidden_layers", "n_routed_experts", "num_nextn_predict_layers", "vocab_size"]
    assert set(model["reduced_why"]) == set(model["published"]) == set(model["reduced"])
    for key, value in PUBLISHED.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value, key
        else:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["n_routed_experts"], model["num_nextn_predict_layers"], model["vocab_size"]) == (5, 8, 0, 16384)
    for ours, theirs in (
        ("hidden_size", "hidden_size"), ("num_attention_heads", "num_attention_heads"), ("intermediate_size", "intermediate_size"),
        ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
        ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"), ("rope_theta", "rope_theta"),
        ("rope_scaling", "rope_scaling"), ("hc_mult", "hc_mult"), ("hc_sinkhorn_iters", "hc_sinkhorn_iters"),
        ("hc_eps", "hc_eps"), ("hc_res_clamp", "mhc_h_res_clamp_max"), ("moe_intermediate_size", "moe_intermediate_size"),
        ("moe_router_width", "n_routed_experts"), ("n_shared_experts", "n_shared_experts"),
        ("num_experts_per_tok", "num_experts_per_tok"), ("routed_scaling_factor", "routed_scaling_factor"),
        ("norm_topk_prob", "norm_topk_prob"), ("layer_norm_epsilon", "rms_norm_eps"),
    ):
        assert config[ours] == PUBLISHED[theirs], ours
    assert -PUBLISHED["mhc_h_res_clamp_min"] == config["hc_res_clamp"]
    assert config["num_hidden_layers"] == 5 and config["n_routed_experts"] == 8
    assert {"hc_unit", "hc_norm", "hc_sinkhorn", "hc_in_out", "hc_init", "rope", "sizes"} <= set(model["assumed"])
    cell["cohort"]["n_subjects"] = 8  # the vocabulary is the cell's, whatever the number of histories
    cohort = cohort_lib.make_cohort(cell["cohort"], 1)
    assert cohort.vocab["vocab_size"] == model["vocab_size"] == 16384
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    sizes = job.reference_model(cell, cohort)
    assert sizes["ffn_layers"] == ["swiglu"] + ["routed"] * 4 and sizes["head_dim"] == 160
    assert (sizes["hc_mult"], sizes["hc_sinkhorn_iters"], sizes["rope_scaling"]["factor"]) == (4, 20, 64)
    shapes = jax.eval_shape(lambda key: reference.init_params(sizes, cohort.vocab, key), jax.random.PRNGKey(0))
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert built == 784_504_435 and f"{built:,} parameters" in model["assumed"]["sizes"]
    # the cell: 8 rows of 1,024, the hybrid cell's cohort to the key, the long warm-up
    hybrid = loader.load_cell("nemotron_twotower_ep16.pretrain_packed")
    assert {k: v for k, v in cell["cohort"].items() if k not in ("note", "n_subjects")} == {
        k: v for k, v in hybrid["cohort"].items() if k not in ("note", "n_subjects")
    }
    assert cell["feed"] == dict(hybrid["feed"], batch_size=8)
    assert cell["optimizer"]["lr_num_warmup_steps"] == 10000


def test_a_program_that_does_not_know_the_streams_is_refused_at_once(monkeypatch):
    """The parent's configuration class stores a key it does not know and
    builds the plain block: the job refuses before anything is built."""
    from eventstreamgpt_tpu.models import config as config_module

    cell = loader.load_cell(CELL)
    job = loader.load_job(cell)
    assert job.model_config(cell).hc_mult == 4
    built = config_module.StructuredTransformerConfig.__init__

    @functools.wraps(built)
    def parents(self, **kwargs):
        built(self, **kwargs)
        self._extra_kwargs = sorted(k for k in kwargs if k.startswith("hc_") or k == "rope_scaling")

    monkeypatch.setattr(config_module.StructuredTransformerConfig, "__init__", parents)
    with pytest.raises(ValueError, match="does not know .*hc_mult"):
        job.model_config(cell)


# ------------------------------------------------------------ the whole model
@pytest.fixture()
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")


def _cell_at_depth():
    """The tiny cell with one dense and two routed layers: four streams of 32,
    latent attention at the published 128 + 64 / 128 with YaRN."""
    cell = tiny_cell(CELL)
    cell["model"]["config"]["num_hidden_layers"] = 3
    return cell


def test_the_model_agrees_with_the_reference_and_the_record_carries_the_counters(interpreted_kernels, tmp_path):
    """Float32 on both sides, packed rows of 32 events whose segments start
    wherever the packing puts them: the loss of four optimizer steps to 1e-6,
    every leaf's first moment to 1e-5 of the reference's."""
    record = run_tiny(_cell_at_depth(), tmp_path)
    counters, sizes, compared = record["counters"], record["model_sizes"], record["compared"]
    assert record["correct"] is True
    assert compared["loss_gap"]["value"] < 1e-6
    assert compared["grad_diff_gap"]["value"] < 1e-5 and compared["grad_norm_gap"]["value"] < 1e-5
    assert {"events", "steps", "global_keys", "flops_per_event", "rows_per_step", "row_len"} <= set(counters)
    assert sizes["ffn_layers"] == ["swiglu", "routed", "routed"] and sizes["hc_mult"] == 4
    assert counters["moe_routed_layers"] == 2 and counters["moe_experts_held"] == 8
    assert 0 < counters["moe_pairs"] <= 4 * 2 * counters["events"]
    assert counters["moe_pairs_per_event_layer"] == pytest.approx(counters["moe_pairs"] / counters["events"] / 2)
    assert not any(key.startswith("relu2_") for key in counters)
    assert loader.metric_readers()["moe_load_max_over_mean"].read(record) > 0
    assert loader.metric_readers()["routed_load_max_over_mean"].read(record) is None


def _follow_with(cell, cohort, reference, sizes, plans, seed, **faults):
    """`job.follow`'s readings of the reference with a fault in its streams."""
    import jax
    import jax.numpy as jnp

    batches = [
        {k: (None if v is None else jnp.asarray(v)) for k, v in b.items()} for b in base.reference_batches(cell, cohort, plans)
    ]
    with jax.default_matmul_precision("highest"):
        p0 = reference.init_params(sizes, cohort.vocab, jax.random.PRNGKey(seed % (2**31)))
        keep = jax.device_get(p0)
        losses, params, mu = reference.train_steps(
            p0, batches, sizes, cohort.vocab, cell["optimizer"], cell["check"]["rows_per_block"], **faults
        )
    leaf = lambda a: float(np.sqrt(np.sum(np.square(np.asarray(a)))))  # noqa: E731
    delta = jax.tree_util.tree_map(lambda a, b: leaf(np.asarray(a) - b), params, keep)
    return {
        "losses": [float(l) for l in losses], "delta": base._flat(delta),
        "mu": base._flat(jax.tree_util.tree_map(leaf, mu)), "mu_tensors": base._flat_arrays(jax.device_get(mu)),
    }


FAULTS = {
    # the fault planted in the reference's streams: (its keywords, the least and the most `grad_diff_gap` may read)
    "none": ({}, 0.0, 1e-6),
    "H_res = I": ({"res_identity": True}, 1e-5, np.inf),
    "19 iterations": ({"iters": 19}, 2e-6, np.inf),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_in_the_streams_is_told_from_the_program(fault):
    """The reference with a fault in its streams in the program's place, on
    the worst leaf's first moment against the reference proper. ``H_res`` the
    identity is past the 1e-5 the program meets. 19 Sinkhorn iterations for 20
    can be told, 5e-6 where the faultless path reads under 1e-6 (a map this
    near the identity still moves by 2e-4 in its 20th iteration), though it
    stays inside 1e-5. With no fault the same path reads the reference itself."""
    from benchmark.harness import cohort as cohort_lib

    keywords, least, most = FAULTS[fault]
    cell = _cell_at_depth()
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    seed = 91
    cohort = cohort_lib.make_cohort(cell["cohort"], seed)
    sizes = job.reference_model(cell, cohort)
    plans = _first_plans(cell, cohort, seed)
    ref = job.follow(cell, cohort, reference, sizes, plans, seed)
    numbers = job.compare(cell, _follow_with(cell, cohort, reference, sizes, plans, seed, **keywords), *ref)["numbers"]
    assert least <= numbers["grad_diff_gap"]["value"] <= most


# ------------------------------------------------------------------ `correct`
def _judged():
    cell = _cell_at_depth()
    cell["check"]["limits"] = loader.load_cell("ci_w1024.pretrain_packed")["check"]["limits"]
    return cell


def test_a_step_that_returns_its_state_unchanged_is_not_correct(interpreted_kernels, tmp_path, monkeypatch):
    cell = _judged()
    job = loader.load_job(cell)
    real = job.Program.dispatch

    def frozen(self, plans):
        import jax

        keep = self.state
        self.state = jax.tree_util.tree_map(lambda a: a.copy(), keep)  # the step donates its input
        losses = real(self, plans)
        self.state = keep
        return losses

    monkeypatch.setattr(job.Program, "dispatch", frozen)
    monkeypatch.setattr(loader, "load_job", lambda c, root=None: job)
    record = run_tiny(cell, tmp_path)
    assert record["correct"] is False
    assert record["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert record["compared"]["param_change_gap"]["value"] > 0.99


def test_the_fp8_control_is_not_correct():
    """The reference in the program's place computed in fp8 reads at least
    three times what it reads in bfloat16 on ``grad_diff_median``, so a limit
    between the two readings passes the stated precision and fails the one
    below."""
    from benchmark.harness import cohort as cohort_lib

    cell = _cell_at_depth()
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    seed = 78
    cohort = cohort_lib.make_cohort(cell["cohort"], seed)
    sizes = job.reference_model(cell, cohort)
    plans = _first_plans(cell, cohort, seed)
    ref = job.follow(cell, cohort, reference, sizes, plans, seed)

    def reading(quant):
        got = job.follow(cell, cohort, reference, sizes, plans, seed, quant=quant)
        return {"losses": got[0], "delta": got[1], "mu": got[2], "mu_tensors": got[3]}

    stated, control = reading(reference.bf16_operand), reading(reference.fp8_operand)
    lower = job.compare(cell, stated, *ref)["numbers"]["grad_diff_median"]["value"]
    upper = job.compare(cell, control, *ref)["numbers"]["grad_diff_median"]["value"]
    assert upper >= 3 * lower
    cell["check"]["limits"] = {"loss_gap": None, "grad_norm_gap": None, "param_change_gap": None,
                               "grad_diff_gap": None, "grad_diff_median": (lower * upper) ** 0.5}
    assert job.compare(cell, stated, *ref)["ok"] is True
    assert job.compare(cell, control, *ref)["ok"] is False
