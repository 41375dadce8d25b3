"""Job kind ``pretrain_hybrid`` (`nemotron_twotower_ep16.pretrain_packed`): its
FLOP and bytes functions against counts by hand, its per-layer readers on
records built by hand (a number with the scope, nothing without, never 0; the
readers of the gated experts' counters read nothing here), the whole model at
the cell's depth against the plain reference, and its `correct` with a fault
planted or the fp8 control in the program's place (`test_faults.py` selects
cells by the job name ``pretrain`` and does not see this one)."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_hybrid, flops_routed, loader, scopes

from .test_faults import _first_plans
from .test_scopes import BWD, FWD, RAW, SOURCES
from .tiny import run_tiny, tiny_cell

CELL = "nemotron_twotower_ep16.pretrain_packed"
NEW_READERS = ("ssm_device_ms", "ssm_scan_device_ms", "ssm_scan_roofline", "relu2_experts_roofline", "routed_load_max_over_mean")
GATED_READERS = ("moe_experts_roofline", "moe_load_max_over_mean", "mla_device_ms")
MODEL = {
    "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3, "pattern": "MEM*E",
    "mamba_num_heads": 4, "mamba_head_dim": 2, "mamba_n_groups": 2, "ssm_state_size": 5, "mamba_chunk_size": 7,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24, "moe_router_width": 128,
    "n_routed_experts": 8, "tte_components": 3,
}
VOCAB = {
    "vocab_size": 48, "vocab_sizes": {"event_type": 5, "lab": 20, "med": 6, "demo": 16},
    "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
    "multivariate_regression": ["lab"],
}


def test_forward_flops_by_hand():
    # the scan, an event: 4 keys inside a chunk of 7; C B^T 2*2*5*4 = 80, (L o C B^T) X 2*4*2*4 = 64,
    #   B^T X and C S 2*4*2*5 = 80 each -> 304
    # a Mamba layer: in 8 -> 8 + 8 + 20 + 4 = 40: 2*8*40 = 640; out 2*8*8 = 128 -> 1072
    # a routed layer: router 2*8*128 = 2048, shared 2*2*8*24 = 768, half a pair an event 0.5 * 2*2*8*16 = 256 -> 3072
    # attention at 10 keys: q, o 2 * 2*8*12 = 384, k, v 2 * 2*8*6 = 192, QK^T and PV 2 * 2*4*3*10 = 480 -> 1056
    # heads: 2*8*(48 + 40 + 9 + 4) = 1616
    assert flops_hybrid.ssm_scan_flops(MODEL) == 304
    assert flops_hybrid.ssm_layer_flops(MODEL) == 1072
    assert flops_hybrid.attention_layer_flops(MODEL, 10) == 1056
    want = 2 * 1072 + 2 * 3072 + 1056 + 1616
    assert flops_hybrid.forward_flops_per_event(MODEL, VOCAB, global_keys=10, pairs_per_event=0.5) == want


def test_ssm_scan_needs_by_hand():
    need = flops_hybrid.ssm_scan_needs(events=1000, model=MODEL, itemsize=2)
    # two Mamba layers; an event: x and y 8 values each, B and C 20 together, 4 step sizes in float32
    assert need == {
        "fwd_flops": 1000 * 2 * 304, "bwd_flops": 2 * 1000 * 2 * 304,
        "fwd_bytes": 1000 * 2 * (2 * 16 + 40 + 16), "bwd_bytes": 1000 * 2 * (3 * 16 + 2 * 40 + 32),
    }


def test_relu2_experts_needs_are_two_thirds_of_the_gated_ones():
    need = flops_hybrid.relu2_experts_needs(pairs=1000, layer_steps=4, model=MODEL, itemsize=2)
    # a pair: two products of 8x16 -> 512 operations; rows read and written: 2*8 + 2*16 = 48 values
    rows, weights = 1000 * 48 * 2, 4 * 8 * 256 * 2
    assert need == {
        "fwd_flops": 512000, "bwd_flops": 1024000, "fwd_bytes": rows + weights, "bwd_bytes": 2 * rows + 2 * weights,
    }
    gated = flops_routed.routed_experts_needs(pairs=1000, layer_steps=4, model=MODEL, itemsize=2)
    assert {k: 3 * v for k, v in need.items()} == {k: 2 * v for k, v in gated.items()}


# ---------------------------------------------------------------- the readers
HYBRID_RAW = RAW + [
    ("%fusion.30 = bf16[...] fusion(...)", 1000, 300),
    ("%fusion.31 = bf16[...] fusion(...)", 1300, 40),
    ("%fusion.32 = f32[...] fusion(...)", 1340, 500),
    ("%fusion.35 = f32[...] fusion(...)", 1840, 700),
    ("%fusion.33 = bf16[...] fusion(...)", 2540, 60),
    ("%_gmm.4 = bf16[...] custom-call(...)", 2600, 400),
    ("%_tgmm.5 = bf16[...] custom-call(...)", 3000, 600),
    ("%sort.3 = s32[...] sort(...)", 3600, 50),
    ("%fusion.34 = bf16[...] fusion(...)", 3650, 120),
]
HYBRID_NAMES = {
    "fusion.30": FWD + "h0/mixer/es.ssm_proj/in_proj/dot_general",
    "fusion.31": FWD + "h0/mixer/es.ssm_conv/checkpoint/mul",
    "fusion.32": FWD + "h0/mixer/es.ssm_scan/dot_general",
    "fusion.35": BWD + "checkpoint/h0/mixer/es.ssm_scan/dot_general",
    "fusion.33": FWD + "h0/mixer/es.ssm_gate/checkpoint/mul",
    "_gmm.4": FWD + "h1/mlp/es.moe_experts/pallas_call",
    "_tgmm.5": BWD + "checkpoint/h1/mlp/es.moe_experts/pallas_call",
    "sort.3": FWD + "h1/mlp/es.moe_dispatch/sort",
    "fusion.34": FWD + "h1/mlp/es.moe_shared/shared_experts/dot_general",
}
ROUTING = {"relu2_pairs": 6000, "relu2_load_max_sum": 500, "relu2_routed_layers": 2, "relu2_experts_held": 8}


def _record(counters: dict | None = None) -> dict:
    return {
        "counters": {"steps": 4, "events": 2000} | (counters or {}), "end_to_end": {"train_events_per_s": 1.0},
        "model_sizes": MODEL, "device_kind": "TPU v5 lite",
    }


def _traced(monkeypatch, raw, names):
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", lambda d: scopes.scoped(raw, names, {}))


def _roofline(need, seconds):
    t_flops = (need["fwd_flops"] + need["bwd_flops"]) / 197e12
    t_bytes = (need["fwd_bytes"] + need["bwd_bytes"]) / 819e9
    return 100 * max(t_flops, t_bytes) / seconds


def test_the_new_readers_read_the_new_scopes_and_counters(monkeypatch):
    _traced(monkeypatch, HYBRID_RAW, SOURCES[0] | HYBRID_NAMES)
    record = _record(ROUTING)
    readers = loader.metric_readers()
    got = {name: readers[name].read(record) for name in NEW_READERS}
    per_step = 1e6 * 4
    assert got["ssm_device_ms"] == pytest.approx((300 + 40 + 500 + 700 + 60) / per_step)
    assert got["ssm_scan_device_ms"] == pytest.approx((40 + 500 + 700 + 60) / per_step)
    # 1,200 ns under es.ssm_scan; the needs of 2,000 events in two Mamba layers
    assert got["ssm_scan_roofline"] == pytest.approx(_roofline(flops_hybrid.ssm_scan_needs(2000, MODEL, 2), 1200e-9))
    # 1,000 ns under es.moe_experts; the needs of 6,000 pairs over 8 layer-steps
    assert got["relu2_experts_roofline"] == pytest.approx(
        _roofline(flops_hybrid.relu2_experts_needs(6000, 8, MODEL, 2), 1000e-9)
    )
    # the steps' largest loads, 500 in all, over the mean load 6000 / (2 layers * 8 experts)
    assert got["routed_load_max_over_mean"] == pytest.approx(500 / 375)
    # the scope readers that are right for any stack read the routed layers; the gated experts' readers nothing
    assert readers["moe_device_ms"].read(record) == pytest.approx((400 + 600 + 50 + 120) / per_step)
    assert readers["moe_dispatch_device_ms"].read(record) == pytest.approx(50 / per_step)
    for name in GATED_READERS:
        assert readers[name].read(record) is None


def test_the_new_readers_find_nothing_in_the_other_cells(monkeypatch):
    """A trace with scopes and none of the state-space layers', a record with
    the gated experts' counters or none (the accepted cells; the parent's
    program under this PR's benchmark files): nothing, never 0, no raise."""
    _traced(monkeypatch, RAW, SOURCES[0])
    gated = {"moe_pairs": 8000, "moe_load_max_sum": 600, "moe_routed_layers": 2, "moe_experts_held": 8}
    classic = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4, "seq_attention_types": ["global"]}
    for record in (_record(), _record(gated), _record() | {"model_sizes": classic}):
        for name in NEW_READERS:
            assert loader.metric_readers()[name].read(record) is None
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: None)  # no trace at all (a CPU rehearsal)
    for name in NEW_READERS[:4]:
        assert loader.metric_readers()[name].read(_record(ROUTING)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    manifest = json.loads((loader.ROOT.parent / "BENCHMARK.json").read_text())
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert listed[name] == [CELL]
    for name in ("moe_device_ms", "moe_dispatch_device_ms", "flash_attn_roofline"):
        assert listed[name][-1] == CELL
    for name in GATED_READERS:
        assert CELL not in listed[name]


# ------------------------------------------------------------ the whole model
@pytest.fixture()
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")


def _cell_at_depth():
    """The tiny cell at the cell's own depth, ``MEMEM*EME``: every kind of
    layer, attention included (the rehearsal's two layers are ``ME``)."""
    cell = tiny_cell(CELL)
    cell["model"]["config"]["num_hidden_layers"] = 9
    return cell


def _judged():
    cell = _cell_at_depth()
    if all(limit is None for limit in cell["check"]["limits"].values()):
        cell["check"]["limits"] = loader.load_cell("ci_w1024.pretrain_packed")["check"]["limits"]
    return cell


def test_the_nine_layer_model_agrees_with_the_reference_and_the_record_carries_the_counters(interpreted_kernels, tmp_path):
    """Float32 on both sides, packed rows of 32 events whose segments start
    wherever the packing puts them: the loss of four optimizer steps to 1e-6,
    every leaf's first moment to 1e-5 of the reference's."""
    cell = _cell_at_depth()
    record = run_tiny(cell, tmp_path)
    counters, sizes, compared = record["counters"], record["model_sizes"], record["compared"]
    assert record["correct"] is True
    assert compared["loss_gap"]["value"] < 1e-6
    assert compared["grad_diff_gap"]["value"] < 1e-5 and compared["grad_norm_gap"]["value"] < 1e-5
    assert {"events", "steps", "global_keys", "flops_per_event", "rows_per_step", "row_len"} <= set(counters)
    assert sizes["pattern"] == "MEMEM*EME" and sizes["published_layers"] == 52
    assert sizes["seq_attention_types"] == ["none"] * 5 + ["global"] + ["none"] * 3
    assert (sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]) == (4, 2, 8)
    assert counters["relu2_routed_layers"] == 4 and counters["relu2_experts_held"] == 8
    # six choices of 128 an event, 8 of the 128 held: 0.375 pairs an event and layer at even load
    assert 0 < counters["relu2_pairs"] <= 6 * 4 * counters["events"]
    assert counters["relu2_pairs_per_event_layer"] == pytest.approx(counters["relu2_pairs"] / counters["events"] / 4)
    assert not any(key.startswith("moe_") for key in counters)
    readers = loader.metric_readers()
    assert readers["routed_load_max_over_mean"].read(record) > 0
    assert readers["moe_load_max_over_mean"].read(record) is None
    assert readers["moe_experts_roofline"].read(record) is None


def test_the_configuration_file_states_the_cut_and_the_built_models_count():
    """Every width is the published one, `reduced` is the three cuts, the
    layer kinds are the pattern's letters, and the parameter count in the file
    is the built model's at the cell's vocabulary."""
    import jax

    from benchmark.harness import cohort as cohort_lib

    cell = loader.load_cell(CELL)
    model, config = cell["model"], cell["model"]["config"]
    assert model["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert set(model["reduced_why"]) == set(model["published"]) == set(model["reduced"])
    kinds = {"M": ("ssm", "none"), "E": ("none", "routed"), "*": ("mha", "none")}
    pattern = model["hybrid_override_pattern"]
    assert len(pattern) == model["published"]["num_hidden_layers"] == 52
    assert list(zip(config["mixer_types"], config["ffn_types"])) == [kinds[c] for c in pattern]
    for ours, theirs in (
        ("hidden_size", "hidden_size"), ("head_dim", "head_dim"), ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"), ("mamba_num_heads", "mamba_num_heads"),
        ("mamba_head_dim", "mamba_head_dim"), ("mamba_n_groups", "n_groups"), ("ssm_state_size", "ssm_state_size"),
        ("mamba_conv_kernel", "conv_kernel"), ("mamba_chunk_size", "chunk_size"),
        ("moe_intermediate_size", "moe_intermediate_size"), ("num_experts_per_tok", "num_experts_per_tok"),
        ("moe_shared_expert_intermediate_size", "moe_shared_expert_intermediate_size"),
        ("routed_scaling_factor", "routed_scaling_factor"), ("layer_norm_epsilon", "layer_norm_epsilon"),
        ("moe_router_width", ("published", "n_routed_experts")),
    ):
        want = model[theirs[0]][theirs[1]] if isinstance(theirs, tuple) else model[theirs]
        assert config[ours] == want, ours
    cell["cohort"]["n_subjects"] = 8  # the vocabulary is the cell's, whatever the number of histories
    cohort = cohort_lib.make_cohort(cell["cohort"], 1)
    assert cohort.vocab["vocab_size"] == model["vocab_size"] == 16384
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    shapes = jax.eval_shape(
        lambda key: reference.init_params(job.reference_model(cell, cohort), cohort.vocab, key), jax.random.PRNGKey(0)
    )
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert f"{built:,} parameters" in model["assumed"]["sizes"]


# ------------------------------------------------------------------ `correct`
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


def test_half_of_the_batch_left_out_is_not_correct(interpreted_kernels, tmp_path, monkeypatch):
    cell = _judged()
    job = loader.load_job(cell)
    real = job.Program.dispatch
    half = cell["feed"]["batch_size"] // 2

    def halved(self, plans):
        plans = {k: np.array(v) for k, v in plans.items()}
        plans["event_mask"][:, half:] = False  # rows past the first half hold no event
        return real(self, plans)

    monkeypatch.setattr(job.Program, "dispatch", halved)
    monkeypatch.setattr(loader, "load_job", lambda c, root=None: job)
    record = run_tiny(cell, tmp_path)
    assert record["correct"] is False
    assert record["compared"]["grad_norm_gap"]["value"] > record["compared"]["grad_norm_gap"]["limit"]


def test_the_fp8_control_is_not_correct():
    """The reference in the program's place computed in fp8 reads at least
    three times what it reads in bfloat16 on ``grad_diff_gap``, so a limit
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
    lower = job.compare(cell, stated, *ref)["numbers"]["grad_diff_gap"]["value"]
    upper = job.compare(cell, control, *ref)["numbers"]["grad_diff_gap"]["value"]
    assert upper >= 3 * lower
    cell["check"]["limits"] = {"loss_gap": None, "grad_norm_gap": None, "param_change_gap": None,
                               "grad_diff_gap": (lower * upper) ** 0.5}
    assert job.compare(cell, stated, *ref)["ok"] is True
    assert job.compare(cell, control, *ref)["ok"] is False
