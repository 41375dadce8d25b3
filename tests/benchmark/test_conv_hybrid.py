"""Job kind ``pretrain_conv`` (`lfm2_8b_a1b_ep4.pretrain_packed`): its FLOP
count against a count by hand, the accepted readers the cell is listed for on
a record of its job built by hand, the whole model at the cell's depth
against the plain reference, the cut the configuration file states, the
refusal of a program with no ``conv`` mixer (the parent's), and `correct` with
a fault planted or the fp8 control in the program's place (`test_faults.py`
selects cells by the job name ``pretrain`` and does not see this one)."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_conv, flops_hybrid, flops_routed, loader, scopes
from benchmark.harness import trace as trace_lib
from benchmark.harness.flops import attention_needs

from .test_faults import _first_plans
from .test_scopes import BWD, FWD, RAW, SOURCES
from .tiny import run_tiny, tiny_cell

CELL = "lfm2_8b_a1b_ep4.pretrain_packed"
MODEL = {
    "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3, "intermediate_size": 20,
    "mixer_layers": ["conv", "mha", "conv"], "ffn_layers": ["swiglu", "routed", "routed"], "conv_L_cache": 3,
    "moe_intermediate_size": 16, "moe_router_width": 32, "n_routed_experts": 8, "tte_components": 3,
}
VOCAB = {
    "vocab_size": 48, "vocab_sizes": {"event_type": 5, "lab": 20, "med": 6, "demo": 16},
    "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
    "multivariate_regression": ["lab"],
}


def test_forward_flops_by_hand():
    # a conv mixer: W_in 2*8*24 = 384, W_out 2*8*8 = 128 -> 512
    # attention at 10 keys: q, o 2 * 2*8*12 = 384, k, v 2 * 2*8*6 = 192, QK^T and PV 2 * 2*4*3*10 = 480 -> 1056
    # the dense layer: 2*3*8*20 = 960; a routed layer: router 2*8*32 = 512, a pair an event 2*3*8*16 = 768 -> 1280
    # heads: 2*8*(48 + 40 + 9 + 4) = 1616
    assert flops_conv.conv_mixer_flops(MODEL) == 512
    assert flops_hybrid.attention_layer_flops(MODEL, 10) == 1056
    want = 512 + 960 + 1056 + 1280 + 512 + 1280 + 1616
    assert flops_conv.forward_flops_per_event(MODEL, VOCAB, global_keys=10, pairs_per_event=1.0) == want


# ---------------------------------------------------------------- the readers
CONV_RAW = RAW + [
    ("%fusion.60 = bf16[...] fusion(...)", 1000, 300),
    ("%fusion.61 = f32[...] fusion(...)", 1300, 40),
    ("%_gmm.4 = bf16[...] custom-call(...)", 1340, 400),
    ("%_tgmm.5 = bf16[...] custom-call(...)", 1740, 600),
    ("%sort.3 = s32[...] sort(...)", 2340, 50),
]
CONV_NAMES = {
    "fusion.60": FWD + "h0/conv/es.conv_proj/in_proj/dot_general",
    "fusion.61": BWD + "checkpoint/h0/conv/es.conv_gate/checkpoint/mul",
    "_gmm.4": FWD + "h1/mlp/es.moe_experts/pallas_call",
    "_tgmm.5": BWD + "checkpoint/h1/mlp/es.moe_experts/pallas_call",
    "sort.3": FWD + "h1/mlp/es.moe_dispatch/sort",
}
ROUTING = {"moe_pairs": 6000, "moe_load_max_sum": 500, "moe_routed_layers": 2, "moe_experts_held": 8}


def _record(counters: dict | None = None) -> dict:
    return {
        "counters": {"steps": 4, "events": 2000, "global_keys": 10.0} | (counters or {}),
        "end_to_end": {"train_events_per_s": 1.0},
        "model_sizes": MODEL | {"num_hidden_layers": 3, "seq_attention_types": ["none", "global", "none"]},
        "device_kind": "TPU v5 lite",
    }


def _traced(monkeypatch, raw, names):
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", lambda d: scopes.scoped(raw, names, {}))


def test_the_listed_readers_read_the_cells_record(monkeypatch):
    """The readers whose lists name this cell read its job's counters and
    sizes: the gated experts' three products of every counted pair, the
    routing balance; the scope readers that are right for any stack read
    the routed layers; the state-space and ungated experts' readers read
    nothing here."""
    _traced(monkeypatch, CONV_RAW, SOURCES[0] | CONV_NAMES)
    readers, record = loader.metric_readers(), _record(ROUTING)
    need = flops_routed.routed_experts_needs(6000, 8, MODEL, 2)
    t = max((need["fwd_flops"] + need["bwd_flops"]) / 197e12, (need["fwd_bytes"] + need["bwd_bytes"]) / 819e9)
    assert readers["moe_experts_roofline"].read(record) == pytest.approx(100 * t / 1000e-9)
    assert readers["moe_load_max_over_mean"].read(record) == pytest.approx(500 / (6000 / 16))
    assert readers["moe_device_ms"].read(record) == pytest.approx((400 + 600 + 50) / 4e6)
    for name in ("ssm_device_ms", "ssm_scan_roofline", "relu2_experts_roofline", "routed_load_max_over_mean", "hc_roofline"):
        assert readers[name].read(record) is None


def test_the_flash_roofline_counts_the_one_attention_layer_at_its_head_width(monkeypatch):
    """`flash_attn_roofline` reads ``seq_attention_types``: one global layer of
    the three, 4 heads of 3 here (32 of 64 in the cell), the keys the job counted."""
    record = _record() | {"trace": {"ops": []}}
    monkeypatch.setattr(trace_lib, "kernel_ns", lambda ops, names: (2000.0, 3))
    need = attention_needs(2000 * 1, 10.0, 4, 3, 2)
    t = max((need["fwd_flops"] + need["bwd_flops"]) / 197e12, (need["fwd_bytes"] + need["bwd_bytes"]) / 819e9)
    assert loader.metric_readers()["flash_attn_roofline"].read(record) == pytest.approx(100 * t / 2000e-9)


def test_the_gated_taps_bytes_by_hand():
    """`conv_gate_needs`, what a roofline of the taps would read: two conv
    mixers of three layers at hidden 8 and 3 taps, 2,000 events, bfloat16.
    Forward reads the projection (24 wide) and writes the output (8); backward
    reads both and writes the projection's gradient (24); two operations a tap
    and one a gate, elementwise."""
    need = flops_conv.conv_gate_needs(2000, MODEL, 2)
    assert need["fwd_bytes"] == 2000 * 2 * (24 + 8) * 2
    assert need["bwd_bytes"] == 2000 * 2 * (24 + 8 + 24) * 2
    assert need["fwd_flops"] == 2000 * 2 * (2 * 3 + 2) * 8 and need["bwd_flops"] == 2 * need["fwd_flops"]
    assert flops_conv.conv_gate_needs(2000, MODEL | {"mixer_layers": ["mha"] * 3}, 2)["fwd_bytes"] == 0


def test_the_manifest_lists_the_cell_where_its_readers_read():
    """Appended, and nothing else changed: the cell at the end of the gated
    experts' readers' lists; no reader of the convolution is listed (the
    accepted tests pin the manifest's last three per-layer entries and
    `flash_attn_roofline`'s, `moe_device_ms`' and `moe_dispatch_device_ms`'
    last cell, PERF.md section 7)."""
    manifest = json.loads((loader.ROOT.parent / "BENCHMARK.json").read_text())
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    for name in ("moe_experts_roofline", "moe_load_max_over_mean"):
        assert listed[name] == ["glm47flash_ep8.pretrain_packed", "xing40_a4b_ep8.pretrain_packed", CELL]
    for name in ("flash_attn_roofline", "moe_device_ms", "moe_dispatch_device_ms"):
        assert CELL not in listed[name]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[-1] == CELL and [c["name"] for c in manifest["configs"]][-1] == "lfm2_8b_a1b_ep4"
    assert not [name for name in loader.metric_readers() if "conv" in name]


# ------------------------------------------------------------ the whole model
@pytest.fixture()
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")


def _cell_at_depth():
    """The tiny cell at the cell's own depth, ``conv + dense``, ``attention +
    routed``, three ``conv + routed`` (the rehearsal's two layers are the first two)."""
    cell = tiny_cell(CELL)
    cell["model"]["config"]["num_hidden_layers"] = 5
    return cell


def test_the_five_layer_model_agrees_with_the_reference_and_the_record_carries_the_counters(interpreted_kernels, tmp_path):
    """Float32 on both sides, packed rows of 32 events whose segments start
    wherever the packing puts them: the loss of four optimizer steps to 1e-6,
    every leaf's first moment to 1e-5 of the reference's. The rehearsal's four
    query heads take one key/value head, as the published 32 take 8."""
    cell = _cell_at_depth()
    record = run_tiny(cell, tmp_path)
    counters, sizes, compared = record["counters"], record["model_sizes"], record["compared"]
    assert record["correct"] is True
    assert compared["loss_gap"]["value"] < 1e-6
    assert compared["grad_diff_gap"]["value"] < 1e-5 and compared["grad_norm_gap"]["value"] < 1e-5
    assert sizes["mixer_layers"] == ["conv", "mha", "conv", "conv", "conv"]
    assert sizes["ffn_layers"] == ["swiglu", "routed", "routed", "routed", "routed"]
    assert sizes["seq_attention_types"] == ["none", "global", "none", "none", "none"]
    assert (sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]) == (4, 1, 8)
    assert counters["moe_routed_layers"] == 4 and counters["moe_experts_held"] == 8
    assert 0 < counters["moe_pairs"] <= 4 * 4 * counters["events"]
    readers = loader.metric_readers()
    assert readers["moe_load_max_over_mean"].read(record) > 0
    assert readers["routed_load_max_over_mean"].read(record) is None


def test_the_even_bias_spreads_leaning_scores_evenly():
    """`even_bias` on scores that lean to a few experts (the largest load
    about three times the mean at a bias of zero): every one of the 32 experts
    takes its share of the choices to a hundredth, and the bias is centred."""
    import jax
    import jax.numpy as jnp

    reference = loader.load_reference(loader.load_cell(CELL))
    rng = np.random.default_rng(5)
    x = rng.normal(0.0, 1.0, (20000, 64)) + rng.normal(0.0, 1.0, 64)
    scores = jax.nn.sigmoid(jnp.asarray(x @ rng.normal(0.0, 0.1, (64, 32)), jnp.float32))
    weight = jnp.asarray(rng.random(20000) < 0.9, jnp.float32)  # padding slots count for nothing

    def loads(bias):
        chosen = np.asarray(jax.lax.top_k(scores + bias, 4)[1])
        return np.bincount(chosen.reshape(-1), np.repeat(np.asarray(weight), 4), minlength=32)

    mean = float(weight.sum()) * 4 / 32
    assert loads(jnp.zeros(32)).max() > 2 * mean
    bias = reference.even_bias(scores, weight, 4)
    assert np.abs(loads(bias) / mean - 1).max() < 0.01
    assert abs(float(bias.mean())) < 1e-6


def test_the_seeds_selection_biases_even_its_first_dispatch_layer_by_layer():
    """`balance_selection_bias` three layers deep (two routed): under the
    biases it gives, each routed layer spreads the first dispatch's real
    events evenly over the 32 experts (about 20 pairs each), and the eight
    held here take their quarter, the second layer's scores those the first
    layer's bias gives; at a bias of zero they lean."""
    import jax

    from benchmark.harness import cohort as cohort_lib
    from benchmark.harness import pretrain as base

    cell = _cell_at_depth()
    cell["model"]["config"]["num_hidden_layers"] = 3
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    cohort = cohort_lib.make_cohort(cell["cohort"], 7)
    sizes = job.reference_model(cell, cohort)
    params = reference.init_params(sizes, cohort.vocab, jax.random.PRNGKey(7))
    batches = base.reference_batches(cell, cohort, _first_plans(cell, cohort, 7))
    rows = cell["check"]["rows_per_block"]
    # The blocks of rows the balancing runs its forward over: a row at a tie reads the same here.
    blocks = [{k: (None if v is None else v[lo : lo + rows]) for k, v in b.items()}
              for b in batches for lo in range(0, b["event_mask"].shape[0], rows)]
    mask = np.concatenate([b["event_mask"].reshape(-1) for b in blocks])
    mean = mask.sum() * 4 / 32
    scores_of = jax.jit(lambda p, b: reference.encode(p["params"], b, sizes, None)[1])

    def loads(params):
        encoder = params["params"]["encoder"]
        scores = [scores_of(params, b) for b in blocks]
        out = []
        for i, name in enumerate(reference.routed_layers(sizes)):
            layer = np.concatenate([np.asarray(s[i]).reshape(-1, 32) for s in scores])
            bias = np.asarray(encoder[name]["mlp"]["e_score_correction_bias"])
            chosen = np.asarray(jax.lax.top_k(layer + bias, 4)[1])
            out.append(np.bincount(chosen[mask].reshape(-1), minlength=32))
        return np.array(out)

    assert loads(params).max() > 1.5 * mean
    biases = reference.balance_selection_bias(params, batches, sizes, rows)
    assert list(biases) == reference.routed_layers(sizes) == ["h1", "h2"]
    got = loads(reference.with_selection_bias(params, biases))
    # A balanced assignment leaves rows at ties, which rounding here decides
    # otherwise than in the balancing's own forward: a few pairs of about 20.
    assert np.abs(got - mean).max() <= 0.2 * mean, got
    held = got[:, :8].sum(-1) / (8 * mean)  # this chip's eight experts
    assert np.abs(held - 1).max() < 0.03, held


def test_the_balancing_rows_are_whole_histories_the_checked_dispatch_does_not_hold():
    """`balancing_plans`: the feed's shape, rows of whole histories in the
    seed's order, none of the subjects of the dispatch `correct` is decided on,
    and the same rows for the same seed."""
    from benchmark.harness import cohort as cohort_lib

    cell = tiny_cell(CELL)
    job = loader.load_job(cell)
    cohort = cohort_lib.make_cohort(cell["cohort"], 11)
    plans = _first_plans(cell, cohort, 11)
    got = job.balancing_plans(cell, cohort, plans, 11)
    feed = cell["feed"]
    assert got["event_ids"].shape == (feed["steps_per_dispatch"], feed["batch_size"], feed["seq_len"])
    subject = lambda p: np.searchsorted(cohort.offsets, p["event_ids"][p["event_mask"]], side="right") - 1  # noqa: E731
    held = subject(got)
    assert len(held) and not set(held.tolist()) & set(subject(plans).tolist())
    lengths = np.diff(cohort.offsets)
    for s in np.unique(held):
        assert (held == s).sum() == min(lengths[s], feed["seq_len"])  # whole, once
    again = job.balancing_plans(cell, cohort, plans, 11)
    assert all(np.array_equal(got[k], again[k]) for k in got)


def test_the_configuration_file_states_the_cut_and_the_built_models_count():
    """Every width is the catalog row's, `reduced` is the four cuts, the layer
    kinds are published layers 0 and 2-5, and the parameter count in the file
    is the built model's at the cell's vocabulary."""
    import jax

    from benchmark.harness import cohort as cohort_lib

    cell = loader.load_cell(CELL)
    model, config = cell["model"], cell["model"]["config"]
    assert model["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert set(model["reduced_why"]) == set(model["published"]) == set(model["reduced"])
    kinds = {"conv": "conv", "full_attention": "mha"}
    published = [kinds[t] for t in model["layer_types"]]
    assert len(published) == model["published"]["num_hidden_layers"] == 24
    assert config["mixer_types"] == [published[i] for i in (0, 2, 3, 4, 5)]
    assert config["ffn_types"] == ["swiglu"] * model["num_dense_layers"] + ["routed"] * 4
    assert model["num_hidden_layers"] == config["num_hidden_layers"] == 5
    for ours, theirs in (
        ("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"), ("conv_L_cache", "conv_L_cache"),
        ("conv_bias", "conv_bias"), ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"), ("rope_theta", "rope_theta"),
        ("moe_intermediate_size", "moe_intermediate_size"), ("num_experts_per_tok", "num_experts_per_tok"),
        ("routed_scaling_factor", "routed_scaling_factor"), ("norm_topk_prob", "norm_topk_prob"),
        ("layer_norm_epsilon", "norm_eps"), ("n_routed_experts", "num_experts"),
        ("moe_router_width", ("published", "num_experts")),
    ):
        want = model[theirs[0]][theirs[1]] if isinstance(theirs, tuple) else model[theirs]
        assert config[ours] == want, ours
    assert config["head_dim"] * config["num_attention_heads"] == config["hidden_size"]
    assert config["mha_qk_norm"] and config["mha_rope"] and config["n_shared_experts"] == 0
    assert config["moe_topk_norm_eps"] == 1e-6
    cell["cohort"]["n_subjects"] = 8  # the vocabulary is the cell's, whatever the number of histories
    cohort = cohort_lib.make_cohort(cell["cohort"], 1)
    assert cohort.vocab["vocab_size"] == model["vocab_size"] == 16384 == model["published"]["vocab_size"] // 4
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    shapes = jax.eval_shape(
        lambda key: reference.init_params(job.reference_model(cell, cohort), cohort.vocab, key), jax.random.PRNGKey(0)
    )
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert f"{built:,} parameters" in model["assumed"]["sizes"]


def test_a_program_without_the_conv_mixer_is_refused_at_once(monkeypatch):
    """The parent's program knows no ``conv`` mixer: its configuration refuses
    the kind, or (had it stored the keys it does not know and built another
    mixer) the job refuses it, before anything is built."""
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    cell = loader.load_cell(CELL)
    job = loader.load_job(cell)
    assert job.model_config(cell).mixer_layers[0] == "conv"
    monkeypatch.setattr(StructuredTransformerConfig, "MIXER_KINDS", ("mha", "latent", "ssm", "none"))
    with pytest.raises(ValueError, match="mixer_types must be of"):
        job.model_config(cell)
    with pytest.raises(ValueError, match="mixer_types must be of"):
        job.run(cell, 1, 1.0, False, env=None)


def test_the_key_value_heads_follow_a_cut_of_the_query_heads():
    """`program_cell`: the cell as it is, unless its query heads were cut to no
    multiple of its key/value heads; then the published four queries a key/value head."""
    job = loader.load_job(loader.load_cell(CELL))
    cell = loader.load_cell(CELL)
    assert job.program_cell(cell) is cell
    cut = tiny_cell(CELL)
    assert job.program_cell(cut)["model"]["config"]["num_key_value_heads"] == 1
    cut["model"]["config"]["num_attention_heads"] = 12
    assert job.program_cell(cut)["model"]["config"]["num_key_value_heads"] == 3
    assert cut["model"]["config"]["num_key_value_heads"] == 8  # the cell it was given is left as it was


# ------------------------------------------------------------------ `correct`
def _judged():
    cell = _cell_at_depth()
    cell["model"]["config"]["num_hidden_layers"] = 2
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
    three times what it reads in bfloat16 on ``grad_diff_median``, so a limit
    between the two readings passes the stated precision and fails the one
    below."""
    from benchmark.harness import cohort as cohort_lib

    cell = _judged()
    job, reference = loader.load_job(cell), loader.load_reference(cell)
    seed = 78
    cohort = cohort_lib.make_cohort(cell["cohort"], seed)
    cell = job.program_cell(cell)
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
