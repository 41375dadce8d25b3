"""Job kind ``pretrain_routed`` (`glm47flash_ep8.pretrain_packed`): its FLOP
and bytes functions against counts by hand, its per-layer readers on records
built by hand (a number with the scope, nothing without, never 0), and its
`correct` with a fault planted or the fp8 control in the program's place
(`test_faults.py` selects cells by the job name ``pretrain`` and does not see
this one)."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_routed, loader, scopes

from .test_faults import _first_plans
from .test_scopes import BWD, FWD, RAW, SOURCES
from .tiny import run_tiny, tiny_cell

CELL = "glm47flash_ep8.pretrain_packed"
READERS = ("moe_device_ms", "moe_dispatch_device_ms", "mla_device_ms", "moe_experts_roofline", "moe_load_max_over_mean")
MODEL = {
    "hidden_size": 8, "num_attention_heads": 2, "intermediate_size": 32,
    "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 5,
    "moe_intermediate_size": 16, "moe_router_width": 64, "n_routed_experts": 8, "n_shared_experts": 1,
    "ffn_layers": ["swiglu", "routed", "routed"], "tte_components": 3,
}
VOCAB = {
    "vocab_size": 48, "vocab_sizes": {"event_type": 5, "lab": 20, "med": 6, "demo": 16},
    "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
    "multivariate_regression": ["lab"],
}


def test_forward_flops_by_hand():
    # latent attention, a layer: projections 8*6 + 6*2*5 + 8*(4+2) + 4*2*(3+5) + 2*5*8 = 300 -> 600;
    #   core at 10 keys: QK^T 2*2*5*10 = 200, PV 2*2*5*10 = 200 -> 1000 a layer
    # dense layer: 2*3*8*32 = 1536; a routed layer: router 2*8*64 = 1024, shared 2*3*8*16 = 768,
    #   half a pair an event: 0.5*768 = 384 -> 2176
    # heads: 2*8*(48 + 40 + 9 + 4) = 1616
    want = 3 * 1000 + 1536 + 2 * 2176 + 1616
    assert flops_routed.latent_attention_flops(MODEL, 10) == 1000
    assert flops_routed.forward_flops_per_event(MODEL, VOCAB, global_keys=10, pairs_per_event=0.5) == want


def test_routed_experts_needs_by_hand():
    need = flops_routed.routed_experts_needs(pairs=1000, layer_steps=4, model=MODEL, itemsize=2)
    # a pair: three products of 8x16 -> 768 operations; rows read and written: 3*8 + 3*16 = 72 values
    # the held matrices: 4 layer-steps * 8 experts * 3*8*16 values
    rows, weights = 1000 * 72 * 2, 4 * 8 * 384 * 2
    assert need == {
        "fwd_flops": 768000, "bwd_flops": 1536000, "fwd_bytes": rows + weights, "bwd_bytes": 2 * rows + 2 * weights,
    }


# ---------------------------------------------------------------- the readers
KINDS_RAW = RAW + [
    ("%_gmm.4 = bf16[...] custom-call(...)", 1000, 400),
    ("%_tgmm.5 = bf16[...] custom-call(...)", 1400, 600),
    ("%fusion.20 = bf16[...] fusion(...)", 2000, 200),
    ("%sort.3 = s32[...] sort(...)", 2200, 50),
    ("%fusion.21 = f32[...] fusion(...)", 2250, 30),
    ("%fusion.22 = bf16[...] fusion(...)", 2300, 120),
    ("%fusion.23 = bf16[...] fusion(...)", 2500, 80),
]
KINDS_NAMES = {
    "_gmm.4": FWD + "h1/mlp/es.moe_experts/pallas_call",
    "_tgmm.5": BWD + "checkpoint/h1/mlp/es.moe_experts/pallas_call",
    "fusion.20": FWD + "h1/mlp/es.moe_dispatch/gather",
    "sort.3": FWD + "h1/mlp/es.moe_dispatch/sort",
    "fusion.21": FWD + "h1/mlp/es.moe_router/dot_general",
    "fusion.22": FWD + "h1/mlp/es.moe_shared/shared_experts/dot_general",
    "fusion.23": FWD + "h1/self_attn/es.attn_latent/q_b_proj/dot_general",
}


def _record(counters: dict | None = None) -> dict:
    steps = {"steps": 4}
    return {
        "counters": steps | (counters or {}), "end_to_end": {"train_events_per_s": 1.0},
        "model_sizes": MODEL, "device_kind": "TPU v5 lite",
    }


ROUTING = {"moe_pairs": 8000, "moe_load_max_sum": 600, "moe_routed_layers": 2, "moe_experts_held": 8}


def _traced(monkeypatch, raw, names):
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", lambda d: scopes.scoped(raw, names, {}))


def test_the_readers_read_the_kinds_blocks_scopes_and_counters(monkeypatch):
    _traced(monkeypatch, KINDS_RAW, SOURCES[0] | KINDS_NAMES)
    record = _record(ROUTING)
    got = {name: loader.metric_readers()[name].read(record) for name in READERS}
    per_step = 1e6 * 4
    assert got["moe_device_ms"] == pytest.approx((400 + 600 + 200 + 50 + 30 + 120) / per_step)
    assert got["moe_dispatch_device_ms"] == pytest.approx((200 + 50 + 30) / per_step)
    assert got["mla_device_ms"] == pytest.approx(80 / per_step)
    # 1,000 ns under es.moe_experts; the needs of 8,000 pairs over 8 layer-steps
    need = flops_routed.routed_experts_needs(8000, 8, MODEL, 2)
    t_flops = (need["fwd_flops"] + need["bwd_flops"]) / 197e12
    t_bytes = (need["fwd_bytes"] + need["bwd_bytes"]) / 819e9
    assert got["moe_experts_roofline"] == pytest.approx(100 * max(t_flops, t_bytes) / 1000e-9)
    # the steps' largest loads, 600 in all, over the mean load 8000 / (2 layers * 8 experts)
    assert got["moe_load_max_over_mean"] == pytest.approx(600 / 500)


def test_the_readers_find_nothing_in_a_cell_of_the_classic_block(monkeypatch):
    """A trace with scopes and none of the kinds block's, a record without the
    routing counters (the `ci_w1024` cells): nothing, never 0."""
    _traced(monkeypatch, RAW, SOURCES[0])
    record = _record()
    assert loader.metric_readers()["mlp_device_ms"].read(record) > 0
    for name in READERS:
        assert loader.metric_readers()[name].read(record) is None
    # and with no trace at all (the parent's program, a CPU rehearsal)
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: None)
    for name in READERS:
        assert loader.metric_readers()[name].read(_record()) is None


# ------------------------------------------------------------------ `correct`
@pytest.fixture()
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")


def _judged():
    cell = tiny_cell(CELL)
    if all(limit is None for limit in cell["check"]["limits"].values()):
        cell["check"]["limits"] = loader.load_cell("ci_w1024.pretrain_packed")["check"]["limits"]
    return cell


def test_the_record_carries_the_routing_counters_and_the_classic_readers_keys(interpreted_kernels, tmp_path):
    cell = tiny_cell(CELL)
    record = run_tiny(cell, tmp_path)
    counters, sizes = record["counters"], record["model_sizes"]
    assert record["correct"] is True
    assert {"events", "steps", "global_keys", "flops_per_event", "rows_per_step", "row_len"} <= set(counters)
    assert (sizes["seq_attention_types"], sizes["num_attention_heads"], sizes["head_dim"]) == (["global"], 4, 256)
    assert sizes["num_hidden_layers"] == 2 and counters["moe_routed_layers"] == 1
    # four choices of 64 an event, 8 of the 64 held: half a pair an event, at any routing at most four
    assert 0 < counters["moe_pairs"] <= 4 * counters["events"]
    assert counters["moe_pairs_per_event_layer"] == pytest.approx(counters["moe_pairs"] / counters["events"])
    # the largest load is read per batch shard of the rehearsal's mesh (at most the 8 virtual devices)
    assert counters["moe_pairs"] / (8 * 8) <= counters["moe_load_max_sum"] <= counters["moe_pairs"]
    assert loader.metric_readers()["moe_load_max_over_mean"].read(record) > 0  # at least 1 on one chip; a shard's load here


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
    # the worst leaf's change may lie a hair under the median leaf's, which it is measured against
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
    """As `test_faults.py` holds for the classic cells: the reference in the
    program's place computed in fp8 reads at least three times what it reads
    in bfloat16 on ``grad_diff_gap``, so a limit between the two readings
    passes the stated precision and fails the one below."""
    from benchmark.harness import cohort as cohort_lib

    cell = tiny_cell(CELL)
    cell["model"]["config"]["num_hidden_layers"] = 5  # the cell's depth: rounding adds up over it
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
