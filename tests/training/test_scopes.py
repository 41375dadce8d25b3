"""The scope contract inside the program (`eventstreamgpt_tpu/utils/scopes.py`).

The chunked train step is lowered and compiled on the CPU at the benchmark
rehearsal's tiny sizes (kernels interpreted) for CI packed, CI padded and NA;
the compiled text's ``op_name``s are what a device trace shows. A named scope
is metadata: the losses of one dispatch are the parent commit's to the bit.
"""

from __future__ import annotations

import re
import shutil
import tempfile
from pathlib import Path

import pytest

from eventstreamgpt_tpu.utils import scopes as program_scopes
from tests import SAMPLE_DIR as REF_SAMPLE
from tests.benchmark.tiny import tiny_cell

REPO = Path(__file__).resolve().parents[2]
SEED = 3000000019
# float32 losses of the first dispatch at SEED as the commit before the scopes
# gives them (f865e17, this sandbox's CPU, the conftest's 8 virtual devices).
PARENT_LOSSES = {
    "ci_w1024.pretrain_packed": ["0x1.606af80000000p+3", "0x1.61af700000000p+3", "0x1.611f580000000p+3", "0x1.5d58e60000000p+3"],
    "ci_w1024.pretrain_padded": ["0x1.5cdbf20000000p+3", "0x1.6d66840000000p+3", "0x1.5976780000000p+3", "0x1.5f61e20000000p+3"],
    "na_w1024.pretrain": ["0x1.5c6b2a0000000p+3", "0x1.70c5700000000p+3", "0x1.59d9620000000p+3", "0x1.602e640000000p+3"],
}
KINDS_CELL = "glm47flash_ep8.pretrain_packed"  # added by PR 28: it has no parent to be equal to
HYBRID_CELL = "nemotron_twotower_ep16.pretrain_packed"  # added by PR 32; compiled here at six layers, MEMEM*
STREAMED_CELL = "xing40_a4b_ep8.pretrain_packed"  # added by PR 34; compiled here at its dense and one routed layer
CONV_CELL = "lfm2_8b_a1b_ep4.pretrain_packed"  # compiled here at its first two layers: conv + dense, attention + routed
CELLS = sorted(PARENT_LOSSES) + [KINDS_CELL]
# Scopes a model does not have: CI has no dependency graph, the classic block
# none of the kinds block's (docs/layer_kinds.md), the kinds block no local layer;
# GLM's stack has no state-space layer, nemotron_h's no latent attention and no dense feed-forward.
# Only Xing4.0's block carries residual streams, only LFM2's gated short convolutions (and no shared expert).
SSM = {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate"}
STREAMS = {"hc_maps", "hc_mix"}
CONV = {"conv_proj", "conv_gate"}
KINDS = {"attn_latent", "moe_router", "moe_dispatch", "moe_experts", "moe_shared"} | SSM | STREAMS | CONV
ABSENT = {
    "ci_w1024.pretrain_packed": {"dep_graph"} | KINDS,
    "ci_w1024.pretrain_padded": {"dep_graph"} | KINDS,
    "na_w1024.pretrain": KINDS,
    KINDS_CELL: {"dep_graph", "attn_local"} | SSM | STREAMS | CONV,
    HYBRID_CELL: {"dep_graph", "attn_local", "attn_latent", "mlp"} | STREAMS | CONV,
    STREAMED_CELL: {"dep_graph", "attn_local"} | SSM | CONV,
    CONV_CELL: {"dep_graph", "attn_local", "attn_latent", "moe_shared"} | SSM | STREAMS,
}
# Instructions of the scan body with an op_name and no es. scope, at most:
# constants and broadcasts the compiler hoists, the scan's own slicing, the
# residual adds and the masking between layers.
UNSCOPED_SHARE = 0.40


@pytest.fixture(scope="module")
def compiled():
    """Per cell: the compiled chunked step's ``op_name``s and one dispatch's losses."""
    done = {}

    def get(name):
        if name not in done:
            import jax

            from benchmark.harness import cohort as cohort_lib
            from benchmark.harness import loader
            from eventstreamgpt_tpu.parallel.context import kernel_mesh

            with pytest.MonkeyPatch.context() as patch:
                patch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
                cell = tiny_cell(name)
                if name == HYBRID_CELL:  # the rehearsal's two layers are ME: take in the attention layer
                    cell["model"]["config"]["num_hidden_layers"] = 6
                cohort = cohort_lib.make_cohort(cell["cohort"], SEED)
                work = Path(tempfile.mkdtemp(prefix="scopes_"))
                prog = loader.load_job(cell).Program(cell, cohort, loader.load_reference(cell), SEED, work)
                plans, _ = prog.next_plans()
                with kernel_mesh(prog.mesh):
                    text = prog.step.lower(prog.state, prog.device_data.arrays, plans, prog.rng).compile().as_text()
                losses = jax.device_get(prog.dispatch(plans))
                shutil.rmtree(work, ignore_errors=True)
            done[name] = re.findall(r'op_name="([^"]*)"', text), [float(x).hex() for x in losses]
        return done[name]

    return get


def _scope(op_name: str):
    """The scope as the benchmark's reader takes it from a path."""
    from benchmark.harness.scopes import scope_of

    return scope_of(op_name)[0]


@pytest.mark.parametrize("name", CELLS + [HYBRID_CELL, STREAMED_CELL, CONV_CELL])
def test_every_scope_the_model_has_occurs(name, compiled):
    op_names, _ = compiled(name)
    seen = {_scope(n) for n in op_names} - {None}
    assert seen <= set(program_scopes.SCOPES)
    assert set(program_scopes.SCOPES) - seen == ABSENT[name]


@pytest.mark.parametrize("name", CELLS)
def test_all_three_phases_occur_under_the_mlp(name, compiled):
    op_names, _ = compiled(name)
    mlp = [n for n in op_names if _scope(n) == "mlp"]
    recompute = [n for n in mlp if "rematted_computation" in n]
    backward = [n for n in mlp if "transpose(" in n and "rematted_computation" not in n]
    forward = [n for n in mlp if "transpose(" not in n and "rematted_computation" not in n]
    assert forward and backward
    # the NA rehearsal runs without a remat policy (as its cell does): nothing is computed again
    assert bool(recompute) == (name != "na_w1024.pretrain")


@pytest.mark.parametrize("name", CELLS + [HYBRID_CELL, STREAMED_CELL, CONV_CELL])
def test_most_of_the_scan_body_is_under_a_scope(name, compiled):
    op_names, _ = compiled(name)
    body = [n for n in op_names if "/while/body/" in n]
    assert len(body) > 1000
    unscoped = sum(_scope(n) is None for n in body)
    assert unscoped / len(body) <= UNSCOPED_SHARE


@pytest.mark.parametrize("name", sorted(PARENT_LOSSES))
def test_the_losses_of_one_dispatch_are_the_parents_bit_for_bit(name, compiled):
    _, losses = compiled(name)
    assert losses == PARENT_LOSSES[name]


def test_a_name_outside_the_contract_raises():
    with pytest.raises(ValueError, match="nonsense"):
        program_scopes.scope("nonsense")
    with pytest.raises(ValueError, match="nonsense"):
        program_scopes.scoped("nonsense")
    with pytest.raises(ValueError, match="nonsense"):
        program_scopes.host_span("nonsense")
    assert len(set(program_scopes.SCOPES)) == len(program_scopes.SCOPES)


def _program_files():
    return sorted((REPO / "eventstreamgpt_tpu").rglob("*.py"))


def test_one_contract_and_no_knob():
    """`jax.named_scope` and `TraceAnnotation` are called in utils/scopes.py
    only; every ``es.`` name written anywhere in the program is in the
    contract; the compile cache's key is left as JAX sets it."""
    names = set()
    for path in _program_files():
        text = path.read_text()
        if path.name != "scopes.py" or path.parent.name != "utils":
            assert "named_scope(" not in text and "TraceAnnotation(" not in text, path
            names |= set(re.findall(r"""["']es\.([A-Za-z0-9_/]+)""", text))
        for call, known in (
            ("scope", program_scopes.SCOPES), ("scoped", program_scopes.SCOPES),
            ("host_span", program_scopes.HOST_SPANS), ("host_spanned", program_scopes.HOST_SPANS), ("record", program_scopes.HOST_SPANS),
        ):
            for used in re.findall(rf"\b{call}\(\"([a-z_/]+)\"", text):
                assert used in known, (path, call, used)
    assert names <= set(program_scopes.SCOPES) | {"host/" + n for n in program_scopes.HOST_SPANS}
    for path in [*_program_files(), *(REPO / "benchmark").rglob("*.py"), REPO / "chip_smoke.py"]:
        assert "include_metadata_in_key" not in path.read_text(), path


def test_an_operators_trace_holds_the_loop(tmp_path, monkeypatch):
    """`train()` with ``trainer_config.profile_dir``: the traces it writes hold
    the dispatch loop's host spans and the step's scopes; the spans around the
    evaluation and the epoch's last save are entered too (they lie after the
    traced steps, so a trace started around `train()` shows them)."""
    from jax.profiler import ProfileData

    from eventstreamgpt_tpu.data import PytorchDatasetConfig
    from eventstreamgpt_tpu.models.config import MetricsConfig, OptimizationConfig
    from eventstreamgpt_tpu.training import PretrainConfig, train
    from eventstreamgpt_tpu.training import pretrain as pretrain_module

    data = tmp_path / "ds"
    data.mkdir()
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, data / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", data / "DL_reps")
    shutil.copy(data / "DL_reps" / "tuning_0.parquet", data / "DL_reps" / "train_0.parquet")
    entered = []
    real = pretrain_module.host_span

    def spy(name, **given):
        entered.append(name)
        return real(name, **given)

    monkeypatch.setattr(pretrain_module, "host_span", spy)
    cfg = PretrainConfig(
        seed=1,
        config=dict(
            hidden_size=32, head_dim=8, num_attention_heads=4, num_hidden_layers=2, intermediate_size=32,
            TTE_generation_layer_type="log_normal_mixture", TTE_lognormal_generation_num_components=2,
        ),
        optimization_config=OptimizationConfig(
            init_lr=1e-3, max_epochs=20, batch_size=4, validation_batch_size=4, lr_frac_warmup_steps=0.5,
            patience=None, max_training_steps=14,
        ),
        data_config=PytorchDatasetConfig(save_dir=data, max_seq_len=16, min_seq_len=2),
        pretraining_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        final_validation_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        experiment_dir=str(tmp_path),
        save_dir=str(tmp_path / "pretrain"),
        do_final_validation_on_metrics=False,
        trainer_config={
            "log_every_n_steps": 1, "checkpoint_every_n_steps": 3, "device_resident_data": True,
            "steps_per_execution": 2, "profile_dir": str(tmp_path / "profile"),
        },
    )
    train(cfg)
    assert {"dispatch", "checkpoint", "log_flush", "eval"} <= set(entered)

    files = sorted((tmp_path / "profile").rglob("*.xplane.pb"))
    assert files
    spans, blob = set(), b""
    for path in files:
        blob += path.read_bytes()
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                spans |= {ev.name for ev in line.events if ev.name.startswith("es.host/")}
    assert {"es.host/plan", "es.host/dispatch", "es.host/checkpoint", "es.host/log_flush"} <= spans
    # the compiled step the trace's metadata carries names its operations
    assert b"es.optimizer" in blob and b"es.mlp" in blob and b"es.collate" in blob
