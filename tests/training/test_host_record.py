"""The program's own host record (`eventstreamgpt_tpu/utils/scopes.py`): spans
with parents and self time, one id a dispatch, a bounded ring beside kept
start-up and compile spans, JAX's compile events by program, the feed's counts
on the plan spans, and what `train()` writes of it to ``train_log.jsonl``.

The record is the process's: every test reads what was recorded after a place
it marks itself.
"""

from __future__ import annotations

import json
import shutil
import time
import warnings

import numpy as np
import pytest

from eventstreamgpt_tpu.utils import scopes
from tests.benchmark.tiny import tiny_cell
from tests.training.test_scopes import PARENT_LOSSES, SEED


def _since(t0: float, name: str | None = None) -> list:
    return [s for s in scopes.recorded() if s.start >= t0 and (name is None or s.name == name)]


def test_nested_spans_have_parents_and_self_time():
    t0 = time.perf_counter()
    with scopes.host_span("eval", id=7, batches=3) as outer:
        time.sleep(0.02)
        with scopes.host_span("checkpoint") as inner:
            time.sleep(0.03)
        with scopes.host_span("log_flush", id="other"):
            pass
    evaluated, saved, flushed = _since(t0)
    assert (evaluated.name, saved.name, flushed.name) == ("eval", "checkpoint", "log_flush")
    assert evaluated.parent is None and saved.parent == flushed.parent == evaluated.seq == outer.seq
    assert (evaluated.id, saved.id, flushed.id) == (7, 7, "other")  # a span without an id takes its parent's
    assert evaluated.counts == {"batches": 3} and saved.counts == {} and inner.seq == saved.seq
    assert evaluated.start <= saved.start <= saved.end <= flushed.start <= flushed.end <= evaluated.end
    own = scopes.self_seconds(_since(t0))
    assert own[saved.seq] == saved.end - saved.start >= 0.03
    took = evaluated.end - evaluated.start
    assert own[evaluated.seq] == pytest.approx(took - own[saved.seq] - own[flushed.seq]) and 0.02 <= own[evaluated.seq] < took


def test_the_ring_is_bounded_and_start_up_spans_survive_it():
    t0 = time.perf_counter()
    with scopes.host_span("startup/state", id="startup"):
        pass
    scopes.record("startup/import", t0, id="startup")
    for i in range(5000):
        with scopes.host_span("dispatch", id=i):
            pass
    spans = _since(t0)
    dispatches = [s for s in spans if s.name == "dispatch"]
    assert len(dispatches) <= 4096 and dispatches[-1].id == 4999  # the oldest went
    assert len([s for s in scopes.recorded() if not s.name.startswith(("startup/", "compile/"))]) <= 4096
    for i in range(17000):  # a process that never stops compiling: the newest compile spans stay, the start-up's too
        scopes.record("compile/trace", t0, t0, id=f"program_{i}")
    spans = _since(t0)
    compiles = [s for s in spans if s.name == "compile/trace"]
    assert len(compiles) <= 16384 and {s.id for s in compiles} >= {"program_16999", "program_616"}
    kept = [s for s in spans if s.name.startswith("startup/")]
    assert [s.name for s in kept] == ["startup/import", "startup/state"]
    assert kept[1].parent == kept[0].seq  # written after the fact, it takes what it spans as its child
    assert [s for s in scopes.since(t0) if s.name.startswith("startup/")] == kept and not scopes.since(time.perf_counter())


def test_threads_that_record_while_the_record_is_read_lose_nothing():
    import sys
    import threading

    t0, failures = time.perf_counter(), []

    def work(n: int) -> None:
        try:
            for i in range(300):
                with scopes.host_span("startup/state", id=f"thread_{n}"):
                    scopes.record("compile/trace", time.perf_counter(), id=f"thread_{n}")
        except Exception as e:  # noqa: BLE001 -- reported below, on the test's thread
            failures.append(e)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(n,)) for n in range(8)]
        for thread in threads:
            thread.start()
        while any(thread.is_alive() for thread in threads) and time.perf_counter() - t0 < 60:
            scopes.recorded(), scopes.since(t0), scopes.compile_totals()  # a deque read while it grows would raise
        for thread in threads:
            thread.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not failures and not any(thread.is_alive() for thread in threads)
    mine = [s for s in scopes.since(t0) if str(s.id).startswith("thread_")]
    assert len(mine) == 8 * 300 * 2
    by_seq = {s.seq: s for s in mine}
    traces = [s for s in mine if s.name == "compile/trace"]
    assert all(by_seq[s.parent].id == s.id for s in traces)  # each under the span open on its own thread


def test_a_name_outside_the_contract_raises():
    for make in (scopes.host_span, scopes.host_spanned, lambda name: scopes.record(name, 0.0)):
        with pytest.raises(ValueError, match="startup/nonsense"):
            make("startup/nonsense")
    assert len(set(scopes.HOST_SPANS)) == len(scopes.HOST_SPANS)


def test_a_jitted_functions_first_call_records_its_compile_and_its_second_none():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def halve(x):
        return x / 2

    @jax.jit
    def host_record_probe(x):
        return halve(x) + 1

    x = jnp.arange(3.0)
    jax.block_until_ready(x)
    compiles, t0 = scopes.compile_totals()["backend"], time.perf_counter()
    with scopes.host_span("dispatch", id=0) as dispatch:
        host_record_probe(x)
    group = [s for s in _since(t0) if s.id == "host_record_probe"]
    assert [s.name for s in group] == ["compile/trace", "compile/lower", "compile/backend"]
    assert all(s.parent == dispatch.seq and dispatch.start <= s.end for s in group)
    assert group[2].counts == {"hit": 0} and scopes.compile_totals()["backend"] == compiles + 1
    assert not [s for s in _since(t0) if s.id == "halve"]  # traced inside the probe's trace: in its time, no span of its own
    row = scopes.summary(_since(t0), small=3600.0)["compile"]  # small programs fold into one row (under 0.1 s by default: a loaded machine's compile is not)
    assert set(row) == {"(other)"} and row["(other)"]["compiles"] == 1
    by_program = scopes.summary(_since(t0), small=0.0)["compile"]
    assert set(by_program) == {"host_record_probe"}
    assert by_program["host_record_probe"]["trace"] == pytest.approx(group[0].end - group[0].start)
    t1 = time.perf_counter()
    host_record_probe(x)
    assert not [s for s in _since(t1) if s.name.startswith("compile/")]


# ------------------------------------------------------------ the feed's counts
def _pairs_by_hand(seg: np.ndarray, chunk: int) -> tuple[int, int]:
    """Chunk pairs between a query chunk's first and last key chunk that hold
    a visible pair (key at or before the query, same segment id; padding is
    ``-1`` and sees its padded predecessors), and the dense pairs, by loops."""
    n = seg.shape[-1] // chunk
    visited = 0
    for row in seg.reshape(-1, seg.shape[-1]):
        visible = (np.arange(len(row))[None, :] <= np.arange(len(row))[:, None]) & (row[None, :] == row[:, None])
        for i in range(n):
            hit = [j for j in range(n) if visible[i * chunk : (i + 1) * chunk, j * chunk : (j + 1) * chunk].any()]
            visited += hit[-1] - hit[0] + 1
    return visited, seg[..., 0].size * n * n


@pytest.fixture(scope="module")
def long_cohort(tmp_path_factory):
    """A cohort whose rows are whole 128-event chunks: 40 histories of up to 256 events."""
    from benchmark.harness import cohort as cohort_lib
    from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig

    spec = tiny_cell("ci_w1024.pretrain_packed")["cohort"]
    spec.update(n_subjects=40, mean_seq_len=90, max_seq_len=256)
    cohort = cohort_lib.make_cohort(spec, 11)
    data = cohort_lib.write_dl_cache(cohort, spec, tmp_path_factory.mktemp("long_cohort"))
    return data, JaxDataset(PytorchDatasetConfig(save_dir=data, max_seq_len=256, min_seq_len=2), split="train")


@pytest.mark.parametrize("packed, heads, chunk", [(True, (4, 8, 8), 256), (True, (4, 192, 128), 128), (False, (4, 8, 8), 256), (False, (2, 256, 256), 128)])
def test_plan_spans_carry_the_feeds_counts(long_cohort, packed, heads, chunk):
    from types import SimpleNamespace

    from eventstreamgpt_tpu.data.device_dataset import DeviceDataset
    from eventstreamgpt_tpu.training.pretrain import _flash_pair_counter

    _, dataset = long_cohort
    t0 = time.perf_counter()
    feed = DeviceDataset.create(dataset)
    assert [s.id for s in _since(t0, "startup/device_tables")] == ["startup"]
    heads, width, value_width = heads
    kind = dict(mixer_layers=[], head_dim=width)
    if width != value_width:
        kind = dict(mixer_layers=["latent"], qk_nope_head_dim=width - 64, qk_rope_head_dim=64, v_head_dim=value_width)
    # what make_chunked_train_step tells the feed of such a model
    feed.flash_pairs = _flash_pair_counter(SimpleNamespace(attention_implementation="pallas_flash", num_attention_heads=heads, **kind))
    chunks = feed.packed_plan_chunks(2, 2, seq_len=512, seed=3) if packed else feed.plan_chunks(2, 2, seed=3)
    made = list(chunks)
    spans = _since(t0, "plan")
    assert len(made) == len(spans) >= 3 and [s.id for s in spans] == list(range(len(made)))  # the exhausted pull left none
    off = np.asarray(dataset.data.subject_event_offsets)
    for (plans, n_events), span in zip(made, spans):
        if packed:
            seg = np.where(plans["event_mask"], plans["segment_ids"], -1)
            events, length = int(plans["event_mask"].sum()), 512
        else:
            kept = np.minimum(off[plans["subject_indices"] + 1] - off[plans["subject_indices"]], 256) * plans["valid_mask"]
            seg = np.where(np.arange(256) < kept[..., None], 0, -1)  # right-padded rows
            events, length = int(kept.sum()), 256
        visited, dense = _pairs_by_hand(seg, chunk)
        assert span.counts == {"events": events, "slots": seg[..., 0].size * length, "pairs_visited": visited, "pairs_dense": dense}
        assert n_events == events and 0 < visited <= dense  # the planner's own sum, and the recount from the plans
        # the chunk carries its own span's id and counts, however many chunks were planned after it
        assert type(n_events + 0) is int and (n_events.id, n_events.counts) == (span.id, span.counts)
    assert feed.plan_counts(made[0][0]) == spans[0].counts  # what train() recounts a cut chunk with
    feed.flash_pairs = None  # a step whose global layers run no flash op: events and slots alone
    assert set(feed.plan_counts(made[0][0])) == {"events", "slots"}
    feed.flash_pairs = _flash_pair_counter(SimpleNamespace(attention_implementation="einsum"))
    assert feed.flash_pairs is None


@pytest.mark.parametrize("name", ["ci_w1024.pretrain_packed", "ci_w1024.pretrain_padded"])
def test_the_chunked_steps_losses_stay_the_pinned_ones_under_the_spans(name, tmp_path, monkeypatch):
    """The spans are host code around the compiled step: one dispatch through
    the harness's `Program` gives the float32 losses `test_scopes.py` pins, its
    plans' span holds the chunk's counts, and the record holds the start-up
    phases the builders passed through with the step's compile group."""
    import jax

    from benchmark.harness import cohort as cohort_lib
    from benchmark.harness import loader

    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
    cell = tiny_cell(name)
    cohort = cohort_lib.make_cohort(cell["cohort"], SEED)
    t0 = time.perf_counter()
    prog = loader.load_job(cell).Program(cell, cohort, loader.load_reference(cell), SEED, tmp_path)
    plans, n_events = prog.next_plans()
    losses = jax.device_get(prog.dispatch(plans))
    assert [float(x).hex() for x in losses] == PARENT_LOSSES[name]
    spans = _since(t0)
    (plan,) = [s for s in spans if s.name == "plan"]
    feed = cell["feed"]
    row_len = feed["seq_len"] if feed["packed"] else feed["data_max_seq_len"]
    assert plan.counts == {"events": n_events, "slots": feed["steps_per_dispatch"] * feed["batch_size"] * row_len}  # rows of 32 or 16: no whole chunk
    phases = {s.name for s in spans if s.name.startswith("startup/")}
    assert phases >= {"startup/config", "startup/dataset_read", "startup/device_tables", "startup/build_model", "startup/build_step", "startup/state"}
    assert all(s.id == "startup" for s in spans if s.name.startswith("startup/"))
    step = scopes.summary(spans, small=0.0)["compile"]["chunk_step"]
    assert step["compiles"] == 1 and min(step["trace"], step["lower"], step["backend"]) > 0


# ------------------------------------------------------------------ train()
# `attn_blocks_visited_share` of the run below as the commit before the record
# wrote it (39b91fb: `visited_share` on the plans, in train()'s own loop).
PARENT_VISITED = {2: "0x1.0e38e38e38e39p-1", 4: "0x1.1c71c71c71c72p-1", 6: "0x1.0000000000000p-1"}


def test_train_writes_its_start_up_and_its_recompile_and_the_parents_visited_share(tmp_path, monkeypatch, capsys):
    from benchmark.harness import cohort as cohort_lib
    from eventstreamgpt_tpu.data import PytorchDatasetConfig
    from eventstreamgpt_tpu.models.config import MetricsConfig, OptimizationConfig
    from eventstreamgpt_tpu.training import PretrainConfig, train

    # an earlier run's loop ended here, whatever ran before this test: the line accounts for what the record holds after it
    with scopes.host_span("log_flush"):
        pass

    spec = tiny_cell("ci_w1024.pretrain_packed")["cohort"]
    spec.update(n_subjects=48, mean_seq_len=60, max_seq_len=128)
    data = cohort_lib.write_dl_cache(cohort_lib.make_cohort(spec, 7), spec, tmp_path / "ds")
    shutil.copy(data / "DL_reps" / "train_0.parquet", data / "DL_reps" / "tuning_0.parquet")
    cfg = PretrainConfig(
        seed=1,
        config=dict(
            hidden_size=32, head_dim=8, num_attention_heads=4, num_hidden_layers=2, intermediate_size=32,
            TTE_generation_layer_type="log_normal_mixture", TTE_lognormal_generation_num_components=2,
            attention_implementation="pallas_flash",  # off the chip the einsum runs; the plans' chunk pairs are the same
        ),
        optimization_config=OptimizationConfig(
            init_lr=1e-3, max_epochs=2, batch_size=2, validation_batch_size=2, lr_frac_warmup_steps=0.5,
            patience=None, max_training_steps=7,
        ),
        data_config=PytorchDatasetConfig(save_dir=data, max_seq_len=128, min_seq_len=2),
        pretraining_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        final_validation_metrics_config=MetricsConfig(do_skip_all_metrics=True),
        experiment_dir=str(tmp_path), save_dir=str(tmp_path / "pretrain"), do_final_validation_on_metrics=False,
        trainer_config={
            "log_every_n_steps": 2, "checkpoint_every_n_steps": 100, "device_resident_data": True,
            "steps_per_execution": 2, "use_packed_batches": True, "packed_seq_len": 384,
        },
    )
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "taking the einsum path"
        train(cfg)
    wall = time.perf_counter() - t0
    log = [json.loads(line) for line in (tmp_path / "pretrain" / "train_log.jsonl").read_text().splitlines()]
    visited = {rec["step"]: rec["attn_blocks_visited_share"].hex() for rec in log if "attn_blocks_visited_share" in rec}
    assert visited == PARENT_VISITED  # rows of three 128-event chunks; the same digits from the plan spans' counts

    (startup,) = [rec for rec in log if "startup" in rec]
    assert log[0] is startup and startup["step"] == 2  # written at the first flush, made when the first full dispatch returned
    phases, programs = startup["startup"]["phases"], startup["startup"]["compile"]
    assert set(phases) >= {"startup/dataset_read", "startup/device_tables", "startup/build_model", "startup/build_step", "startup/state"}
    assert all(seconds >= 0 for seconds in phases.values())
    spent = sum(phases.values())
    spent += sum(row[kind] for row in programs.values() for kind in ("trace", "lower", "backend", "cache_load"))
    assert 0 < spent <= startup["wall_s"] <= wall and "startup/import" not in phases
    assert programs["chunk_step"]["compiles"] == 1 and startup["backend"] >= programs["chunk_step"]["compiles"]
    assert {"hits", "misses", "since_process_start_s"} <= set(startup)
    # the seventh step is a chunk cut to one plan: its own shape, compiled in its dispatch, and the log says which step
    (recompile,) = [rec for rec in log if "compile" in rec]
    assert (recompile["compile"], recompile["step"], recompile["compiles"]) == ("chunk_step", 7, 1)
    assert min(recompile["trace"], recompile["lower"], recompile["backend"]) > 0

    # the operator's table of the same lines
    from scripts import startup_report

    capsys.readouterr()
    assert startup_report.main(["--log", str(tmp_path / "pretrain" / "train_log.jsonl")]) == 0
    printed = capsys.readouterr().out.splitlines()
    assert printed[0].split() == ["phase", "self_s"] and any(line.split()[:1] == ["startup/dataset_read"] and line.endswith(" us an event") for line in printed)
    counts = startup["startup"]["counts"]["startup/dataset_read"]
    assert counts["events"] > counts["subjects"] > 0 and counts["data"] >= counts["events"]
    assert any(line.split()[0] == "chunk_step" and line.split()[-2:] == ["1", "0"] for line in printed)  # compiled once, no cache
    assert json.loads(printed[-1]) == recompile

    # one id a dispatch: every dispatch span has exactly the plan span of its chunk, made before it
    spans = _since(t0)
    dispatches = [s for s in spans if s.name == "dispatch"]
    plans = {s.id: s for s in spans if s.name == "plan"}
    assert len(dispatches) == 4 and len(plans) == 4 and [d.id for d in dispatches] == sorted(plans)
    assert all(plans[d.id].end <= d.start for d in dispatches)
    assert [plans[d.id].counts["slots"] for d in dispatches] == [2 * 2 * 384] * 4  # the cut chunk was planned whole
