"""The reader of the program's scopes: paths written by hand, an event list
built by hand, the protobuf walk on bytes built by hand, the eight readers on
a record without scopes, and the reduction on a piece recorded on the chip."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmark.harness import loader, scopes

NEW_METRICS = (
    "scoped_pct.train", "recompute_pct.train", "collate_device_ms", "embed_device_ms",
    "attn_device_ms", "mlp_device_ms", "heads_device_ms", "optimizer_device_ms",
)
STEP = "jit(chunk_step)/while/body/closed_call/"
FWD = STEP + "jvp(CIPPTForGenerativeSequenceModeling)/encoder/"
BWD = STEP + "transpose(jvp(CIPPTForGenerativeSequenceModeling))/encoder/jvp(CIPPTForGenerativeSequenceModeling)/encoder/"


@pytest.mark.parametrize(
    "path, want",
    [
        (FWD + "h0/mlp/es.mlp/c_fc/dot_general", ("mlp", "forward")),
        (BWD + "checkpoint/h0/mlp/es.mlp/c_fc/dot_general", ("mlp", "backward")),
        (BWD + "checkpoint/rematted_computation/h0/mlp/es.mlp/c_fc/add", ("mlp", "recompute")),
        # nested scopes: the innermost stands
        (FWD + "h1/attn/es.attn_global/attention/es.attn_proj/q_proj/dot_general", ("attn_proj", "forward")),
        (BWD + "checkpoint/rematted_computation/h1/attn/es.attn_global/attention/swapaxes", ("attn_global", "recompute")),
        (STEP + "es.optimizer/mul", ("optimizer", "forward")),
        (STEP + "es.collate/gather", ("collate", "forward")),
        # no scope: the phase is still read from the path
        (BWD + "checkpoint/h0/add_any", (None, "backward")),
        (STEP + "dynamic_slice", (None, "forward")),
        ("", (None, "forward")),
        # a name that only looks like a scope is none
        (FWD + "h0/makes.mlp/add", (None, "forward")),
    ],
)
def test_scope_and_phase_of_a_path(path, want):
    assert scopes.scope_of(path) == want


def test_instruction_name_keeps_the_number():
    assert scopes.instruction_name("%fusion.4283 = bf16[16,1024]{1,0} fusion(...)") == "fusion.4283"
    assert scopes.instruction_name("_gather_2d.13") == "_gather_2d.13"


RAW = [
    ("%while.1 = (s32[]) while(...)", 0, 1000),  # a wrapper: left out
    ("%fusion.10 = bf16[8,128] fusion(...)", 0, 100),
    ("%fusion.11 = bf16[8,128] fusion(...)", 50, 100),  # overlaps the first by 50
    ("%flash_attention.3 = bf16[...] custom-call(...)", 200, 300),
    ("%copy.2 = f32[4] copy(...)", 900, 100),
    ("%multiply_add_fusion.7 = f32[4] fusion(...)", 600, 200),
]
BY_INSTRUCTION = {
    "multiply_add_fusion.7": BWD + "checkpoint/h0/mlp/es.mlp/c_fc/dot_general",
    "fusion.10": FWD + "h0/mlp/es.mlp/c_fc/dot_general",
    "fusion.11": BWD + "checkpoint/rematted_computation/h0/mlp/es.mlp/mul",
    "flash_attention.3": BWD + "checkpoint/h1/attn/es.attn_global/attention/pallas_call",
    "copy.2": STEP + "dynamic_update_slice",
}
# the weight-gradient fusion also holds AdamW's update; so does a copy nobody named
INSIDE = {"multiply_add_fusion.7": {"mlp", "optimizer"}, "fusion.10": {"mlp", "norm"}, "copy.2": {"optimizer"}}
SOURCES = (BY_INSTRUCTION, INSIDE)


def test_wrappers_are_left_out_and_each_operation_gets_scope_phase_and_what_it_hides():
    ops = scopes.scoped(RAW, *SOURCES)
    assert [(o[0], o[1], o[2], o[5]) for o in ops] == [
        ("fusion.10", "mlp", "forward", ("norm",)),
        ("fusion.11", "mlp", "recompute", ()),
        ("flash_attention.3", "attn_global", "backward", ()),
        ("copy.2", None, "forward", ("optimizer",)),
        ("multiply_add_fusion.7", "mlp", "backward", ("optimizer",)),
    ]


def test_the_table_sums_by_scope_and_phase_and_busy_is_a_union():
    result = scopes.table(scopes.scoped(RAW, *SOURCES))
    assert result["table"] == {
        ("mlp", "forward"): 100, ("mlp", "recompute"): 100, ("mlp", "backward"): 200,
        ("attn_global", "backward"): 300,
    }
    assert result["scoped_ns"] == 700
    # [0,150) + [200,500) + [600,800) + [900,1000): the overlap counts once
    assert result["busy_ns"] == 750
    assert result["phase_ns"] == {"forward": 200, "backward": 500, "recompute": 100}
    assert result["rides_ns"] == {"norm": 100, "optimizer": 300}
    assert result["unscoped_top"] == [["copy", 100]]
    text = scopes.render(result, steps=1)
    assert "es.attn_global" in text and "copy" in text and "es.optimizer" in text


# ------------------------------------------------------------ bytes by hand
def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | 0x80]) if n else bytes([b])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(name: str, ident: int, op_name: str = "", calls: int | None = None) -> bytes:
    body = _field(1, name) + _field(35, ident)
    if op_name:
        body += _field(7, _field(1, "op_type") + _field(2, op_name))
    if calls is not None:
        body += _field(38, _varint(calls))  # packed, as proto3 writes it
    return _field(2, body)


def _hlo_proto() -> bytes:
    fused = _field(1, "fused_computation") + _instruction("p0", 1) + _instruction(
        "root.1", 2, FWD + "h0/mlp/es.mlp/c_proj/dot_general"
    ) + _field(5, 7) + _field(6, 2)
    entry = _field(1, "main") + _instruction("fusion.5", 3, "", calls=7) + _instruction(
        "fusion.6", 4, STEP + "es.optimizer/mul", calls=7
    ) + _instruction("copy.1", 5) + _field(5, 8) + _field(6, 3)
    module = _field(1, "jit_chunk_step") + _field(3, fused) + _field(3, entry)
    return _field(1, module)


def test_op_names_of_a_compiled_module_and_a_fusion_without_one_takes_its_roots():
    names, inside = scopes.hlo_op_names(memoryview(_hlo_proto()))
    assert names["fusion.5"] == FWD + "h0/mlp/es.mlp/c_proj/dot_general"
    assert names["fusion.6"] == STEP + "es.optimizer/mul"
    assert names["copy.1"] == "" and names["p0"] == ""
    # what the fused computation holds, whatever name the fusion carries
    assert inside == {"fusion.5": {"mlp"}, "fusion.6": {"mlp"}}


def _xspace(tmp_path: Path) -> Path:
    stat_names = _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "Hlo Proto")))
    meta_plane = _field(2, "/host:metadata") + stat_names + _field(
        4, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "jit_chunk_step(5)") + _field(
            5, _field(1, 1) + _field(6, _hlo_proto())))
    )
    device_plane = _field(2, "/device:TPU:0") + _field(3, b"lines are skipped, whatever they hold")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, meta_plane) + _field(1, device_plane))
    return path


def test_the_compiled_module_is_read_from_an_xplane_files_metadata_plane(tmp_path):
    path = _xspace(tmp_path)
    assert [name for name, _ in scopes.xspace_planes(path)] == ["/host:metadata", "/device:TPU:0"]
    by_instruction, inside = scopes.op_name_sources(path)
    assert by_instruction["fusion.5"].endswith("es.mlp/c_proj/dot_general")
    assert "copy.1" not in by_instruction  # an empty name is no entry
    assert inside["fusion.6"] == {"mlp"}


# ---------------------------------------------------------------- the readers
def _record() -> dict:
    return {"counters": {"steps": 4}, "end_to_end": {"train_events_per_s": 1.0}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_on_a_record_without_scopes(name):
    """No trace directory (the parent, a CPU rehearsal): nothing, never 0."""
    assert loader.metric_readers()[name].read(_record()) is None


def test_a_trace_without_scopes_reads_as_nothing_and_says_so(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", lambda d: scopes.scoped(RAW, {}, {}))
    record = _record()
    assert scopes.by_scope(record) is None
    assert "no es. scope" in capsys.readouterr().err
    for name in NEW_METRICS:
        assert loader.metric_readers()[name].read(record) is None


def test_the_readers_share_one_reduction_printed_once(monkeypatch, capsys):
    calls = []

    def read(trace_dir):
        calls.append(trace_dir)
        return scopes.scoped(RAW, *SOURCES)

    monkeypatch.setattr(scopes, "find_trace_dir", lambda: Path("somewhere"))
    monkeypatch.setattr(scopes, "read_scoped_ops", read)
    record = _record()
    readers = loader.metric_readers()
    got = {name: readers[name].read(record) for name in NEW_METRICS}
    assert len(calls) == 1 and capsys.readouterr().err.count("scoped ") == 1
    per_step = 1e6 * 4
    assert got["mlp_device_ms"] == 400 / per_step
    assert got["attn_device_ms"] == 300 / per_step
    # the update rides in a fusion of es.mlp: the trace has no time of its own for it
    assert got["optimizer_device_ms"] == 0.0 and got["collate_device_ms"] == 0.0
    assert got["scoped_pct.train"] == pytest.approx(100 * 700 / 750)
    assert got["recompute_pct.train"] == pytest.approx(100 * 100 / 750)
    # a cell that does not report the rate these metrics move reads nothing
    serve = {"counters": {"steps": 4}, "end_to_end": {}}
    assert readers["mlp_device_ms"].read(serve) is None


def test_the_trace_directory_is_found_as_run_py_lays_it_out(tmp_path, monkeypatch):
    import os

    monkeypatch.setattr(scopes, "__file__", str(tmp_path / "benchmark" / "harness" / "scopes.py"))
    assert scopes.find_trace_dir() is None
    made = tmp_path / ".bench_work" / f"ci_w1024.pretrain_packed.{os.getpid()}" / "trace"
    made.mkdir(parents=True)
    (tmp_path / ".bench_work" / "ci_w1024.pretrain_packed.1" / "trace").mkdir(parents=True)  # another process's
    assert scopes.find_trace_dir() == made
    assert scopes.read_scoped_ops(made) == []  # no file in it yet


# ------------------------------------------------- a piece recorded on the chip
def test_reduction_of_a_piece_recorded_on_the_chip():
    """The operations that start within a millisecond before and three after
    the start of the second dispatch of ``ci_w1024.pretrain_packed`` on one
    TPU v5e (my chip run 1, PR 26), names cut to 120 characters, with the
    ``op_name`` of each instruction as the trace's metadata plane holds it."""
    piece = json.loads((Path(__file__).parent / "data" / "recorded_scoped_trace.json").read_text())
    raw = [tuple(event) for event in piece["events"]]
    ops = scopes.scoped(raw, piece["op_names"], {})
    result = scopes.table(ops)
    seen = {scope for scope, _phase in result["table"]}
    # the seam: one dispatch ends in the optimizer, the next begins with the feed
    assert {"optimizer", "collate"} <= seen
    assert seen <= set(_program_scopes())
    assert 0 < result["scoped_ns"] <= sum(o[4] for o in ops)
    assert result["busy_ns"] <= max(o[3] + o[4] for o in ops) - min(o[3] for o in ops)
    assert all(scopes.trace.short_name(o[0]) not in scopes.trace.WRAPPERS for o in ops)


def _program_scopes():
    """The program's own list (a parent commit laid under these files has none)."""
    return pytest.importorskip("eventstreamgpt_tpu.utils.scopes").SCOPES
