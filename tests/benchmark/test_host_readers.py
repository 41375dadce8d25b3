"""The three readers of the program's own host record
(`benchmark/metrics/plan_host_ms.py`, `feed_fill_pct.py`,
`attn_blocks_visited_pct.py` over `benchmark/harness/host_record.py`) on a
record written by hand, on runs they have nothing to read from, and against
their entries in `BENCHMARK.json`."""

from __future__ import annotations

import json
import time

import pytest

from benchmark.harness import loader
from eventstreamgpt_tpu.utils import scopes

NAMES = ("plan_host_ms", "feed_fill_pct", "attn_blocks_visited_pct")


def _read(record: dict) -> dict:
    readers = loader.metric_readers()
    return {name: readers[name].read(record) for name in NAMES}


def _plans(t: float, pairs: bool = True) -> None:
    """Three plan spans written by hand from ``t`` on: 2 ms with a 0.5 ms
    compile inside it, 3 ms, and 1 ms."""
    flash = lambda visited: {"pairs_visited": visited, "pairs_dense": 64} if pairs else {}  # noqa: E731
    scopes.record("compile/trace", t + 0.0005, t + 0.0010, id="a_plan_helper")
    scopes.record("plan", t, t + 0.002, id=0, events=900, slots=1024, **flash(29))
    scopes.record("plan", t + 0.010, t + 0.013, id=1, events=1000, slots=1024, **flash(35))
    scopes.record("plan", t + 0.020, t + 0.021, id=2, events=500, slots=512, **flash(64))


def test_the_readers_on_a_record_written_by_hand():
    t = time.perf_counter()
    _plans(t)
    whole = {"window": (t - 0.001, t + 0.03), "counters": {"dispatches": 3}}
    got = _read(whole)
    assert got["plan_host_ms"] == pytest.approx((1.5 + 3.0 + 1.0) / 3)  # self time: the compile inside the first is not the plan's
    assert got["feed_fill_pct"] == pytest.approx(100 * 2400 / 2560)
    assert got["attn_blocks_visited_pct"] == pytest.approx(100 * 128 / 192)
    # a span that does not lie inside the window is not the window's
    first_two = {"window": (t - 0.001, t + 0.0205), "counters": {"dispatches": 2}}
    got = _read(first_two)
    assert got["plan_host_ms"] == pytest.approx((1.5 + 3.0) / 2)
    assert got["feed_fill_pct"] == pytest.approx(100 * 1900 / 2048)
    assert got["attn_blocks_visited_pct"] == pytest.approx(100 * 64 / 128)
    # the job dispatched one chunk less than the program planned (an epoch's short last chunk): its time is in
    assert _read({**whole, "counters": {"dispatches": 2}})["plan_host_ms"] == pytest.approx(5.5 / 2)
    assert _read({"window": whole["window"]})["plan_host_ms"] == pytest.approx(5.5 / 3)  # no counters: by span


def test_nothing_and_never_zero_where_there_is_nothing_to_read(monkeypatch):
    t = time.perf_counter()
    _plans(t, pairs=False)
    assert _read({"counters": {"dispatches": 3}}) == dict.fromkeys(NAMES)  # no window
    assert _read({"window": None}) == dict.fromkeys(NAMES)
    assert _read({"window": (t + 0.05, t + 0.06), "counters": {"dispatches": 3}}) == dict.fromkeys(NAMES)  # no span in it
    got = _read({"window": (t - 0.001, t + 0.03), "counters": {"dispatches": 3}})
    assert got["attn_blocks_visited_pct"] is None  # a step whose global layers run no flash op
    assert got["feed_fill_pct"] == pytest.approx(100 * 2400 / 2560) and got["plan_host_ms"] > 0
    # a program that keeps no record (the parent commit's, under this benchmark): nothing, and no raise
    monkeypatch.delattr(scopes, "recorded")
    assert _read({"window": (t - 0.001, t + 0.03), "counters": {"dispatches": 3}}) == dict.fromkeys(NAMES)


def test_the_entries_of_the_manifest_say_what_the_readers_say():
    manifest = json.loads((loader.ROOT.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in manifest["per_layer"]}
    readers = loader.metric_readers()
    want = {
        "plan_host_ms": ("ms", "lower", "program_span", "feed"),
        "feed_fill_pct": ("%", "higher", "program_counter", "feed"),
        "attn_blocks_visited_pct": ("%", "lower", "program_counter", "encoder attention"),
    }
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(want)  # appended, in this order
    for name, (unit, better, source, layer) in want.items():
        entry, module = entries[name], readers[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}  # no list of cells: every cell reports it
        assert (entry["unit"], entry["better"], entry["source"], entry["layer"]) == (unit, better, source, layer)
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (unit, source, layer, "train_events_per_s")
        assert entry["moves"] == "train_events_per_s"
    assert entries["feed_plan_ms"]["source"] == "program_span"  # the harness's timing of the same layer stays
