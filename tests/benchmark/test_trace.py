"""The trace reduction on an event list written by hand."""

from __future__ import annotations

from benchmark.harness import trace

DEV = "/device:TPU:0"


def ev(name, start, dur, line=trace.OPS_LINE, plane=DEV):
    return (plane, line, name, start, dur)


EVENTS = [
    ev("%while.1 = (s32[]) while(...)", 0, 1000),  # a wrapper: spans its body
    ev("%fusion.10 = bf16[8,128] fusion(...)", 0, 100),
    ev("%fusion.11 = bf16[8,128] fusion(...)", 50, 100),  # overlaps the first by 50
    ev("%flash_attention.3 = bf16[...] custom-call(...)", 200, 300),
    ev("%copy.2 = f32[4] copy(...)", 900, 100),
    ev("jit_step(123)", 0, 1000, line="XLA Modules"),  # not an operation
    ev("bench/feed_plan", 500, 150, line="python3", plane="/host:CPU"),
    ev("bench/wait_result", 650, 250, line="python3", plane="/host:CPU"),
    ev("some_python_function", 0, 2000, line="python3", plane="/host:CPU"),
]


def test_short_name_drops_the_instruction_number():
    assert trace.short_name("%fusion.4283 = bf16[16384,4057]{1,0} fusion(...)") == "fusion"
    assert trace.short_name("%_gather_2d.13 = f32[16384,128] custom-call(...)") == "_gather_2d"
    assert trace.short_name("%convolution_reduce-precision_fusion.7 = ...") == "convolution_reduce-precision_fusion"
    assert trace.short_name("plain_name") == "plain_name"


def test_operations_leave_out_wrappers_and_other_lines():
    ops = trace.op_events(EVENTS, DEV)
    assert [o[2] for o in ops] == ["fusion", "fusion", "flash_attention", "copy"]


def test_busy_is_the_union_of_the_operations():
    ops = trace.op_events(EVENTS, DEV)
    # [0,150) + [200,500) + [900,1000) = 150 + 300 + 100
    assert trace.busy_ns(ops) == 550
    assert trace.merged([(0, 100), (50, 150), (200, 500)]) == [(0, 150), (200, 500)]


def test_one_kernels_time():
    ops = trace.op_events(EVENTS, DEV)
    assert trace.kernel_ns(ops, ("flash_attention",)) == (300, 1)
    assert trace.kernel_ns(ops, ("no_such_kernel",)) == (0, 0)


def test_top_operations_are_summed_by_name():
    ops = trace.op_events(EVENTS, DEV)
    assert trace.top_ops(ops, 2) == [["flash_attention", 300 / 1e9], ["fusion", 200 / 1e9]]


def test_a_gap_goes_to_the_span_that_covers_most_of_it():
    ops = trace.op_events(EVENTS, DEV)
    spans = trace.host_spans(EVENTS)
    assert [s[0] for s in spans] == ["feed_plan", "wait_result"]
    gaps = dict((name, s) for name, s in trace.idle_gaps(ops, spans))
    # gap [150,200): no harness span; gap [500,900): feed_plan covers 150, wait_result 250
    assert gaps == {"wait_result": 400 / 1e9, "(no harness span)": 50 / 1e9}


def test_summary_of_one_chip():
    out = trace.summarize(EVENTS, chips=1)
    assert out["busy_s"] == 550 / 1e9
    assert out["breakdown"]["device_ops"][0][0] == "flash_attention"
    assert len(out["breakdown"]["idle_gaps"]) == 2


# ------------------------------------------------------- a trace recorded on the chip
def _recorded():
    """230 events around the seam between two dispatches of
    ``ci_w1024.pretrain_packed`` on one TPU v5e (my chip run A, PR 25;
    ``--trace-sample``), names cut to 120 characters."""
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "recorded_trace.json"
    return [tuple(e) for e in json.loads(path.read_text())]


def test_recorded_trace_planes_and_wrappers():
    events = _recorded()
    assert trace.device_planes(events) == [DEV]
    raw = [e for e in events if e[1] == trace.OPS_LINE]
    ops = trace.op_events(events, DEV)
    # the one wrapper in the piece is the scanned step's `while`
    assert len(raw) == 190 and len(ops) == 189
    assert {trace.short_name(e[2]) for e in raw} - {o[2] for o in ops} == {"while"}


def test_recorded_trace_busy_union_matches_a_timeline_by_brute_force():
    import numpy as np

    ops = trace.op_events(_recorded(), DEV)
    lo = min(o[3] for o in ops)
    hi = max(o[3] + o[4] for o in ops)
    timeline = np.zeros(hi - lo, bool)
    for o in ops:
        timeline[o[3] - lo : o[3] + o[4] - lo] = True
    assert trace.busy_ns(ops) == int(timeline.sum()) == 3408964


def test_recorded_trace_one_kernels_time_and_the_seams_gap():
    events = _recorded()
    ops = trace.op_events(events, DEV)
    assert trace.kernel_ns(ops, ("multiply_add_fusion",)) == (
        sum(o[4] for o in ops if o[2] == "multiply_add_fusion"),
        sum(1 for o in ops if o[2] == "multiply_add_fusion"),
    )
    assert trace.kernel_ns(ops, ("multiply_add_fusion",))[0] == 796541
    # between the two dispatches the device waits 16 us while the host sits in wait_result
    module_start = next(e[3] for e in events if e[1] == "XLA Modules")
    before = max(o[3] + o[4] for o in ops if o[3] < module_start)
    after = min(o[3] for o in ops if o[3] >= module_start)
    assert after - before == 15954
    spans = trace.host_spans(events)
    assert [s[0] for s in spans] == ["wait_result"]
    assert trace.idle_gaps(ops, spans) == [["wait_result", 89989 / 1e9]]


def test_the_flash_reader_counts_the_backward_kernels_as_the_chip_names_them():
    """Names from the device trace of the CI cell (my chip run 2, PR 25)."""
    from benchmark.harness import loader

    kernels = loader.metric_readers()["flash_attn_roofline"].KERNELS
    names = [
        "%flash_attention.3 = bf16[16,8,1024,128] custom-call(...)",
        "%flash_mha_bwd_dkv_block_q_major_1024_block_q_1024_block_k_major_1024_block_k_1024.2 = (...) custom-call(...)",
        "%flash_mha_bwd_dq_block_q_major_1024_block_k_major_1024_block_k_1024.5 = (...) custom-call(...)",
        "%fusion.7 = bf16[8,128] fusion(...)",
    ]
    ops = trace.op_events([ev(name, 100 * i, 10) for i, name in enumerate(names)], DEV)
    assert trace.kernel_ns(ops, kernels) == (30, 3)
