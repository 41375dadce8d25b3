"""The dependency-graph attention kernels' share of their roofline.

Time: ``dep_graph_attention_fwd`` / ``_bwd`` events in the device trace.
Needs: every event row (padding included: the kernel runs on all rows) has G
queries over the history and up to G levels; operations are 2 matmuls forward
and 4 backward, bytes q, k, v, o forward and q, k, v, o, do, dq, dk, dv
backward. Four keys a query: almost no arithmetic per byte, so the bound is
bytes.
"""

from benchmark.harness import trace
from benchmark.harness.device import peaks
from benchmark.harness.flops import roofline_share

LAYER = "encoder attention"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"
KERNELS = ("dep_graph_attention",)


def read(record: dict):
    ns, n = trace.kernel_ns(record["trace"]["ops"], KERNELS)
    if not n:
        return None
    m, c = record["model_sizes"], record["counters"]
    G = len(m["measurements_per_dep_graph_level"])
    rows = c["steps"] * c["rows_per_step"] * c["row_len"] * m["num_hidden_layers"]
    width = m["num_attention_heads"] * m["head_dim"]
    pairs = sum(range(2, G + 2))  # level g sees the history and g levels
    flops = 6 * 2 * width * pairs * rows
    plane = width * 2  # bf16
    nbytes = rows * plane * ((G + 2 * (G + 1) + G) + (2 * G + 2 * (G + 1)) + (G + 2 * (G + 1)))
    share, _bound = roofline_share(flops, nbytes, ns / 1e9, peaks(record["device_kind"]))
    return share
