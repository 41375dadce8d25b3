"""The held ungated (relu^2) experts' grouped products' share of their
roofline.

Time: device time under ``es.moe_experts``, all phases, whatever implements
the products (the recomputed forward is in the time and not in the needs).
Needs (`harness/flops_hybrid.py::relu2_experts_needs`): the two products of
every token-expert pair the program counted, forward and backward, with the
held matrices read and their gradients written once a layer and step. Reads
the counters the job kind ``pretrain_hybrid`` records (``relu2_*``); a cell of
gated experts has `moe_experts_roofline` instead.
"""

from benchmark.harness import scopes
from benchmark.harness.device import peaks
from benchmark.harness.flops import roofline_share
from benchmark.harness.flops_hybrid import relu2_experts_needs

LAYER = "encoder routed mlp"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    counters = record["counters"]
    ms = scopes.device_ms(record, ("moe_experts",))
    if not ms or not counters.get("relu2_pairs"):
        return None
    need = relu2_experts_needs(
        counters["relu2_pairs"], counters["steps"] * counters["relu2_routed_layers"], record["model_sizes"], 2
    )
    share, _bound = roofline_share(
        need["fwd_flops"] + need["bwd_flops"], need["fwd_bytes"] + need["bwd_bytes"],
        ms / 1e3 * counters["steps"], peaks(record["device_kind"]),
    )
    return share
