"""The held experts' grouped products' share of their roofline.

Time: device time under ``es.moe_experts``, all phases, whatever implements
the products (the recomputed forward is in the time and not in the needs).
Needs (`harness/flops_routed.py::routed_experts_needs`): the three products
of every token-expert pair the program counted, forward and backward, with
the held matrices read and their gradients written once a layer and step.
"""

from benchmark.harness import scopes
from benchmark.harness.device import peaks
from benchmark.harness.flops import roofline_share
from benchmark.harness.flops_routed import routed_experts_needs

LAYER = "encoder routed mlp"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    counters = record["counters"]
    ms = scopes.device_ms(record, ("moe_experts",))
    if not ms or not counters.get("moe_pairs"):
        return None
    need = routed_experts_needs(
        counters["moe_pairs"], counters["steps"] * counters["moe_routed_layers"], record["model_sizes"], 2
    )
    share, _bound = roofline_share(
        need["fwd_flops"] + need["bwd_flops"], need["fwd_bytes"] + need["bwd_bytes"],
        ms / 1e3 * counters["steps"], peaks(record["device_kind"]),
    )
    return share
