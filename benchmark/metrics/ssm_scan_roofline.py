"""The state-space scan's share of its roofline.

Time: device time under ``es.ssm_scan``, all phases, whatever implements the
scan (the recomputed forward is in the time and not in the needs). Needs
(`harness/flops_hybrid.py::ssm_scan_needs`): the chunked form's four products
an event of every Mamba-2 layer, forward and backward, with the heads' inputs,
``B``, ``C`` and the step sizes read and the outputs written once.
"""

from benchmark.harness import scopes
from benchmark.harness.device import peaks
from benchmark.harness.flops import roofline_share
from benchmark.harness.flops_hybrid import ssm_scan_needs

LAYER = "encoder state-space mixer"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    counters, model = record["counters"], record["model_sizes"]
    ms = scopes.device_ms(record, ("ssm_scan",))
    if not ms or "M" not in model.get("pattern", ""):
        return None
    need = ssm_scan_needs(counters["events"], model, 2)
    share, _bound = roofline_share(
        need["fwd_flops"] + need["bwd_flops"], need["fwd_bytes"] + need["bwd_bytes"],
        ms / 1e3 * counters["steps"], peaks(record["device_kind"]),
    )
    return share
