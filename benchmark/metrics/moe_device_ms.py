"""Device ms per optimizer step in the routed feed-forward layers
(``es.moe_router`` + ``es.moe_dispatch`` + ``es.moe_experts`` +
``es.moe_shared``), all phases. Nothing where the program has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder routed mlp"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("moe_router", "moe_dispatch", "moe_experts", "moe_shared")) or None
