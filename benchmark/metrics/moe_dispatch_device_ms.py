"""Device ms per optimizer step in routing and dispatch (``es.moe_router`` +
``es.moe_dispatch``: logits, top-k, ordering the pairs by expert, gathering
rows and combining back), all phases: what of a routed layer is not
arithmetic. Nothing where the program has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder routed mlp"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("moe_router", "moe_dispatch")) or None
