"""Device ms per optimizer step in attention: the four projections
(``es.attn_proj``), what lies between them in global and local layers
(``es.attn_global``, ``es.attn_local``) and NA's dependency-graph attention
(``es.dep_graph``), all phases. The table on standard error keeps them apart."""

from benchmark.harness import scopes

LAYER = "encoder attention"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("attn_proj", "attn_global", "attn_local", "dep_graph"))
