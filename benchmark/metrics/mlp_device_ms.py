"""Device ms per optimizer step in the feed-forward blocks and the layer
norms (``es.mlp`` + ``es.norm``), all phases."""

from benchmark.harness import scopes

LAYER = "encoder mlp"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("mlp", "norm"))
