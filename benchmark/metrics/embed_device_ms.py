"""Device ms per optimizer step under ``es.embed``: data embedding, time
encoding and static codes, forward and backward."""

from benchmark.harness import scopes

LAYER = "encoder input"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("embed",))
