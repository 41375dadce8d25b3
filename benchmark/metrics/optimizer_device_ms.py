"""Device ms per optimizer step under ``es.optimizer`` (AdamW's update and
its application) and ``es.health`` (the sentinel's gradient norm)."""

from benchmark.harness import scopes

LAYER = "optimizer"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("optimizer", "health"))
