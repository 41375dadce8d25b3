"""Device ms per optimizer step under ``es.collate``: the device side of the
feed (gathers from the resident tables into one batch), beside `feed_plan_ms`
(the host side)."""

from benchmark.harness import scopes

LAYER = "feed"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("collate",))
