"""Share of the train step's device-busy time whose operation carries one of
the program's ``es.`` scopes: the instrumentation's own health. A refactor
that loses a scope shows here first."""

from benchmark.harness import scopes

LAYER = "train step"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.share_pct(record)
