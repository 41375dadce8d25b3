"""Share of the train step's device-busy time spent computing again what the
remat policy did not keep (phase ``recompute``: ``rematted_computation`` in
the operation's ``op_name`` path, scoped or not)."""

from benchmark.harness import scopes

LAYER = "train step"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.share_pct(record, "recompute")
