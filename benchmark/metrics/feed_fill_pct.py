"""Real events over slots (rows x row length) of the plans made inside the
window, from the counts ``events`` and ``slots`` the program writes on its
``es.host/plan`` spans: what padding or an under-filled packed row leaves of a
step's slots."""

from benchmark.harness import host_record

LAYER = "feed"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "program_counter"


def read(record: dict):
    return host_record.count_ratio(record, "events", "slots")
