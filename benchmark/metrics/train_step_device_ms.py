"""Device-busy milliseconds per optimizer step in the traced window."""

LAYER = "train step"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    steps = record["counters"].get("steps")
    if not steps or "train_events_per_s" not in record["end_to_end"]:
        return None
    return 1000.0 * record["trace"]["busy_s"] / steps
