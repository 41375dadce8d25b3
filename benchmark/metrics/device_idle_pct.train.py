"""Share of the traced training window in which no operation ran on the chip."""

LAYER = "device"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    if "train_events_per_s" not in record["end_to_end"]:
        return None
    t = record["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
