"""Host milliseconds to make one dispatch's batch plans (mean per dispatch)."""

LAYER = "feed"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "program_span"


def read(record: dict):
    seconds, n = record["spans"].total("feed_plan", *record["window"])
    return 1000.0 * seconds / n if n else None
