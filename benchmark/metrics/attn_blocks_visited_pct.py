"""The share of dense chunk pairs the global layers' flash kernels visit on
the plans made inside the window, at the chunk widths the op takes for this
model: the counts ``pairs_visited`` and ``pairs_dense`` the program writes on
its ``es.host/plan`` spans."""

from benchmark.harness import host_record

LAYER = "encoder attention"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "program_counter"


def read(record: dict):
    return host_record.count_ratio(record, "pairs_visited", "pairs_dense")
