"""Device ms per optimizer step in the output layer: the three head groups
with their losses (``es.heads_tte``, ``es.heads_cls``, ``es.heads_reg``) and
the sum after them (``es.loss``), all phases."""

from benchmark.harness import scopes

LAYER = "head stack"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("heads_tte", "heads_cls", "heads_reg", "loss"))
