"""Device ms per optimizer step in the state-space (Mamba-2) layers
(``es.ssm_proj`` + ``es.ssm_conv`` + ``es.ssm_scan`` + ``es.ssm_gate``), all
phases. Nothing where the program has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder state-space mixer"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")) or None
