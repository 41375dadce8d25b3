"""Device ms per optimizer step in what a state-space layer puts between its
two projections (``es.ssm_conv``: the causal convolution inside the segment,
silu, the splits; ``es.ssm_scan``: softplus, decays, the chunked scan, the D
skip; ``es.ssm_gate``: the gate and the grouped norm), all phases. Nothing
where the program has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder state-space mixer"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("ssm_conv", "ssm_scan", "ssm_gate")) or None
