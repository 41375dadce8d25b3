"""Device ms per optimizer step in what latent attention puts between the
block's normed input and q, k, v (``es.attn_latent``: the four down/up
projections, the two latent norms, RoPE, the concatenations), all phases.
Nothing where the program has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder attention"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("attn_latent",)) or None
