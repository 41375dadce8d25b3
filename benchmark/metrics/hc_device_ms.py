"""Device ms per optimizer step under the hyper-connected residual streams'
two scopes, all phases (``es.hc_maps``: the streams' mean of squares,
``Phi``'s product, the sigmoids and the Sinkhorn loop; ``es.hc_mix``: the
pre-mix, the post/res mix and the sum before ``ln_f``). The two are read as
one: a fusion carries one name, and XLA writes the post/res mix and the
mixes' backward into fusions named for the maps (PERF.md section 6, PR 34), so
neither scope's own time is its work's. Not in this time: the pre-mix's
forward read, which XLA writes into the sublayer's norm (``es.norm``;
`metrics/hc_roofline.py` takes it into its time). Nothing where the program
has no such scope."""

from benchmark.harness import scopes

LAYER = "encoder residual streams"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    return scopes.device_ms(record, ("hc_maps", "hc_mix")) or None
