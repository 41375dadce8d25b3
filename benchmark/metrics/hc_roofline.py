"""The hyper-connected residual streams' share of their roofline: the maps and
the two mixes of every sublayer as one quantity, because a trace cannot tell
them apart while XLA writes them (PERF.md section 6, PR 34).

A fusion carries one name, its hero's. XLA writes the post/res mix into the
next part's mean of squares and the mixes' backward (the sixteen
``sum(dX'[i] X[j])`` and the streams' cotangent) into the fusions of ``Phi``'s
products, all named for ``es.hc_maps``; and the pre-mix's read of the four
streams into the reduction of the sublayer's own norm, named for ``es.norm``.
So the time is the device time under ``es.hc_maps``, ``es.hc_mix`` and
``es.norm``, all phases, whatever implements them, and the needs are the
whole of steps 1-5 (`harness/flops_hc.py::hc_needs`): the maps' read of the
streams, ``Phi``'s product and both mixes on every real event, each operand
read or written once a pass, the backward twice the forward. The bound is
bytes: 18 planes of ``C`` values an event and sublayer.

An estimate, and no bound on either side. In the time and not in the needs:
the recomputed forward, the Sinkhorn loop, and the norm's own pass where it is
one. In the needs and not in the time: what XLA writes into another layer's
fusions. On the chip the post mix rides as the epilogue of the products that
make ``y`` (``W_o``, the down projections, the shared expert): in
`xing40_a4b_ep8.pretrain_packed` 44 ms a step of fusions named for
``es.attn_proj``, ``es.mlp``, ``es.moe_shared`` and ``es.moe_dispatch`` hold
the streams' instructions beside 45.4 under the two scopes and 4.9 of
``es.norm``'s 5.6; with all of them in the time the share reads 40 where this
reads 74.5 (PERF.md section 6, PR 34, run 5). It becomes exact when the mixes
are a kernel with a name.
"""

from benchmark.harness import scopes
from benchmark.harness.device import peaks
from benchmark.harness.flops import roofline_share
from benchmark.harness.flops_hc import hc_needs

LAYER = "encoder residual streams"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"


def read(record: dict):
    counters, model = record["counters"], record["model_sizes"]
    if model.get("hc_mult", 1) < 2 or not scopes.device_ms(record, ("hc_maps", "hc_mix")):
        return None
    ms = scopes.device_ms(record, ("hc_maps", "hc_mix", "norm"))
    need = hc_needs(counters["events"], model, 2)
    share, _bound = roofline_share(
        need["fwd_flops"] + need["bwd_flops"], need["fwd_bytes"] + need["bwd_bytes"],
        ms / 1e3 * counters["steps"], peaks(record["device_kind"]),
    )
    return share
