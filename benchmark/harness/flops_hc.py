"""Operations and bytes of a backbone of latent attention and routed
feed-forwards on hyper-connected residual streams (the job kind
``pretrain_routed_hc``), from its shapes.

`harness/flops_routed.py`'s count and convention (matrix products and
attention, 2 operations a multiply-add, forward; a training step three times
the forward; recomputation not counted) plus what the streams add to every
sublayer, two a layer: ``Phi``'s product over the ``n C`` values of an event's
streams and the two mixes.
"""

from __future__ import annotations

from benchmark.harness import flops_routed


def hc_maps_flops(model: dict) -> float:
    """``r Phi`` of one sublayer on one event: ``n C x (n + n + n^2)``."""
    n = model["hc_mult"]
    return 2 * n * model["hidden_size"] * (2 * n + n * n)


def hc_mix_flops(model: dict) -> float:
    """One sublayer's mixes on one event: the pre-mix ``sum_i H_pre[i] X[i]``
    (``n C`` multiply-adds) and ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``
    (``n^2 C + n C``)."""
    n = model["hc_mult"]
    return 2 * model["hidden_size"] * (n * n + 2 * n)


def forward_flops_per_event(model: dict, vocab: dict, global_keys: float, pairs_per_event: float) -> float:
    """`flops_routed.forward_flops_per_event` plus the maps and the mixes of two sublayers a layer."""
    sublayers = 2 * len(model["ffn_layers"])
    return flops_routed.forward_flops_per_event(model, vocab, global_keys, pairs_per_event) + sublayers * (
        hc_maps_flops(model) + hc_mix_flops(model)
    )


def hc_needs(events: float, model: dict, itemsize: int) -> dict:
    """Operations and bytes the residual streams of every sublayer need for
    ``events`` events, forward and backward, without recomputation: the maps
    (``Phi``'s product and the norm's mean of squares on one read of the ``n``
    streams) and the two mixes. Bytes, with every operand read or written once
    a pass: the maps read the ``n`` streams; the pre-mix reads them and writes
    ``u``; the post/res mix reads them and ``y`` and writes them:
    ``(4 n + 2) C`` values an event. The backward moves twice the forward
    (each pass's cotangents in and out, and the streams read again for the
    maps' and ``Phi``'s gradients) and computes twice its operations. The
    maps' planes (``n + n + n^2`` float32 an event), ``Phi`` itself and the
    Sinkhorn loop over them are left out: under a hundredth."""
    n, width = model["hc_mult"], model["hidden_size"]
    sublayers = 2 * len(model["ffn_layers"])
    flops = events * sublayers * (hc_maps_flops(model) + hc_mix_flops(model))
    nbytes = events * sublayers * (4 * n + 2) * width * itemsize
    return {"fwd_flops": flops, "bwd_flops": 2 * flops, "fwd_bytes": nbytes, "bwd_bytes": 2 * nbytes}
