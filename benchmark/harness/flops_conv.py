"""Operations and bytes of a backbone of gated short convolutions,
grouped-query attention and gated feed-forwards, dense and routed (the job
kind ``pretrain_conv``), from its shapes.

The same convention as `harness/flops.py`: matrix products and attention only,
2 operations a multiply-add, forward; a training step needs three times the
forward; recomputation is not counted. A routed layer is counted by the
token-expert pairs really computed here (the program's counter), never by the
buffer they are ordered into. The convolution's taps and gates are elementwise
and are not in the count.
"""

from __future__ import annotations

from benchmark.harness.flops_hybrid import attention_layer_flops
from benchmark.harness.flops_routed import swiglu_flops


def conv_mixer_flops(model: dict) -> float:
    """One gated short convolution's two products on one event: ``W_in``
    (hidden to three hidden) and ``W_out`` (hidden to hidden)."""
    h = model["hidden_size"]
    return 2 * h * 3 * h + 2 * h * h


def forward_flops_per_event(model: dict, vocab: dict, global_keys: float, pairs_per_event: float) -> float:
    """Forward operations for one real event. ``pairs_per_event`` is the mean
    number of token-expert pairs computed here per real event and routed
    layer."""
    h = model["hidden_size"]
    mixers = {"conv": conv_mixer_flops(model), "mha": attention_layer_flops(model, global_keys)}
    ffns = {
        "swiglu": swiglu_flops(h, model["intermediate_size"]),
        "routed": 2 * h * model["moe_router_width"] + pairs_per_event * swiglu_flops(h, model["moe_intermediate_size"]),
    }
    total = sum(mixers[m] + ffns[f] for m, f in zip(model["mixer_layers"], model["ffn_layers"]))
    n_reg = sum(2 * vocab["vocab_sizes"][m] for m in vocab["multivariate_regression"])
    heads = 2 * h * (vocab["vocab_size"] + n_reg + 3 * model["tte_components"] + len(vocab["measurements_idxmap"]))
    return total + heads



def conv_gate_needs(events: float, model: dict, itemsize: int) -> dict:
    """Operations and the least HBM bytes the gated taps of all the conv
    mixers need for ``events`` real events, forward and backward, without
    recomputation. An event and layer: forward reads the projection ``[B | C |
    x~]`` (three hidden widths) once and writes ``(C * c)`` (one); backward
    reads the projection and the output's gradient and writes the
    projection's gradient. The operations are elementwise (two a multiply-add
    of a tap, one a gate) and bound nothing."""
    h = model["hidden_size"]
    layers = model["mixer_layers"].count("conv")
    flops = events * layers * (2 * model["conv_L_cache"] + 2) * h
    return {
        "fwd_flops": flops, "bwd_flops": 2 * flops,
        "fwd_bytes": events * layers * (3 * h + h) * itemsize,
        "bwd_bytes": events * layers * (3 * h + h + 3 * h) * itemsize,
    }
