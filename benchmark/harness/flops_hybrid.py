"""Operations and bytes of a ``nemotron_h`` backbone (the job kind
``pretrain_hybrid``): Mamba-2 layers, routed layers of ungated relu^2 experts
and grouped-query attention layers, one part a layer, from its shapes.

The same convention as `harness/flops.py`: matrix products, attention and the
state-space scan only, 2 operations a multiply-add, forward; a training step
needs three times the forward; recomputation is not counted. The needs are of
the mathematics, whatever implements it. A routed layer is counted by the
token-expert pairs really computed here (the program's counter) at TWO products
an expert, a state-space layer by the four products an event of the chunked
form at the published chunk: ``C B^T`` and ``(L o C B^T) X`` over the keys an
event sees inside its chunk (causal: ``(Q + 1) / 2`` of them), ``B^T X`` into
the chunk's state and ``C S`` out of the carried one.
"""

from __future__ import annotations


def ssm_scan_flops(model: dict) -> float:
    """The scan's four products on one event of one layer."""
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    groups, n, chunk = model["mamba_n_groups"], model["ssm_state_size"], model["mamba_chunk_size"]
    keys = (chunk + 1) / 2
    return 2 * groups * n * keys + 2 * heads * p * keys + 2 * 2 * heads * p * n


def ssm_layer_flops(model: dict) -> float:
    """One Mamba-2 layer on one event: the two projections and the scan."""
    h, inner = model["hidden_size"], model["mamba_num_heads"] * model["mamba_head_dim"]
    into = 2 * inner + 2 * model["mamba_n_groups"] * model["ssm_state_size"] + model["mamba_num_heads"]
    return 2 * h * into + 2 * inner * h + ssm_scan_flops(model)


def relu2_flops(hidden: int, inner: int) -> float:
    """Up and down products of one ungated feed-forward on one row."""
    return 2 * 2 * hidden * inner


def attention_layer_flops(model: dict, keys: float) -> float:
    """One grouped-query attention layer on one event that sees ``keys`` keys."""
    h, heads, kv, d = (model[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    return 2 * h * d * (2 * heads + 2 * kv) + 2 * 2 * heads * d * keys


def forward_flops_per_event(model: dict, vocab: dict, global_keys: float, pairs_per_event: float) -> float:
    """Forward operations for one real event. ``pairs_per_event`` is the mean
    number of token-expert pairs computed here per real event and routed
    layer."""
    h = model["hidden_size"]
    routed = (
        2 * h * model["moe_router_width"]
        + relu2_flops(h, model["moe_shared_expert_intermediate_size"])
        + pairs_per_event * relu2_flops(h, model["moe_intermediate_size"])
    )
    by_letter = {"M": ssm_layer_flops(model), "E": routed, "*": attention_layer_flops(model, global_keys)}
    total = sum(by_letter[letter] for letter in model["pattern"])
    n_reg = sum(2 * vocab["vocab_sizes"][m] for m in vocab["multivariate_regression"])
    heads = 2 * h * (vocab["vocab_size"] + n_reg + 3 * model["tte_components"] + len(vocab["measurements_idxmap"]))
    return total + heads


def ssm_scan_needs(events: float, model: dict, itemsize: int) -> dict:
    """Operations and bytes the scans of all the Mamba-2 layers need for
    ``events`` real events, forward and backward, without recomputation.
    Bytes an event and layer: forward reads ``x``, ``B``, ``C`` and the step
    size (float32) and writes ``y``; backward reads them and ``dy`` and writes
    their four gradients. The carried states stay on the chip."""
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    plane, groups = heads * p * itemsize, 2 * model["mamba_n_groups"] * model["ssm_state_size"] * itemsize
    layers = model["pattern"].count("M")
    flops = events * layers * ssm_scan_flops(model)
    return {
        "fwd_flops": flops, "bwd_flops": 2 * flops,
        "fwd_bytes": events * layers * (2 * plane + groups + 4 * heads),
        "bwd_bytes": events * layers * (3 * plane + 2 * groups + 2 * 4 * heads),
    }


def relu2_experts_needs(pairs: float, layer_steps: float, model: dict, itemsize: int) -> dict:
    """Operations and bytes the held experts' two products need for ``pairs``
    token-expert pairs over ``layer_steps`` (routed layers times optimizer
    steps), forward and backward, without recomputation: two thirds of a
    gated expert's at the same widths (`flops_routed.routed_experts_needs`,
    whose bytes are counted the same way)."""
    h, inner, held = model["hidden_size"], model["moe_intermediate_size"], model["n_routed_experts"]
    flops = pairs * relu2_flops(h, inner)
    rows = pairs * (2 * h + 2 * inner) * itemsize
    weights = layer_steps * held * 2 * h * inner * itemsize
    return {
        "fwd_flops": flops, "bwd_flops": 2 * flops,
        "fwd_bytes": rows + weights, "bwd_bytes": 2 * rows + 2 * weights,
    }
