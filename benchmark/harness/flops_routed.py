"""Operations and bytes of a backbone of latent attention and routed
feed-forwards (the job kind ``pretrain_routed``), from its shapes.

The same convention as `harness/flops.py`: matrix products and attention only,
2 operations a multiply-add, forward; a training step needs three times the
forward; recomputation is not counted. A routed layer is counted by the
token-expert pairs really computed here (the program's counter), never by the
buffer they are ordered into.
"""

from __future__ import annotations


def latent_attention_flops(model: dict, keys: float) -> float:
    """One layer's latent attention on one event that sees ``keys`` keys."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    projections = h * rq + rq * heads * (dn + dr) + h * (rkv + dr) + rkv * heads * (dn + dv) + heads * dv * h
    return 2 * projections + 2 * heads * (dn + dr) * keys + 2 * heads * dv * keys


def swiglu_flops(hidden: int, inner: int) -> float:
    """Gate, up and down products of one gated feed-forward on one row."""
    return 2 * 3 * hidden * inner


def forward_flops_per_event(model: dict, vocab: dict, global_keys: float, pairs_per_event: float) -> float:
    """Forward operations for one real event. ``pairs_per_event`` is the mean
    number of token-expert pairs computed here per real event and routed
    layer (top-k times the share of the experts held, at even routing)."""
    h, inner = model["hidden_size"], model["moe_intermediate_size"]
    total = 0.0
    for kind in model["ffn_layers"]:
        total += latent_attention_flops(model, global_keys)
        if kind == "routed":
            total += 2 * h * model["moe_router_width"]
            total += model["n_shared_experts"] * swiglu_flops(h, inner)
            total += pairs_per_event * swiglu_flops(h, inner)
        else:
            total += swiglu_flops(h, model["intermediate_size"])
    n_reg = sum(2 * vocab["vocab_sizes"][m] for m in vocab["multivariate_regression"])
    heads = 2 * h * (vocab["vocab_size"] + n_reg + 3 * model["tte_components"] + len(vocab["measurements_idxmap"]))
    return total + heads


def routed_experts_needs(pairs: float, layer_steps: float, model: dict, itemsize: int) -> dict:
    """Operations and bytes the held experts' three products need for
    ``pairs`` token-expert pairs over ``layer_steps`` (routed layers times
    optimizer steps), forward and backward, without recomputation. Bytes: each
    product reads its input rows and writes its output rows once forward; the
    backward reads and writes as much for the input's gradient and reads both
    again for the weights' gradient; every held matrix is read once forward,
    once backward, and its gradient written once."""
    h, inner, held = model["hidden_size"], model["moe_intermediate_size"], model["n_routed_experts"]
    flops = pairs * swiglu_flops(h, inner)
    rows = pairs * (3 * h + 3 * inner) * itemsize
    weights = layer_steps * held * 3 * h * inner * itemsize
    return {
        "fwd_flops": flops, "bwd_flops": 2 * flops,
        "fwd_bytes": rows + weights, "bwd_bytes": 2 * rows + 2 * weights,
    }
