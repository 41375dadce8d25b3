"""Operations a configuration needs per event, from its shapes.

Matrix multiplications and attention only (2 operations per multiply-add),
forward; a training step needs three times the forward (the backward is two
matmuls for each forward one). Recomputation under a remat policy is not
counted, embedding gathers are not multiplications, and elementwise work is
left out: this is the numerator of a model FLOP/s utilization, not a
profile.
"""

from __future__ import annotations


def _block(h: int, inner: int) -> int:
    """One feed-forward block on one position."""
    return 2 * (2 * h * inner)


def forward_flops_per_event(model: dict, vocab: dict, global_keys: float, local_keys: float) -> float:
    """Forward operations for one real event.

    ``global_keys`` / ``local_keys`` are the mean number of keys a query of a
    global / local sequence-attention layer attends to in the traffic at hand
    (causal, inside its segment, inside the window).
    """
    h, inner, n_layers = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    types = model["seq_attention_types"]
    keys = [local_keys if types[i % len(types)] == "local" else global_keys for i in range(n_layers)]
    seq_attn = sum(2 * 4 * h * h + 4 * h * k for k in keys)  # q,k,v,out + QK^T and PV
    sizes = vocab["vocab_sizes"]
    n_cls = sum(sizes[m] for m in vocab["single_label_classification"] + vocab["multi_label_classification"])
    n_reg = sum(2 * sizes[m] for m in vocab["multivariate_regression"])
    tte = 3 * model["tte_components"]
    n_meas = len(vocab["measurements_idxmap"])
    if model["mode"] == "ci":
        # CI projects the whole vocabulary plane once per event.
        heads = 2 * h * (vocab["vocab_size"] + n_reg + tte + n_meas)
        return seq_attn + n_layers * _block(h, inner) + heads
    G = len(model["measurements_per_dep_graph_level"])
    dep_proj = 2 * h * h * (2 * (G + 1) + 2 * G)  # k,v on G+1 positions; q,out on G
    dep_attn = 4 * h * sum(range(2, G + 2))  # level g sees the history and g levels
    dep = n_layers * (dep_proj + dep_attn + G * _block(h, inner))
    heads = 2 * h * (n_cls + n_reg + tte + (G - 1) * n_meas)
    return seq_attn + dep + heads


def train_flops_per_event(model: dict, vocab: dict, global_keys: float, local_keys: float) -> float:
    return 3 * forward_flops_per_event(model, vocab, global_keys, local_keys)


def attention_needs(n_queries: float, keys_per_query: float, heads: int, head_dim: int, itemsize: int) -> dict:
    """Operations and bytes that causal attention over ``n_queries`` queries
    needs, forward and backward, without recomputation: forward QK^T and PV;
    backward dV, dP, dQ, dK. Bytes: q, k, v, o read or written once forward;
    q, k, v, o, do read and dq, dk, dv written backward."""
    per = 2 * heads * head_dim * n_queries * keys_per_query  # one matmul
    plane = n_queries * heads * head_dim * itemsize
    return {
        "fwd_flops": 2 * per, "bwd_flops": 4 * per,
        "fwd_bytes": 4 * plane, "bwd_bytes": 8 * plane,
    }


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take over the time it took, in percent,
    and which of the two bounds it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
