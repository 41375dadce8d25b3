"""Plain reference of the event-stream model over GLM-4.7-Flash's block.

The backbone follows the published ``glm4_moe_lite`` model
(https://huggingface.co/zai-org/GLM-4.7-Flash, ``config.json``; the block is
the DeepSeek-V2/V3 one: latent attention, a leading dense SwiGLU layer, then
routed layers with a shared expert under ``noaux_tc`` routing) in
straightforward ``jax.numpy`` and float32: no kernels, no cache, no grouped
products. The event embedding, the time encoding, the head stack, the loss,
AdamW and the lower-precision operands are `reference/esgpt.py`'s, by import:
they are the same model's and this file adds no second copy of them.

One row per event ``x``; no bias anywhere::

    h <- h + MLA(RMSNorm(h));  h <- h + FFN_l(RMSNorm(h));  RMSNorm before the heads
    RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)
    MLA: c_q = RMSNorm(x W_qa); q = c_q W_qb (heads of nope + rope)
         [c_kv ; k_r] = x W_kva; [k_nope ; v] = RMSNorm(c_kv) W_kvb
         RoPE on q_r and k_r (rotate-half pairing; k_r shared by the heads)
         softmax([q_nope ; q_r][k_nope ; k_r]^T / sqrt(nope + rope)) v, causal; concat(heads) W_o
    FFN_0 = D(x) = (silu(x W_g) * x W_u) W_d
    FFN_l = S(x) + sum_{e in T and held} w_e E_e(x),  s = sigmoid(x W_r),  T = top_k(s + b),
            w_e = scaling * s_e / sum_{j in T} s_j

Departures from the published model, each also in the configuration's file:

* the token table and the output head have no counterpart in an event-stream
  model: the event embedding (with the continuous-time encoding) and the
  generative head stack stand in their places;
* a RoPE position is the event's index inside its subject, restarting at
  every segment of a packed row;
* the selection bias ``b`` is held at zero: it gets no gradient, and its
  update belongs to a train loop that the system does not have; the router's
  product is float32;
* the share of one chip of eight: this chip holds ``n_routed_experts`` of the
  router's ``moe_router_width`` experts, from ``moe_expert_offset`` on. The
  router scores all of them and normalises over all the chosen; what the
  absent experts would add is left out, and the partial result goes on;
* the next-token-prediction module (``num_nextn_predict_layers`` 1) is left
  out: it is an auxiliary training loss behind the last of the 47 layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import esgpt
from benchmark.reference.esgpt import adamw_step, bf16_operand, fp8_operand  # noqa: F401  (the job's controls)

NEG = esgpt.NEG


# ------------------------------------------------------------------ parameters
def param_shapes(model: dict, vocab: dict) -> dict:
    h, heads = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    inner, held = model["moe_intermediate_size"], model["n_routed_experts"]

    def swiglu(width):
        return {
            "gate_proj": {"kernel": (h, width)},
            "up_proj": {"kernel": (h, width)},
            "down_proj": {"kernel": (width, h)},
        }

    def block(kind):
        if kind == "routed":
            ffn = {
                "router": (h, model["moe_router_width"]),
                "e_score_correction_bias": (model["moe_router_width"],),
                "experts_gate_proj": (held, h, inner),
                "experts_up_proj": (held, h, inner),
                "experts_down_proj": (held, inner, h),
            }
            if model["n_shared_experts"]:
                ffn["shared_experts"] = swiglu(model["n_shared_experts"] * inner)
        else:
            ffn = swiglu(model["intermediate_size"])
        return {
            "input_layernorm": {"scale": (h,)},
            "self_attn": {
                "q_a_proj": {"kernel": (h, rq)},
                "q_a_layernorm": {"scale": (rq,)},
                "q_b_proj": {"kernel": (rq, heads * (dn + dr))},
                "kv_a_proj_with_mqa": {"kernel": (h, rkv + dr)},
                "kv_a_layernorm": {"scale": (rkv,)},
                "kv_b_proj": {"kernel": (rkv, heads * (dn + dv))},
                "o_proj": {"kernel": (heads * dv, h)},
            },
            "post_attention_layernorm": {"scale": (h,)},
            "mlp": ffn,
        }

    enc = {
        "input_layer": {"data_embedding_layer": {"embed_table": (vocab["vocab_size"], h)}},
        "ln_f": {"scale": (h,)},
    }
    for i, kind in enumerate(model["ffn_layers"]):
        enc[f"h{i}"] = block(kind)
    out = esgpt.param_shapes(dict(model, mode="ci", num_hidden_layers=0), vocab)["params"]["output_layer"]
    return {"params": {"encoder": enc, "output_layer": out}}


def init_params(model: dict, vocab: dict, key) -> dict:
    """Seeded parameters in the program's tree: matrices (the experts' stacks
    too) normal with ``init_std``, norm scales 1, a routed layer's selection
    bias 0."""
    shapes = param_shapes(model, vocab)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=esgpt._is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        if len(shape) >= 2:
            leaf = model["init_std"] * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        elif path[-1].key == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- model pieces
def rms_norm(x, p, eps):
    return p["scale"] * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def positions_in_segment(batch):
    """(B, L): each event's index inside its subject."""
    B, L = batch["event_mask"].shape
    idx = jnp.broadcast_to(jnp.arange(L), (B, L))
    seg = batch.get("segment_ids")
    if seg is None:
        return idx
    return idx - jax.lax.cummax(jnp.where(esgpt._segment_starts(seg), idx, 0), axis=1)


def rope(x, positions, theta):
    """Rotate-half RoPE over the last axis of ``x`` (B, L, ..., d)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2) / d)
    ang = positions[..., None] * inv_freq
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def allowed_keys(batch):
    """(B, L, L): causal, inside the query's segment, real events as keys."""
    L = batch["event_mask"].shape[1]
    ok = (jnp.arange(L)[None, :] <= jnp.arange(L)[:, None])[None] & batch["event_mask"][:, None, :]
    seg = batch.get("segment_ids")
    if seg is not None:
        ok = ok & (seg[:, :, None] == seg[:, None, :])
    return ok


def latent_attention(x, p, model, allowed, positions, quant):
    heads = model["num_attention_heads"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rkv, eps, theta = model["kv_lora_rank"], model["rms_norm_eps"], model["rope_theta"]
    B, L = x.shape[:2]
    c_q = rms_norm(esgpt._dense(x, p["q_a_proj"], quant), p["q_a_layernorm"], eps)
    q = esgpt._dense(c_q, p["q_b_proj"], quant).reshape(B, L, heads, dn + dr)
    kv_a = esgpt._dense(x, p["kv_a_proj_with_mqa"], quant)
    c_kv = rms_norm(kv_a[..., :rkv], p["kv_a_layernorm"], eps)
    kv = esgpt._dense(c_kv, p["kv_b_proj"], quant).reshape(B, L, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta)], axis=-1)
    k_r = rope(kv_a[..., rkv:], positions, theta)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, L, heads, dr))], axis=-1)
    v = kv[..., dn:]
    if quant is not None:
        q, k = quant(q), quant(k)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dn + dr)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], logits, NEG), axis=-1)
    if quant is not None:
        probs, v = quant(probs), quant(v)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, L, heads * dv)
    return esgpt._dense(out, p["o_proj"], quant)


def swiglu(x, p, quant):
    hidden = jax.nn.silu(esgpt._dense(x, p["gate_proj"], quant)) * esgpt._dense(x, p["up_proj"], quant)
    return esgpt._dense(hidden, p["down_proj"], quant)


def routing(x, p, model):
    """The chosen experts (..., k), by score plus selection bias, and their
    weights, by score alone; float32 whatever the operands' precision."""
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), model["num_experts_per_tok"])[1]
    chosen_scores = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        chosen_scores = chosen_scores / chosen_scores.sum(-1, keepdims=True)
    return chosen, model["routed_scaling_factor"] * chosen_scores


def routed_feed_forward(x, p, model, quant):
    """The shared expert plus the held experts' weighted outputs, and the
    chosen experts; every held expert is computed on every row and weighted
    by 0 where not chosen."""
    chosen, weights = routing(x, p, model)
    out = swiglu(x, p["shared_experts"], quant) if model["n_shared_experts"] else jnp.zeros_like(x)
    for i in range(model["n_routed_experts"]):
        w = jnp.sum(jnp.where(chosen == model["moe_expert_offset"] + i, weights, 0.0), axis=-1)
        expert = {name: {"kernel": p[f"experts_{name}"][i]} for name in ("gate_proj", "up_proj", "down_proj")}
        out = out + w[..., None] * swiglu(x, expert, quant)
    return out, chosen


def encode(params, batch, model, quant):
    """The encoding (B, L, h) and every routed layer's chosen experts."""
    enc = params["encoder"]
    m = batch["event_mask"][..., None]
    eps = model["rms_norm_eps"]
    table = enc["input_layer"]["data_embedding_layer"]["embed_table"]
    w = jnp.where(batch["dynamic_values_mask"], batch["dynamic_values"], 1.0)
    x = jnp.where(m, esgpt._bag(table, batch["dynamic_indices"], w), 0.0)
    if batch.get("static_indices") is not None:
        st = esgpt._bag(table, batch["static_indices"], jnp.ones(batch["static_indices"].shape))
        x = jnp.where(m, 0.5 * x + 0.5 * st[:, None], 0.0)
    x = esgpt._held(jnp.where(m, x + esgpt._time_encoding(batch, model["hidden_size"]), 0.0), quant)
    allowed, positions = allowed_keys(batch), positions_in_segment(batch)

    def layer(x, p, kind):
        normed = esgpt._held(rms_norm(x, p["input_layernorm"], eps), quant)
        x = x + latent_attention(normed, p["self_attn"], model, allowed, positions, quant)
        normed = esgpt._held(rms_norm(x, p["post_attention_layernorm"], eps), quant)
        if kind == "routed":
            fed, chosen = routed_feed_forward(normed, p["mlp"], model, quant)
        else:
            fed, chosen = swiglu(normed, p["mlp"], quant), None
        return esgpt._held(jnp.where(m, x + fed, 0.0), quant), chosen

    choices = []
    for i, kind in enumerate(model["ffn_layers"]):
        x, chosen = jax.checkpoint(layer, static_argnums=2)(x, enc[f"h{i}"], kind)
        if chosen is not None:
            choices.append(chosen)
    return rms_norm(x, enc["ln_f"], eps), choices


# ------------------------------------------------------------------------ loss
def rows_loss(params, batch, model, vocab, weights, quant=None):
    """The share of the batch loss that the rows of ``batch`` contribute
    (`esgpt.rows_loss` for the CI model, over this encoder)."""
    p = params["params"]
    masks = esgpt.head_row_masks(batch, vocab)
    enc, _ = encode(p, batch, model, quant)
    prev = jnp.concatenate([jnp.zeros_like(enc[:, :1]), enc[:, :-1]], axis=1)
    if batch.get("segment_ids") is not None:
        prev = jnp.where(esgpt._segment_starts(batch["segment_ids"])[..., None], 0.0, prev)
    rows = esgpt._content_losses(p, batch, lambda name: prev, vocab, quant, masks)
    total = sum((rows[name] * weights[name]).sum() for name in rows)
    tte = esgpt._tte_row_ll(p, batch, enc, model, quant, masks["tte"])
    return total - (tte * weights["tte"]).sum()


def batch_loss_and_grad(params, batch, model, vocab, rows_per_block: int, quant=None):
    """Loss and gradient of one batch, accumulated over blocks of rows."""
    B = batch["event_mask"].shape[0]
    if B % rows_per_block:
        raise ValueError(f"{B} rows do not split into blocks of {rows_per_block}")
    weights = esgpt.term_weights(batch, vocab)
    present = {k: v for k, v in batch.items() if v is not None}
    blocks = {k: v.reshape((B // rows_per_block, rows_per_block) + v.shape[1:]) for k, v in present.items()}
    fn = jax.value_and_grad(rows_loss)

    def one_block(carry, block):
        loss, grads = carry
        block = {k: block.get(k) for k in batch}
        l, g = fn(params, block, model, vocab, weights, quant)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one_block, zero, blocks)
    return loss, grads


def train_steps(params, batches: list, model, vocab, opt, rows_per_block: int, quant=None):
    """Follows ``len(batches)`` optimizer steps from fresh AdamW state.
    Returns the losses, the final parameters and the final first moment."""
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)

    def one_step(params, mu, nu, batch, count):
        loss, grads = batch_loss_and_grad(params, batch, model, vocab, rows_per_block, quant)
        return (*adamw_step(params, mu, nu, grads, count, opt), loss)

    step = jax.jit(one_step, donate_argnums=(0, 1, 2))
    losses = []
    for count, batch in enumerate(batches):
        params, mu, nu, loss = step(params, mu, nu, batch, count)
        losses.append(loss)
    return losses, params, mu


def routed_choices(params, batch, model, quant=None) -> list:
    """The chosen experts (B, L, k) of every routed layer, in order: the share
    of rows that choose differently under ``quant`` is how far a lower
    precision moves the discrete part of the model."""
    return encode(params["params"], batch, model, quant)[1]
