"""Plain reference of the event-stream model over LFM2-8B-A1B's block.

The backbone follows the published ``lfm2_moe`` model
(https://huggingface.co/LiquidAI/LFM2-8B-A1B, ``config.json``) in
straightforward ``jax.numpy`` and float32: no kernels, no cache, no grouped
products. The event embedding, the time encoding, the head stack, the loss,
AdamW and the lower-precision operands are `reference/esgpt.py`'s, the norm,
RoPE, the key mask and the gated feed-forward `reference/glm47flash_ep8.py`'s,
and the segments of a packed row `reference/nemotron_twotower_ep16.py`'s, by
import: they are the same model's and this file adds no second copy of them.

Every layer is ``h <- h + mixer(RMSNorm(h)); h <- h + ffn(RMSNorm(h))`` with
eps 1e-5, and one more RMSNorm follows the last layer; no bias anywhere::

    conv  [B | C | x~] = u W_in                          W_in of hidden x 3 hidden, split in that order
          v = B * x~
          c_t = sum_{j=0..L-1} w_j * v_{t-j}              depthwise, L = conv_L_cache taps; a tap that reaches
                                                         before the segment's first event reads 0
          out = (C * c) W_out
    mha   q = RMSNorm_d(x W_q), k = RMSNorm_d(x W_k)       per head over its width d, a scale each for q and k
          q, k rotated (RoPE over the whole head, rotate-half, theta rope_theta)
          softmax(q k^T / sqrt(d)) v, causal inside the packed segment, query head i reads
          key/value head i // (heads / kv heads); out = o W_o
    ffn   the first layers D(x) = (silu(x W_g) * x W_u) W_d; the rest
          s = sigmoid(x W_r) in float32, T = top_k(s + b),
          w_e = scaling * s_e / (sum_{j in T} s_j + 1e-6),  y = sum_{e in T and held} w_e E_e(x)

The convolution here is an explicit sum over the taps, each masked by the
event's index inside its segment; attention an einsum under its mask; routing
scores all of the router's outputs and computes only the experts held here.

Departures from the published model, each also in the configuration's file:

* the token table and the output head have no counterpart in an event-stream
  model: the event embedding (with the continuous-time encoding) and the
  generative head stack stand in their places;
* packed rows: the convolution's taps and attention stop at a segment
  boundary, and a RoPE position is the event's index inside its subject;
* the selection bias ``b`` (``use_expert_bias``) gets no gradient, and the
  published rule that moves it after every step belongs to a train loop the
  system does not have; the seed's ``b`` is the one that rule would hold the
  seed's router to, even loads over the router's experts on a batch of the
  seed's own (`balance_selection_bias`), and it stays there; the router's
  product is float32;
* the share of one chip of four: this chip holds ``n_routed_experts`` of the
  router's ``moe_router_width`` experts, from ``moe_expert_offset`` on; what
  the absent experts would add is left out, and the partial result goes on;
* initialisation (the catalog gives none): see `init_params`.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference import esgpt
from benchmark.reference.esgpt import adamw_step, bf16_operand, fp8_operand  # noqa: F401  (the job's controls)
from benchmark.reference.glm47flash_ep8 import allowed_keys, positions_in_segment, rms_norm, rope, swiglu
from benchmark.reference.nemotron_twotower_ep16 import _segments

NEG = esgpt.NEG
TOPK_NORM_EPS = 1e-6


# ------------------------------------------------------------------ parameters
def param_shapes(model: dict, vocab: dict) -> dict:
    h, heads, kv_heads, d = (model[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    inner, held = model["moe_intermediate_size"], model["n_routed_experts"]

    mixers = {
        "conv": lambda: {"conv": {
            "in_proj": {"kernel": (h, 3 * h)},
            "conv_kernel": (model["conv_L_cache"], h),
            "out_proj": {"kernel": (h, h)},
        }},
        "mha": lambda: {"self_attn": {
            "q_proj": {"kernel": (h, heads * d)},
            "k_proj": {"kernel": (h, kv_heads * d)},
            "v_proj": {"kernel": (h, kv_heads * d)},
            "q_layernorm": {"scale": (d,)},
            "k_layernorm": {"scale": (d,)},
            "o_proj": {"kernel": (heads * d, h)},
        }},
    }
    ffns = {
        "swiglu": lambda: {
            "gate_proj": {"kernel": (h, model["intermediate_size"])},
            "up_proj": {"kernel": (h, model["intermediate_size"])},
            "down_proj": {"kernel": (model["intermediate_size"], h)},
        },
        "routed": lambda: {
            "router": (h, model["moe_router_width"]),
            "e_score_correction_bias": (model["moe_router_width"],),
            "experts_gate_proj": (held, h, inner),
            "experts_up_proj": (held, h, inner),
            "experts_down_proj": (held, inner, h),
        },
    }
    enc = {
        "input_layer": {"data_embedding_layer": {"embed_table": (vocab["vocab_size"], h)}},
        "ln_f": {"scale": (h,)},
    }
    for i, (mixer, ffn) in enumerate(zip(model["mixer_layers"], model["ffn_layers"])):
        enc[f"h{i}"] = {
            "input_layernorm": {"scale": (h,)}, **mixers[mixer](),
            "post_attention_layernorm": {"scale": (h,)}, "mlp": ffns[ffn](),
        }
    out = esgpt.param_shapes(dict(model, mode="ci", num_hidden_layers=0), vocab)["params"]["output_layer"]
    return {"params": {"encoder": enc, "output_layer": out}}


def init_params(model: dict, vocab: dict, key) -> dict:
    """Seeded parameters in the program's tree: matrices and expert stacks
    normal with ``init_std``; the convolution's taps uniform in +-1/2, as the
    Mamba-2 mixer's; norm scales 1; the selection bias 0 (the job kind then
    sets it by `balance_selection_bias`)."""
    shapes = param_shapes(model, vocab)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=esgpt._is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name == "conv_kernel":
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif len(shape) >= 2:
            leaf = model["init_std"] * jax.random.normal(k, shape, jnp.float32)
        elif name == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- model pieces
def short_conv(u, p, model, mask, since, quant):
    """The gated short convolution: the taps as a sum, one masked shift a tap."""
    h = model["hidden_size"]
    proj = esgpt._dense(u, p["in_proj"], quant)
    b, c, x = proj[..., :h], proj[..., h : 2 * h], proj[..., 2 * h :]
    v = jnp.where(mask[..., None], b * x, 0.0)
    taps = p["conv_kernel"]
    conv = taps[-1] * v
    for back in range(1, min(taps.shape[0], v.shape[1])):
        earlier = jnp.concatenate([jnp.zeros_like(v[:, :back]), v[:, :-back]], axis=1)
        conv = conv + taps[-1 - back] * jnp.where((since >= back)[..., None], earlier, 0.0)
    return esgpt._dense(esgpt._held(c * conv, quant), p["out_proj"], quant)


def grouped_query_attention(x, p, model, allowed, positions, quant):
    heads, kv_heads, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    B, L = x.shape[:2]
    q = rms_norm(esgpt._dense(x, p["q_proj"], quant).reshape(B, L, heads, d), p["q_layernorm"], eps)
    k = rms_norm(esgpt._dense(x, p["k_proj"], quant).reshape(B, L, kv_heads, d), p["k_layernorm"], eps)
    v = esgpt._dense(x, p["v_proj"], quant).reshape(B, L, kv_heads, d)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    if quant is not None:
        q, k = quant(q), quant(k)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], logits, NEG), axis=-1)
    if quant is not None:
        probs, v = quant(probs), quant(v)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, L, heads * d)
    return esgpt._dense(out, p["o_proj"], quant)


def routing(scores, p, model):
    """The chosen experts (..., k), by score plus selection bias, and their
    weights, by score alone, normalised with the published 1e-6; float32
    whatever the operands' precision."""
    chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), model["num_experts_per_tok"])[1]
    chosen_scores = jnp.take_along_axis(scores, chosen, axis=-1)
    chosen_scores = chosen_scores / (chosen_scores.sum(-1, keepdims=True) + TOPK_NORM_EPS)
    return chosen, model["routed_scaling_factor"] * chosen_scores


def routed_feed_forward(x, p, model, quant):
    """The held experts' weighted outputs, and the router's scores; every held
    expert is computed on every row and weighted by 0 where not chosen. No
    shared expert."""
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen, weights = routing(scores, p, model)
    out = jnp.zeros_like(x)
    for i in range(model["n_routed_experts"]):
        w = jnp.sum(jnp.where(chosen == model["moe_expert_offset"] + i, weights, 0.0), axis=-1)
        expert = {name: {"kernel": p[f"experts_{name}"][i]} for name in ("gate_proj", "up_proj", "down_proj")}
        out = out + w[..., None] * swiglu(x, expert, quant)
    return out, scores


def encode(params, batch, model, quant):
    """The encoding (B, L, h) and every routed layer's router scores (B, L, W)."""
    enc = params["encoder"]
    mask = batch["event_mask"]
    m = mask[..., None]
    eps = model["rms_norm_eps"]
    table = enc["input_layer"]["data_embedding_layer"]["embed_table"]
    w = jnp.where(batch["dynamic_values_mask"], batch["dynamic_values"], 1.0)
    x = jnp.where(m, esgpt._bag(table, batch["dynamic_indices"], w), 0.0)
    if batch.get("static_indices") is not None:
        st = esgpt._bag(table, batch["static_indices"], jnp.ones(batch["static_indices"].shape))
        x = jnp.where(m, 0.5 * x + 0.5 * st[:, None], 0.0)
    x = esgpt._held(jnp.where(m, x + esgpt._time_encoding(batch, model["hidden_size"]), 0.0), quant)
    allowed, positions = allowed_keys(batch), positions_in_segment(batch)
    _, since = _segments(batch)

    def layer(x, p, kinds):
        mixer, ffn = kinds
        normed = esgpt._held(rms_norm(x, p["input_layernorm"], eps), quant)
        if mixer == "conv":
            x = x + short_conv(normed, p["conv"], model, mask, since, quant)
        else:
            x = x + grouped_query_attention(normed, p["self_attn"], model, allowed, positions, quant)
        normed = esgpt._held(rms_norm(x, p["post_attention_layernorm"], eps), quant)
        if ffn == "routed":
            fed, scores = routed_feed_forward(normed, p["mlp"], model, quant)
        else:
            fed, scores = swiglu(normed, p["mlp"], quant), None
        return esgpt._held(jnp.where(m, x + fed, 0.0), quant), scores

    routed = []
    for i, kinds in enumerate(zip(model["mixer_layers"], model["ffn_layers"])):
        x, scores = jax.checkpoint(layer, static_argnums=2)(x, enc[f"h{i}"], kinds)
        if scores is not None:
            routed.append(scores)
    return rms_norm(x, enc["ln_f"], eps), routed


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


# ------------------------------------------------------------ the selection bias
BALANCE_STEPS = 1000


def routed_layers(model: dict) -> list[str]:
    """The names of the routed layers, in order."""
    return [f"h{i}" for i, ffn in enumerate(model["ffn_layers"]) if ffn == "routed"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def even_bias(scores, weight, top_k: int, steps: int = BALANCE_STEPS):
    """The selection bias ``(W,)`` under which the rows of ``scores`` ``(N,
    W)``, each counted ``weight`` ``(N,)`` times, choose every expert equally
    often. The bias descends ``load_e / mean load - 1`` with a shrinking step:
    the loads are a subgradient of the balanced assignment's convex dual, so
    the walk settles on even loads. Centred: a shift of every expert's bias
    chooses the same."""
    target = weight.sum() * top_k / scores.shape[-1]
    step0 = 0.1 * jnp.std(scores)

    def step(t, bias):
        chosen = jax.lax.top_k(scores + bias, top_k)[1]
        load = jnp.zeros_like(bias).at[chosen].add(jnp.broadcast_to(weight[:, None], chosen.shape))
        return bias - step0 * 0.995**t * (load / target - 1)

    bias = jax.lax.fori_loop(0, steps, step, jnp.zeros(scores.shape[-1], jnp.float32))
    return bias - bias.mean()


def with_selection_bias(params, biases: dict):
    """``params`` with the named routed layers' selection biases replaced."""
    enc = params["params"]["encoder"]
    enc = {**enc, **{name: {**enc[name], "mlp": {**enc[name]["mlp"], "e_score_correction_bias": b}}
                     for name, b in biases.items()}}
    return {**params, "params": {**params["params"], "encoder": enc}}


@functools.lru_cache(maxsize=4)
def _router_scores(model_json: str):
    """The jitted forward to every routed layer's scores, one per model (the
    program's set-up and the reference's run share its compile)."""
    model = json.loads(model_json)
    return jax.jit(lambda p, block: encode(p["params"], block, model, None)[1])


def balance_selection_bias(params, batches: list, model: dict, rows_per_block: int) -> dict:
    """Every routed layer's selection bias under which the real events of
    ``batches`` choose each of the router's experts equally often: the fixed
    point of the published rule (``b_e += u * sign(mean load - load_e)``
    after every step), which holds a trained model's loads even. Layer by
    layer, each on the router scores that the biases before it give; the
    forward runs in blocks of ``rows_per_block`` rows at the default matmul
    precision. Returns ``{layer name: (W,) bias}``."""
    forward = _router_scores(json.dumps(model, sort_keys=True))
    blocks = []
    for batch in batches:
        for lo in range(0, batch["event_mask"].shape[0], rows_per_block):
            blocks.append({k: (None if v is None else v[lo : lo + rows_per_block]) for k, v in batch.items()})
    weight = jnp.concatenate([jnp.asarray(b["event_mask"], jnp.float32).reshape(-1) for b in blocks])
    biases = {}
    for i, name in enumerate(routed_layers(model)):
        with jax.default_matmul_precision("default"):
            got = [forward(with_selection_bias(params, biases), block)[i] for block in blocks]
        scores = jnp.concatenate([s.reshape(-1, s.shape[-1]) for s in got])
        biases[name] = even_bias(scores, weight, model["num_experts_per_tok"])
    return biases
