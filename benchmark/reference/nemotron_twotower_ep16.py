"""Plain reference of the event-stream model over the ``nemotron_h`` tower of
Nemotron-Labs-TwoTower-30B-A3B.

The backbone follows the published ``nemotron_h`` stack
(https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
``config.json``) in straightforward ``jax.numpy`` and float32: no kernels, no
cache, no grouped products, no chunked scan. The event embedding, the time
encoding, the head stack, the loss, AdamW and the lower-precision operands are
`reference/esgpt.py`'s, and the router, the norm and the key mask are
`reference/glm47flash_ep8.py`'s, by import: they are the same model's and this
file adds no second copy of them.

Every layer is ONE part behind one norm, ``h <- h + F(RMSNorm(h))`` with eps
1e-5, and one more RMSNorm follows the last layer. ``F`` by the letter of
``hybrid_override_pattern`` (no bias but the convolution's)::

    M  Mamba-2 mixer, heads H of width P, groups G, state N, kernel K
       [z | xBC | dt] = u W_in                    widths H P | H P + 2 G N | H
       xBC = silu(conv(xBC))                      depthwise causal, K taps and a bias a channel, over the events
                                                  of the same segment only: a tap before the segment's first event reads 0
       [x | B | C] = xBC                          head h reads group h // (H / G)
       Delta = softplus(dt + dt_bias),  A = -exp(A_log)
       S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T,  y_t = S_t C_t + D x_t     per head, S of P x N,
                                                  S zero before a segment's first event; a padding slot has x = 0, Delta = 0
       y = RMSNorm_grouped(y * silu(z))           gate first, then RMS over each of the G groups of H P / G channels
       out = y W_out
    E  s = sigmoid(x W_r) in float32, T = top_k(s + b), w_e = scaling * s_e / sum_{j in T} s_j
       y = S(x) + sum_{e in T and held} w_e E_e(x),   E_e(x) = relu(x U_e)^2 D_e,  S of the same form at its own width
    *  q = x W_q (heads of d), k, v = x W_k, x W_v (kv heads of d), query head i reads key/value head i // (heads / kv heads)
       softmax(q k^T / sqrt(d)) v, causal inside the packed segment; out = o W_o; no rotary embedding

The state-space layer here is the recurrence itself, a `lax.scan` over the
events with the state set to zero at a segment's first event, and the
convolution four shifted, masked adds by the event's index inside its segment:
it shares no algebra with the program's chunked form. (The scan is nested, an
outer scan over blocks of `SCAN_BLOCK` events whose inner scan is computed
again in the backward, so that the states kept are a block's.)

Departures from the published model, each also in the configuration's file:

* the token table and the output head have no counterpart in an event-stream
  model: the event embedding (with the continuous-time encoding) and the
  generative head stack stand in their places;
* the second, denoising tower (adaLN, bidirectional in-block attention,
  cross-tower conditioning) and decoding by diffusion over blocks are left
  out: the published configuration gives none of their sizes or equations;
* packed rows: the recurrent state, the convolution's history and attention
  all stop at a segment boundary;
* the selection bias ``b`` is held at zero and the router's product is float32;
* the share of one chip of sixteen: this chip holds ``n_routed_experts`` of the
  router's ``moe_router_width`` experts, from ``moe_expert_offset`` on; what
  the absent experts would add is left out, and the partial result goes on;
* initialisation (the catalog gives none): see `init_params`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import esgpt
from benchmark.reference.esgpt import adamw_step, bf16_operand, fp8_operand  # noqa: F401  (the job's controls)
from benchmark.reference.glm47flash_ep8 import allowed_keys, rms_norm, routing

NEG = esgpt.NEG
SCAN_BLOCK = 64
DT_RANGE, DT_FLOOR = (1e-3, 1e-1), 1e-4  # time_step_min, time_step_max, time_step_floor


# ------------------------------------------------------------------ parameters
def param_shapes(model: dict, vocab: dict) -> dict:
    h = model["hidden_size"]
    heads, p, groups, n = (model[k] for k in ("mamba_num_heads", "mamba_head_dim", "mamba_n_groups", "ssm_state_size"))
    inner, conv_dim = heads * p, heads * p + 2 * groups * n
    q_heads, kv_heads, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    width, held = model["moe_intermediate_size"], model["n_routed_experts"]

    parts = {
        "M": lambda: {"mixer": {
            "in_proj": {"kernel": (h, inner + conv_dim + heads)},
            "conv_kernel": (model["mamba_conv_kernel"], conv_dim),
            "conv_bias": (conv_dim,),
            "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
            "norm_scale": (inner,),
            "out_proj": {"kernel": (inner, h)},
        }},
        "E": lambda: {"mlp": {
            "router": (h, model["moe_router_width"]),
            "e_score_correction_bias": (model["moe_router_width"],),
            "experts_up_proj": (held, h, width),
            "experts_down_proj": (held, width, h),
            "shared_experts": {
                "up_proj": {"kernel": (h, model["moe_shared_expert_intermediate_size"])},
                "down_proj": {"kernel": (model["moe_shared_expert_intermediate_size"], h)},
            },
        }},
        "*": lambda: {"self_attn": {
            "q_proj": {"kernel": (h, q_heads * d)},
            "k_proj": {"kernel": (h, kv_heads * d)},
            "v_proj": {"kernel": (h, kv_heads * d)},
            "o_proj": {"kernel": (q_heads * d, h)},
        }},
    }
    enc = {
        "input_layer": {"data_embedding_layer": {"embed_table": (vocab["vocab_size"], h)}},
        "ln_f": {"scale": (h,)},
    }
    for i, letter in enumerate(model["pattern"]):
        enc[f"h{i}"] = {"input_layernorm": {"scale": (h,)}, **parts[letter]()}
    out = esgpt.param_shapes(dict(model, mode="ci", num_hidden_layers=0), vocab)["params"]["output_layer"]
    return {"params": {"encoder": enc, "output_layer": out}}


def init_params(model: dict, vocab: dict, key) -> dict:
    """Seeded parameters in the program's tree. Matrices and expert stacks
    normal with ``init_std``; the products back onto the residual stream
    (``out_proj``, ``o_proj``, the experts' and the shared ``down_proj``) times
    ``1 / sqrt(published_layers)`` (``rescale_prenorm_residual``, at the
    published depth); ``A_log = log(1..H)``, ``D = 1``, ``dt_bias`` the inverse
    softplus of ``exp(U(log 0.001, log 0.1))`` floored at 1e-4; the
    convolution's weights uniform in +-1/2, its bias 0; norm weights 1; the
    selection bias 0."""
    shapes = param_shapes(model, vocab)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=esgpt._is_shape)
    onto_residual = 1.0 / math.sqrt(model["published_layers"])
    out = []
    for i, (path, shape) in enumerate(leaves):
        names = [k.key for k in path]
        name, k = names[-1], jax.random.fold_in(key, i)
        if name == "conv_kernel":
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif len(shape) >= 2:
            leaf = model["init_std"] * jax.random.normal(k, shape, jnp.float32)
            if "encoder" in names and ("down_proj" in name or {"down_proj", "out_proj", "o_proj"} & set(names)):
                leaf = leaf * onto_residual
        elif name == "A_log":
            leaf = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            dt = jnp.maximum(dt, DT_FLOOR)
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name in ("scale", "norm_scale", "D"):
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- model pieces
def _segments(batch):
    """``(first, since)`` of (B, L): whether an event is its segment's first
    and its index inside the segment, a padding slot counted as a segment of
    its own."""
    mask = batch["event_mask"]
    B, L = mask.shape
    seg = batch.get("segment_ids")
    seg = jnp.where(mask, jnp.zeros((B, L), jnp.int32) if seg is None else seg, -1)
    first = esgpt._segment_starts(seg)
    idx = jnp.broadcast_to(jnp.arange(L), (B, L))
    return first, idx - jax.lax.cummax(jnp.where(first, idx, 0), axis=1)


def recurrence(x, dt, a, bmat, cmat, first):
    """``y_t = S_t C_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T`` per
    head, ``S`` zero before a segment's first event: a scan over the events.
    ``x`` (B, L, H, P), ``dt`` (B, L, H), ``a`` (H), ``bmat``/``cmat`` (B, L,
    G, N), ``first`` (B, L)."""
    B, L, H, P = x.shape
    r = H // bmat.shape[2]

    def step(state, now):
        x_t, dt_t, b_t, c_t, first_t = now
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        b_t, c_t = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)  # (B, H, N)
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    events = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bmat, cmat, first))
    zero = jnp.zeros((B, H, P, bmat.shape[-1]), jnp.float32)
    if L % SCAN_BLOCK or L == SCAN_BLOCK:
        y = jax.lax.scan(step, zero, events)[1]
    else:
        blocks = tuple(v.reshape((L // SCAN_BLOCK, SCAN_BLOCK) + v.shape[1:]) for v in events)
        y = jax.lax.scan(jax.checkpoint(lambda s, block: jax.lax.scan(step, s, block)), zero, blocks)[1]
        y = y.reshape((L,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(u, p, model, mask, first, since, quant):
    heads, P, groups, n = (model[k] for k in ("mamba_num_heads", "mamba_head_dim", "mamba_n_groups", "ssm_state_size"))
    inner, taps = heads * P, model["mamba_conv_kernel"]
    B, L = u.shape[:2]
    proj = esgpt._dense(u, p["in_proj"], quant)
    z, xbc, dt = proj[..., :inner], proj[..., inner:-heads], proj[..., -heads:]
    conv = p["conv_bias"] + p["conv_kernel"][-1] * xbc
    for back in range(1, min(taps, L)):
        earlier = jnp.concatenate([jnp.zeros_like(xbc[:, :back]), xbc[:, :-back]], axis=1)
        conv = conv + p["conv_kernel"][-1 - back] * jnp.where((since >= back)[..., None], earlier, 0.0)
    xbc = esgpt._held(jax.nn.silu(conv), quant)
    x = jnp.where(mask[..., None, None], xbc[..., :inner].reshape(B, L, heads, P), 0.0)
    bmat = xbc[..., inner : inner + groups * n].reshape(B, L, groups, n)
    cmat = xbc[..., inner + groups * n :].reshape(B, L, groups, n)
    dt = jnp.where(mask[..., None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), bmat, cmat, first) + p["D"][:, None] * x
    y = esgpt._held(y.reshape(B, L, inner), quant) * jax.nn.silu(z)
    y = y.reshape(B, L, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + model["rms_norm_eps"])
    return esgpt._dense(p["norm_scale"] * y.reshape(B, L, inner), p["out_proj"], quant)


def relu2(x, p, quant):
    return esgpt._dense(jnp.square(jax.nn.relu(esgpt._dense(x, p["up_proj"], quant))), p["down_proj"], quant)


def routed_feed_forward(x, p, model, quant):
    """The shared expert plus the held experts' weighted outputs, and the
    chosen experts; every held expert is computed on every row and weighted
    by 0 where not chosen."""
    chosen, weights = routing(x, p, model)
    out = relu2(x, p["shared_experts"], quant)
    for i in range(model["n_routed_experts"]):
        w = jnp.sum(jnp.where(chosen == model["moe_expert_offset"] + i, weights, 0.0), axis=-1)
        expert = {name: {"kernel": p[f"experts_{name}"][i]} for name in ("up_proj", "down_proj")}
        out = out + w[..., None] * relu2(x, expert, quant)
    return out, chosen


def grouped_query_attention(x, p, model, allowed, quant):
    q_heads, kv_heads, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    B, L = x.shape[:2]
    q = esgpt._dense(x, p["q_proj"], quant).reshape(B, L, q_heads, d)
    k = jnp.repeat(esgpt._dense(x, p["k_proj"], quant).reshape(B, L, kv_heads, d), q_heads // kv_heads, axis=2)
    v = jnp.repeat(esgpt._dense(x, p["v_proj"], quant).reshape(B, L, kv_heads, d), q_heads // kv_heads, axis=2)
    if quant is not None:
        q, k = quant(q), quant(k)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], logits, NEG), axis=-1)
    if quant is not None:
        probs, v = quant(probs), quant(v)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, L, q_heads * d)
    return esgpt._dense(out, p["o_proj"], quant)


def encode(params, batch, model, quant):
    """The encoding (B, L, h) and every routed layer's chosen experts."""
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
    allowed = allowed_keys(batch)
    first, since = _segments(batch)

    def layer(x, p, letter):
        normed = esgpt._held(rms_norm(x, p["input_layernorm"], eps), quant)
        chosen = None
        if letter == "M":
            part = mamba_mixer(normed, p["mixer"], model, mask, first, since, quant)
        elif letter == "E":
            part, chosen = routed_feed_forward(normed, p["mlp"], model, quant)
        else:
            part = grouped_query_attention(normed, p["self_attn"], model, allowed, quant)
        return esgpt._held(jnp.where(m, x + part, 0.0), quant), chosen

    choices = []
    for i, letter in enumerate(model["pattern"]):
        x, chosen = jax.checkpoint(layer, static_argnums=2)(x, enc[f"h{i}"], letter)
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
    """The chosen experts (B, L, k) of every routed layer, in order."""
    return encode(params["params"], batch, model, quant)[1]
