"""Plain reference of the event-stream model over Xing4.0-29B-A4B's block.

The backbone follows the published ``xing4_0`` configuration
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, ``config.json``) in
straightforward ``jax.numpy`` and float32: no kernels, no cache, no grouped
products, the attention an einsum under its mask, every held expert on every
row, the Sinkhorn loop a Python loop, autodiff through all of it. The event
embedding, the time encoding, the head stack, the loss, AdamW and the
lower-precision operands are `reference/esgpt.py`'s, and the norm, the key
mask, the positions, the gated feed-forward and the routed layer are
`reference/glm47flash_ep8.py`'s, by import: they are the same model's and
this file adds no second copy of them.

Sizes as published: hidden ``C`` 3,584; 32 heads; ``q_lora_rank`` 768,
``kv_lora_rank`` 512, nope 128, rope 64, value 128; dense width 9,216; expert
width 1,024; router 64, top-4, scale 2, sigmoid, ``norm_topk_prob``; 1 shared
expert; RMSNorm eps 1e-6; theta 10,000; ``hc_mult`` 4, 20 Sinkhorn
iterations, ``hc_eps`` 1e-6, clamp +-30. Layer 0 is latent attention and a
dense SwiGLU, the layers after it latent attention and a routed feed-forward.

**Residual streams** (manifold-constrained hyper-connections,
arXiv:2512.24880). An event's state is ``X`` in ``R^{n x C}``, ``n`` = 4. In:
the event embedding ``e`` replicated, ``X[i] = e``. Out:
``RMSNorm_f(sum_i X[i])``, then the head stack. Every sublayer ``F`` (a
layer's attention, then its feed-forward, each with maps of its own) is
wrapped so::

    r = vec(X) / sqrt(mean(vec(X)^2) + 1e-6)                over the nC = 14,336 values, no learned scale
    [p | q | R] = r Phi                                      Phi: nC x (n + n + n^2) = 14,336 x 24
    H_pre = sigmoid(a_pre p + b_pre)                         n
    H_post = 2 sigmoid(a_post q + b_post)                    n
    Z = clip(a_res mat(R) + b_res, -30, 30);  M = exp(Z)     n x n
    20 times: every column of M over its sum + hc_eps, then every row over its sum + hc_eps;  H_res = M
    u = sum_i H_pre[i] X[i];  y = F(RMSNorm(u))              the sublayer's own norm
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

**Latent attention** is GLM-4.7-Flash's (`reference/glm47flash_ep8.py`) at
these widths, with YaRN as DeepSeek-V3's public modelling code defines it:
``f_i = theta^(-2i/64)``, ``i`` = 0..31; ``dim(beta) = 64 ln(4096 / (2 pi
beta)) / (2 ln theta)``, ``low = floor(dim(32))``, ``high = ceil(dim(1))``,
clipped to [0, 63]; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
``inv_freq_i = (f_i / 64) ramp_i + f_i (1 - ramp_i)``; cos and sin times
``m(mscale) / m(mscale_all_dim)`` = 1; the softmax's scale ``192^-1/2 m^2``,
``m = 0.1 ln 64 + 1``.

Departures from the published model, each also in the configuration's file:

* the token table and the output head have no counterpart in an event-stream
  model: the event embedding (with the continuous-time encoding) and the
  generative head stack stand in their places;
* a RoPE position is the event's index inside its subject, restarting at
  every segment of a packed row;
* a sublayer is one hyper-connected unit (two a layer); the norm of step 1 has
  no learned scale; columns before rows; the clamp before ``exp``; ``hc_eps``
  in the Sinkhorn denominators and ``rms_norm_eps`` in step 1; replicate in,
  sum out; the initialisation of the maps (`init_params`);
* the selection bias is held at zero and the router's product is float32;
* the share of one chip of eight: experts ``moe_expert_offset`` ... of the
  router's ``moe_router_width``; what the absent experts would add is left
  out, and the partial result goes on;
* the next-token-prediction module (``num_nextn_predict_layers`` 1) is left
  out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import esgpt
from benchmark.reference import glm47flash_ep8 as glm
from benchmark.reference.esgpt import adamw_step, bf16_operand, fp8_operand  # noqa: F401  (the job's controls)
from benchmark.reference.glm47flash_ep8 import allowed_keys, positions_in_segment, rms_norm, routed_feed_forward, swiglu

NEG = esgpt.NEG
# The maps at the seed's weights: H_pre near 1/n, H_post near 1, H_res near the
# identity with 3 / (e^3.5 + 3) = 8% of a row's mass off the diagonal; the gains
# let Phi's product (of deviation 0.02 sqrt(14,336) = 2.4) move each logit by 0.24.
HC_GAIN = 0.1
HC_RES_DIAGONAL = 3.5


# ------------------------------------------------------------------ parameters
def hc_shapes(model: dict) -> dict:
    n, width = model["hc_mult"], model["hidden_size"]
    return {"phi": (n * width, 2 * n + n * n), "gain": (3,), "bias": (2 * n + n * n,)}


def param_shapes(model: dict, vocab: dict) -> dict:
    """`glm47flash_ep8.param_shapes` with two sets of maps a layer."""
    shapes = glm.param_shapes(model, vocab)
    for name, layer in shapes["params"]["encoder"].items():
        if name.startswith("h") and name[1:].isdigit():
            layer["mixer_hc"], layer["ffn_hc"] = hc_shapes(model), hc_shapes(model)
    return shapes


def init_params(model: dict, vocab: dict, key) -> dict:
    """Seeded parameters in the program's tree: matrices (the experts' stacks
    and ``Phi`` too) normal with ``init_std``, norm scales 1, a routed
    layer's selection bias 0; the maps' three gains `HC_GAIN`, their biases
    ``logit(1/n)`` (pre), 0 (post) and `HC_RES_DIAGONAL` on the diagonal of
    the res map, 0 off it."""
    n = model["hc_mult"]
    shapes = param_shapes(model, vocab)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=esgpt._is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name, owner = path[-1].key, path[-2].key
        if len(shape) >= 2:
            leaf = model["init_std"] * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        elif name == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "gain":
            leaf = jnp.full(shape, HC_GAIN, jnp.float32)
        elif name == "bias" and owner.endswith("_hc"):
            leaf = jnp.concatenate(
                [jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)), (HC_RES_DIAGONAL * jnp.eye(n)).reshape(-1)]
            ).astype(jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- model pieces
def yarn_inv_freq(d: int, theta: float, scaling: dict | None):
    """RoPE's ``d / 2`` frequencies, blended under a ``yarn`` group."""
    f = 1.0 / theta ** (jnp.arange(0, d, 2) / d)
    if scaling is None:
        return f

    def dim_of(turns):
        return d * math.log(scaling["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f / scaling["factor"]) * ramp + f * (1.0 - ramp)


def yarn_m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x, positions, theta, scaling):
    """Rotate-half RoPE over the last axis of ``x`` (B, L, ..., d)."""
    d = x.shape[-1]
    ang = positions[..., None] * yarn_inv_freq(d, theta, scaling)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    m = 1.0 if scaling is None else yarn_m(scaling["factor"], scaling["mscale"]) / yarn_m(
        scaling["factor"], scaling["mscale_all_dim"]
    )
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_attention(x, p, model, allowed, positions, quant):
    """`glm47flash_ep8.latent_attention` with a value width of its own, YaRN's
    frequencies and YaRN's softmax scale."""
    heads = model["num_attention_heads"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rkv, eps, theta, scaling = model["kv_lora_rank"], model["rms_norm_eps"], model["rope_theta"], model["rope_scaling"]
    B, L = x.shape[:2]
    c_q = rms_norm(esgpt._dense(x, p["q_a_proj"], quant), p["q_a_layernorm"], eps)
    q = esgpt._dense(c_q, p["q_b_proj"], quant).reshape(B, L, heads, dn + dr)
    kv_a = esgpt._dense(x, p["kv_a_proj_with_mqa"], quant)
    c_kv = rms_norm(kv_a[..., :rkv], p["kv_a_layernorm"], eps)
    kv = esgpt._dense(c_kv, p["kv_b_proj"], quant).reshape(B, L, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta, scaling)], axis=-1)
    k_r = rope(kv_a[..., rkv:], positions, theta, scaling)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, L, heads, dr))], axis=-1)
    v = kv[..., dn:]
    if quant is not None:
        q, k = quant(q), quant(k)
    scale = (dn + dr) ** -0.5
    if scaling is not None and scaling["mscale_all_dim"]:
        scale = scale * yarn_m(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(allowed[:, None], logits, NEG), axis=-1)
    if quant is not None:
        probs, v = quant(probs), quant(v)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, L, heads * dv)
    return esgpt._dense(out, p["o_proj"], quant)


def hc_maps(streams, p, model, res_identity=False, iters=None):
    """``(H_pre (B, L, n), H_post (B, L, n), H_res (B, L, n, n))`` of the
    streams ``(B, L, n, C)``. ``res_identity`` and ``iters`` plant faults for
    the tests: the res map as the identity, another number of iterations."""
    n = model["hc_mult"]
    B, L = streams.shape[:2]
    flat = streams.reshape(B, L, -1)
    r = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + model["rms_norm_eps"])
    proj = r @ p["phi"]
    gain, bias = p["gain"], p["bias"]
    h_pre = jax.nn.sigmoid(gain[0] * proj[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(gain[1] * proj[..., n : 2 * n] + bias[n : 2 * n])
    z = (gain[2] * proj[..., 2 * n :] + bias[2 * n :]).reshape(B, L, n, n)
    m = jnp.exp(jnp.clip(z, -model["hc_res_clamp"], model["hc_res_clamp"]))
    for _ in range(model["hc_sinkhorn_iters"] if iters is None else iters):
        m = m / (m.sum(axis=-2, keepdims=True) + model["hc_eps"])  # every column over its sum
        m = m / (m.sum(axis=-1, keepdims=True) + model["hc_eps"])  # every row over its sum
    if res_identity:
        m = jnp.broadcast_to(jnp.eye(n), m.shape)
    return h_pre, h_post, m


def hyper_connected(streams, p, model, sublayer, quant, **faults):
    """One sublayer between its mixes; the streams are held as the compute
    dtype holds them (the control only)."""
    h_pre, h_post, h_res = hc_maps(streams, p, model, **faults)
    u = esgpt._held(jnp.einsum("bln,blnc->blc", h_pre, streams), quant)
    y = sublayer(u)
    mixed = jnp.einsum("blij,bljc->blic", h_res, streams) + h_post[..., None] * y[:, :, None, :]
    return esgpt._held(mixed, quant)


def encode(params, batch, model, quant, **faults):
    """The encoding (B, L, h) and every routed layer's chosen experts."""
    enc = params["encoder"]
    m = batch["event_mask"][..., None]
    eps, n = model["rms_norm_eps"], model["hc_mult"]
    table = enc["input_layer"]["data_embedding_layer"]["embed_table"]
    w = jnp.where(batch["dynamic_values_mask"], batch["dynamic_values"], 1.0)
    x = jnp.where(m, esgpt._bag(table, batch["dynamic_indices"], w), 0.0)
    if batch.get("static_indices") is not None:
        st = esgpt._bag(table, batch["static_indices"], jnp.ones(batch["static_indices"].shape))
        x = jnp.where(m, 0.5 * x + 0.5 * st[:, None], 0.0)
    x = esgpt._held(jnp.where(m, x + esgpt._time_encoding(batch, model["hidden_size"]), 0.0), quant)
    allowed, positions = allowed_keys(batch), positions_in_segment(batch)

    def layer(streams, p, kind):
        def attention(u):
            normed = esgpt._held(rms_norm(u, p["input_layernorm"], eps), quant)
            return latent_attention(normed, p["self_attn"], model, allowed, positions, quant)

        chosen = []

        def feed_forward(u):
            normed = esgpt._held(rms_norm(u, p["post_attention_layernorm"], eps), quant)
            if kind != "routed":
                return swiglu(normed, p["mlp"], quant)
            fed, picked = routed_feed_forward(normed, p["mlp"], model, quant)
            chosen.append(picked)
            return fed

        streams = hyper_connected(streams, p["mixer_hc"], model, attention, quant, **faults)
        streams = hyper_connected(streams, p["ffn_hc"], model, feed_forward, quant, **faults)
        return jnp.where(m[:, :, None], streams, 0.0), (chosen[0] if chosen else None)

    streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1]))
    choices = []
    for i, kind in enumerate(model["ffn_layers"]):
        streams, chosen = jax.checkpoint(layer, static_argnums=2)(streams, enc[f"h{i}"], kind)
        if chosen is not None:
            choices.append(chosen)
    return rms_norm(esgpt._held(streams.sum(axis=2), quant), enc["ln_f"], eps), choices


# ------------------------------------------------------------------------ loss
def rows_loss(params, batch, model, vocab, weights, quant=None, **faults):
    """The share of the batch loss that the rows of ``batch`` contribute
    (`esgpt.rows_loss` for the CI model, over this encoder)."""
    p = params["params"]
    masks = esgpt.head_row_masks(batch, vocab)
    enc, _ = encode(p, batch, model, quant, **faults)
    prev = jnp.concatenate([jnp.zeros_like(enc[:, :1]), enc[:, :-1]], axis=1)
    if batch.get("segment_ids") is not None:
        prev = jnp.where(esgpt._segment_starts(batch["segment_ids"])[..., None], 0.0, prev)
    rows = esgpt._content_losses(p, batch, lambda name: prev, vocab, quant, masks)
    total = sum((rows[name] * weights[name]).sum() for name in rows)
    tte = esgpt._tte_row_ll(p, batch, enc, model, quant, masks["tte"])
    return total - (tte * weights["tte"]).sum()


def batch_loss_and_grad(params, batch, model, vocab, rows_per_block: int, quant=None, **faults):
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
        l, g = fn(params, block, model, vocab, weights, quant, **faults)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one_block, zero, blocks)
    return loss, grads


def train_steps(params, batches: list, model, vocab, opt, rows_per_block: int, quant=None, **faults):
    """Follows ``len(batches)`` optimizer steps from fresh AdamW state.
    Returns the losses, the final parameters and the final first moment.

    At the cell's size the parameters, both moments and the gradient are 12.55
    GB of float32, and the gradient is accumulated over blocks of rows into a
    second copy (3.14 GB more, with 2.1 GB of a block's activations: the step
    as one program compiles to 17.8 GB for the described v5e). So the gradient
    and the update are two programs, and the second moment, which the gradient
    does not read, waits on the host while the gradient is made."""
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    gradient = jax.jit(lambda p, b: batch_loss_and_grad(p, b, model, vocab, rows_per_block, quant, **faults))
    update = jax.jit(lambda p, m, v, g, c: adamw_step(p, m, v, g, c, opt), donate_argnums=(0, 1, 2))
    losses, nu_on_host = [], None
    for count, batch in enumerate(batches):
        loss, grads = gradient(params, batch)
        nu = jax.tree_util.tree_map(jnp.zeros_like, grads) if nu_on_host is None else jax.device_put(nu_on_host)
        params, mu, nu = update(params, mu, nu, grads, count)
        del grads
        nu_on_host = jax.device_get(nu)
        del nu
        losses.append(loss)
    return losses, params, mu


def routed_choices(params, batch, model, quant=None) -> list:
    """The chosen experts (B, L, k) of every routed layer, in order."""
    return encode(params["params"], batch, model, quant)[1]
