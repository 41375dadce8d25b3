"""Plain reference of the event-stream point-process transformer.

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no batching
tricks: the conditionally-independent (CI) and nested-attention (NA) models'
forward pass, their loss, and AdamW with the warm-up/polynomial-decay
schedule. It imports nothing of ``eventstreamgpt_tpu`` and is given nothing
the program has made: parameters come from `init_params` (the benchmark's
own, from the seed), data from the benchmark's cohort arrays.

It follows the published model (McDermott et al., "Event Stream GPT",
EventStream/transformer/*.py) with the departures the program states:

* attention logits are not scaled by ``1/sqrt(d)`` (GPT-Neo lineage);
* packed rows (``segment_ids``): attention, the time origin, the CI
  next-event shift and the TTE gap all stop at a segment boundary;
* a row with no observed inter-event gap contributes 0 to the TTE term
  instead of dividing by zero.

``quant`` (used by the lower-precision control only) maps a matmul operand
to the operand the lower precision would see; the reference proper passes
``None``.

Parameters are a nested dict in the layout of the program's checkpoint
(``params/encoder/h{i}/...``): that layout is the interface between the
benchmark's parameters and the program, not something the program made.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

NEG = -1e30
LN_EPS = 1e-5
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ------------------------------------------------------------------ parameters
def param_shapes(model: dict, vocab: dict) -> dict:
    """The nested dict of parameter shapes of one configuration."""
    h, inner = model["hidden_size"], model["intermediate_size"]
    V = vocab["vocab_size"]

    def attn():
        return {
            "attention": {
                "q_proj": {"kernel": (h, h)},
                "k_proj": {"kernel": (h, h)},
                "v_proj": {"kernel": (h, h)},
                "out_proj": {"kernel": (h, h), "bias": (h,)},
            },
            "layer_norm": {"scale": (h,), "bias": (h,)},
        }

    def block():
        return {
            "attn": attn(),
            "layer_norm": {"scale": (h,), "bias": (h,)},
            "mlp": {
                "c_fc": {"kernel": (h, inner), "bias": (inner,)},
                "c_proj": {"kernel": (inner, h), "bias": (h,)},
            },
        }

    enc = {
        "input_layer": {"data_embedding_layer": {"embed_table": (V, h)}},
        "ln_f": {"scale": (h,), "bias": (h,)},
    }
    for i in range(model["num_hidden_layers"]):
        if model["mode"] == "ci":
            enc[f"h{i}"] = block()
        else:
            enc[f"h{i}"] = {"block": {"seq_attn": attn(), "dep_graph_block": block()}}
    out = {
        "ClassificationLayer": {"kernel": (h, V), "bias": (V,)},
        "IsObservedLayer": {
            "kernel": (h, len(vocab["measurements_idxmap"])),
            "bias": (len(vocab["measurements_idxmap"]),),
        },
        "TTE_layer": {
            "proj": {
                "kernel": (h, 3 * model["tte_components"]),
                "bias": (3 * model["tte_components"],),
            }
        },
    }
    for m in vocab["multivariate_regression"]:
        n = 2 * vocab["vocab_sizes"][m]
        out[f"regression_layer_{m}"] = {"proj": {"kernel": (h, n), "bias": (n,)}}
    return {"params": {"encoder": enc, "output_layer": out}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(model: dict, vocab: dict, key) -> dict:
    """Seeded parameters, made in one traced call (jit it, with the key an
    argument so that every seed runs one compiled program): matrices normal
    with the configuration's ``init_std``, norm scales 1, biases 0."""
    shapes = param_shapes(model, vocab)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if len(shape) == 2:
            leaf = model["init_std"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            )
        elif name == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jnp.zeros(shape, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- model pieces
def _mm(x, w, quant):
    if quant is not None:
        x, w = quant(x), quant(w)
    return x @ w


def _dense(x, p, quant):
    y = _mm(x, p["kernel"], quant)
    return y + p["bias"] if "bias" in p else y


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _held(x, quant):
    """An activation as the compute dtype holds it between blocks (the
    control only; the reference proper holds float32)."""
    return x if quant is None else quant(x)


def _segment_starts(seg):
    return jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)


def _time_encoding(batch, h):
    """Sinusoids over minutes since the subject's (segment's) first event."""
    td = jnp.where(batch["event_mask"], batch["time_delta"], 0.0)
    csum = jnp.cumsum(td, axis=-1)
    t = jnp.concatenate([jnp.zeros_like(csum[:, :1]), csum[:, :-1]], axis=1)
    if batch.get("segment_ids") is not None:
        start = _segment_starts(batch["segment_ids"])
        t = t - jax.lax.cummax(jnp.where(start, t, -jnp.inf), axis=1)
    div = jnp.exp(jnp.arange(0, h, 2) * (-math.log(10000.0) / h))
    ang = t[..., None] * div
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1).reshape(t.shape + (h,))


def _attention(x, p, model, allowed, quant, q_from: int = 0):
    """Multi-head attention over axis -2 of ``x`` (..., S, h); ``allowed`` is
    a boolean (..., Q, S) mask; queries are positions ``q_from:``."""
    H, D = model["num_attention_heads"], model["head_dim"]

    def heads(y):
        return y.reshape(y.shape[:-1] + (H, D))

    q = heads(_dense(x[..., q_from:, :], p["q_proj"], quant))
    k = heads(_dense(x, p["k_proj"], quant))
    v = heads(_dense(x, p["v_proj"], quant))
    if quant is not None:
        q, k = quant(q), quant(k)
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k)
    logits = jnp.where(allowed[..., None, :, :], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    if quant is not None:
        probs, v = quant(probs), quant(v)
    out = jnp.einsum("...hqk,...khd->...qhd", probs, v)
    return _dense(out.reshape(out.shape[:-2] + (H * D,)), p["out_proj"], quant)


def _seq_allowed(batch, layer: int, model):
    """(B, L, L): causal, inside the window of a local layer, inside the
    query's segment, and only real events as keys."""
    L = batch["event_mask"].shape[1]
    q = jnp.arange(L)[:, None]
    k = jnp.arange(L)[None, :]
    ok = k <= q
    types = model["seq_attention_types"]
    if types[layer % len(types)] == "local":
        ok = ok & (k > q - model["seq_window_size"])
    ok = ok[None] & batch["event_mask"][:, None, :]
    seg = batch.get("segment_ids")
    if seg is not None:
        ok = ok & (seg[:, :, None] == seg[:, None, :])
    return ok


def _mlp(x, p, quant):
    return _dense(jax.nn.gelu(_dense(x, p["c_fc"], quant), approximate=True), p["c_proj"], quant)


def _bag(table, idx, w):
    """Sum of ``w``-weighted table rows; index 0 is padding."""
    w = w * (idx != 0)
    return jnp.einsum("...md,...m->...d", table[idx], w)


def _ci_encode(params, batch, model, quant):
    enc = params["encoder"]
    h = model["hidden_size"]
    m = batch["event_mask"][..., None]
    table = enc["input_layer"]["data_embedding_layer"]["embed_table"]
    w = jnp.where(batch["dynamic_values_mask"], batch["dynamic_values"], 1.0)
    x = _bag(table, batch["dynamic_indices"], w)
    x = jnp.where(m, x, 0.0)
    if batch.get("static_indices") is not None:
        st = _bag(table, batch["static_indices"], jnp.ones(batch["static_indices"].shape))
        x = jnp.where(m, 0.5 * x + 0.5 * st[:, None], 0.0)
    x = _held(jnp.where(m, x + _time_encoding(batch, h), 0.0), quant)

    def layer(x, p, allowed):
        x = x + _attention(_layer_norm(x, p["attn"]["layer_norm"]), p["attn"]["attention"], model, allowed, quant)
        x = x + _mlp(_layer_norm(x, p["layer_norm"]), p["mlp"], quant)
        return _held(jnp.where(m, x, 0.0), quant)

    for i in range(model["num_hidden_layers"]):
        x = jax.checkpoint(layer)(x, enc[f"h{i}"], _seq_allowed(batch, i, model))
    return _layer_norm(x, enc["ln_f"])


def _na_encode(params, batch, model, vocab, quant):
    enc = params["encoder"]
    h = model["hidden_size"]
    levels = model["measurements_per_dep_graph_level"]
    G = len(levels)
    B, L = batch["event_mask"].shape
    m = batch["event_mask"]
    table = enc["input_layer"]["data_embedding_layer"]["embed_table"]
    meas = batch["dynamic_measurement_indices"]
    slots = []
    for level in levels:
        in_level = jnp.zeros(meas.shape, bool)
        for name in level:
            in_level = in_level | (meas == vocab["measurements_idxmap"][name])
        w = jnp.where(batch["dynamic_values_mask"] & in_level, batch["dynamic_values"], 1.0)
        slots.append(_bag(table, batch["dynamic_indices"], w))
    x = jnp.stack(slots, axis=2)  # (B, L, G, h)
    x = jnp.where(m[:, :, None, None], x, 0.0)
    if batch.get("static_indices") is not None:
        st = _bag(table, batch["static_indices"], jnp.ones(batch["static_indices"].shape))
        x = jnp.where(m[:, :, None, None], 0.5 * x + 0.5 * st[:, None, None], 0.0)
    x = x.at[:, :, 0, :].add(_time_encoding(batch, h))
    x = _held(jnp.where(m[:, :, None, None], jnp.cumsum(x, axis=2), 0.0), quant)

    # Dependency-graph positions: 0 is the history, 1..G the event's levels;
    # level g (query g) sees the history and the levels up to itself.
    dep_allowed = jnp.arange(G + 1)[None, :] <= (jnp.arange(G)[:, None] + 1)

    def layer(x, p, allowed):
        p = p["block"]
        per_event = jnp.where(m[..., None], x[:, :, -1, :], 0.0)
        ctx = _attention(
            _layer_norm(per_event, p["seq_attn"]["layer_norm"]), p["seq_attn"]["attention"], model, allowed, quant
        )
        ctx = jnp.where(m[..., None], ctx, 0.0)
        hist = jnp.concatenate([jnp.zeros_like(ctx[:, :1]), ctx[:, :-1]], axis=1)
        if batch.get("segment_ids") is not None:
            hist = jnp.where(_segment_starts(batch["segment_ids"])[..., None], 0.0, hist)
        g = jnp.concatenate([hist[:, :, None, :], x], axis=2)  # (B, L, G+1, h)
        g = g.at[:, :, -1, :].set(ctx)
        d = p["dep_graph_block"]
        y = g[:, :, 1:, :] + _attention(
            _layer_norm(g, d["attn"]["layer_norm"]), d["attn"]["attention"], model, dep_allowed, quant, q_from=1
        )
        y = y + _mlp(_layer_norm(y, d["layer_norm"]), d["mlp"], quant)
        return _held(jnp.where(m[:, :, None, None], y, 0.0), quant)

    for i in range(model["num_hidden_layers"]):
        x = jax.checkpoint(layer)(x, enc[f"h{i}"], _seq_allowed(batch, i, model))
    return _layer_norm(x, enc["ln_f"])


# ------------------------------------------------------------------------ loss
def _log_sigmoid_bce(logits, labels):
    """-log p(labels) of independent Bernoullis given logits."""
    return -(labels * jax.nn.log_sigmoid(logits) + (1 - labels) * jax.nn.log_sigmoid(-logits))


def _row_mean(x, mask):
    """Per-row mean of ``x`` over ``mask``; 0 and ``False`` for an empty row."""
    cnt = mask.sum(-1)
    return jnp.where(cnt > 0, (x * mask).sum(-1) / jnp.maximum(cnt, 1), 0.0), cnt > 0


def head_row_masks(batch, vocab) -> dict:
    """For each loss term the boolean (B, L) mask of the events it averages
    over. Data only: the caller derives each term's count of non-empty rows
    from the whole batch before the model runs on blocks of rows."""
    em = batch["event_mask"]
    meas = batch["dynamic_measurement_indices"]
    idx = vocab["measurements_idxmap"]
    out = {}
    for name in vocab["single_label_classification"]:
        out[f"cls.{name}"] = em & (meas == idx[name]).any(-1)
    for name in vocab["multi_label_classification"]:
        out[f"cls.{name}"] = em
    for name in vocab["multivariate_regression"]:
        out[f"reg.{name}"] = em & ((meas == idx[name]) & batch["dynamic_values_mask"]).any(-1)
    tte = em[:, 1:] & em[:, :-1]
    if batch.get("segment_ids") is not None:
        tte = tte & (batch["segment_ids"][:, 1:] == batch["segment_ids"][:, :-1])
    out["tte"] = jnp.concatenate([tte, jnp.zeros_like(tte[:, :1])], axis=1)
    return out


def term_weights(batch, vocab) -> dict:
    """1 / (rows the term averages over): non-empty rows for the content
    heads, every row for the time-to-event term."""
    out = {}
    for name, mask in head_row_masks(batch, vocab).items():
        if name == "tte":
            out[name] = 1.0 / mask.shape[0]
        else:
            out[name] = 1.0 / jnp.maximum(mask.any(-1).sum(), 1)
    return out


def _content_losses(params, batch, enc_for, vocab, quant, masks) -> dict:
    """Per-row losses of the content heads; ``enc_for(name)`` gives the
    (B, L, h) encoding that predicts measurement ``name``."""
    out_p = params["output_layer"]
    idxmap, offs, sizes = vocab["measurements_idxmap"], vocab["vocab_offsets"], vocab["vocab_sizes"]
    meas, di = batch["dynamic_measurement_indices"], batch["dynamic_indices"]
    rows = {}
    for name in vocab["single_label_classification"]:
        e = enc_for(name)
        lo, n = offs[name], sizes[name]
        is_m = meas == idxmap[name]
        has = is_m.any(-1)
        obs_logit = _dense(e, out_p["IsObservedLayer"], quant)[..., idxmap[name] - 1]
        logits = _dense(e, out_p["ClassificationLayer"], quant)[..., lo : lo + n]
        label = ((di * is_m).sum(-1) - lo) * has
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), label[..., None], axis=-1)[..., 0]
        rows[f"cls.{name}"] = _row_mean(nll + _log_sigmoid_bce(obs_logit, has), masks[f"cls.{name}"])[0]
    for name in vocab["multi_label_classification"]:
        e = enc_for(name)
        lo, n = offs[name], sizes[name]
        logits = _dense(e, out_p["ClassificationLayer"], quant)[..., lo : lo + n]
        local = jnp.where(meas == idxmap[name], di - lo, -1)
        labels = (local[..., :, None] == jnp.arange(n)).any(-2).astype(jnp.float32)
        rows[f"cls.{name}"] = _row_mean(_log_sigmoid_bce(logits, labels).mean(-1), masks[f"cls.{name}"])[0]
    for name in vocab["multivariate_regression"]:
        e = enc_for(name)
        lo = offs[name]
        z = _dense(e, out_p[f"regression_layer_{name}"]["proj"], quant)
        sel = (meas == idxmap[name]) & batch["dynamic_values_mask"]
        target = jnp.where(sel, di - lo, 0)
        mean = jnp.take_along_axis(z, 2 * target, axis=-1)
        raw = jnp.take_along_axis(z, 2 * target + 1, axis=-1)
        std = jax.nn.elu(raw) + 1.0 + jnp.finfo(jnp.float32).tiny
        val = jnp.where(sel, batch["dynamic_values"], 0.0)
        nll = (val - mean) ** 2 / (2 * std**2) + jnp.log(std) + 0.5 * math.log(2 * math.pi)
        per_event = _row_mean(nll, sel)[0]
        rows[f"reg.{name}"] = _row_mean(per_event, masks[f"reg.{name}"])[0]
    return rows


def _tte_row_ll(params, batch, enc, model, quant, mask):
    z = _dense(enc, params["output_layer"]["TTE_layer"]["proj"], quant)
    loc, log_scale, log_w = z[..., 0::3], z[..., 1::3], z[..., 2::3]
    t = jnp.where(mask, batch["time_delta"], 1.0)
    t = jnp.maximum(t, jnp.finfo(jnp.float32).tiny)
    mean_log, std_log = model["mean_log_inter_event_time"], model["std_log_inter_event_time"]
    y = ((jnp.log(t) - mean_log) / std_log)[..., None]
    comp = -((y - loc) ** 2) / (2 * jnp.exp(2 * log_scale)) - log_scale - 0.5 * math.log(2 * math.pi)
    ll = jax.nn.logsumexp(jax.nn.log_softmax(log_w, -1) + comp, axis=-1) - jnp.log(t) - math.log(std_log)
    return (ll * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1)


def rows_loss(params, batch, model, vocab, weights, quant: Callable | None = None):
    """The share of the batch loss that the rows of ``batch`` contribute:
    summed over blocks of rows it is the loss of the whole batch.
    ``weights`` is `term_weights` of the WHOLE batch."""
    p = params["params"]
    masks = head_row_masks(batch, vocab)
    if model["mode"] == "ci":
        enc = _ci_encode(p, batch, model, quant)
        prev = jnp.concatenate([jnp.zeros_like(enc[:, :1]), enc[:, :-1]], axis=1)
        if batch.get("segment_ids") is not None:
            prev = jnp.where(_segment_starts(batch["segment_ids"])[..., None], 0.0, prev)
        enc_for = lambda name: prev  # noqa: E731
        whole = enc
    else:
        enc = _na_encode(p, batch, model, vocab, quant)
        level_of = {
            name: g for g, level in enumerate(model["measurements_per_dep_graph_level"]) for name in level
        }
        enc_for = lambda name: enc[:, :, level_of[name] - 1, :]  # noqa: E731
        whole = enc[:, :, -1, :]
    rows = _content_losses(p, batch, enc_for, vocab, quant, masks)
    total = sum((rows[name] * weights[name]).sum() for name in rows)
    tte = _tte_row_ll(p, batch, whole, model, quant, masks["tte"])
    return total - (tte * weights["tte"]).sum()


def batch_loss_and_grad(params, batch, model, vocab, rows_per_block: int, quant=None):
    """Loss and gradient of one batch, accumulated over blocks of rows so the
    float32 activations fit beside the parameters."""
    B = batch["event_mask"].shape[0]
    if B % rows_per_block:
        raise ValueError(f"{B} rows do not split into blocks of {rows_per_block}")
    weights = term_weights(batch, vocab)
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


# ------------------------------------------------------------------- optimizer
def learning_rate(step, opt: dict):
    """Linear warm-up from 0, then polynomial decay to ``end_lr``
    (HuggingFace ``get_polynomial_decay_schedule_with_warmup``)."""
    step = jnp.asarray(step, jnp.float32)
    warm, total = opt["lr_num_warmup_steps"], opt["max_training_steps"]
    up = opt["init_lr"] * step / max(warm, 1)
    left = 1.0 - (step - warm) / max(total - warm, 1)
    down = (opt["init_lr"] - opt["end_lr"]) * left ** opt["lr_decay_power"] + opt["end_lr"]
    return jnp.where(step >= total, opt["end_lr"], jnp.where(step < warm, up, down))


def adamw_step(params, mu, nu, grads, count, opt: dict):
    """One AdamW update (decoupled weight decay on every leaf); ``count`` is
    the number of updates already made."""
    t = count + 1
    lr = learning_rate(count, opt)
    mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)

    def upd(p, m, v):
        m_hat = m / (1 - B1**t)
        v_hat = v / (1 - B2**t)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + opt["weight_decay"] * p)

    return jax.tree_util.tree_map(upd, params, mu, nu), mu, nu


def train_steps(params, batches: list, model, vocab, opt, rows_per_block: int, quant=None):
    """Follows ``len(batches)`` optimizer steps from fresh AdamW state.
    Returns the losses, the final parameters and the final first moment."""
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    step = jax.jit(
        lambda p, m, v, b, c: _one_step(p, m, v, b, c, model, vocab, opt, rows_per_block, quant),
        donate_argnums=(0, 1, 2),
    )
    for count, batch in enumerate(batches):
        params, mu, nu, loss = step(params, mu, nu, batch, count)
        losses.append(loss)
    return losses, params, mu


def _one_step(params, mu, nu, batch, count, model, vocab, opt, rows_per_block, quant):
    loss, grads = batch_loss_and_grad(params, batch, model, vocab, rows_per_block, quant)
    params, mu, nu = adamw_step(params, mu, nu, grads, count, opt)
    return params, mu, nu, loss


# --------------------------------------------------------------------- control
def bf16_operand(x):
    """``x`` as bfloat16 holds it (straight-through): the reference computed
    in the precision the configurations state. A second witness, never the
    reference proper: where it reads what the program reads against the
    float32 reference, the gap is bfloat16's and not the program's."""
    return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def fp8_operand(x):
    """``x`` as fp8 (e4m3, per-tensor scaled) holds it: the precision one
    step below the bf16 the configurations state. The control passes it as
    ``quant``: every matmul operand and the activations between blocks go
    through it, as they go through bf16 in the program."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    seen = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    # Straight-through: the backward's matmuls run on the quantised operands,
    # the cotangents themselves are not quantised (a milder control).
    return x + jax.lax.stop_gradient(seen - x)
