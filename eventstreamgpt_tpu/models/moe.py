"""Gated and routed feed-forward layers of the kinds block (`models/blocks.py`).

`SwiGLU` is ``(silu(x W_g) * x W_u) W_d`` and `Relu2` is ``relu(x W_u)^2 W_d``,
both with no bias. `RoutedFeedForward` is the sparse layer of GLM-4.7-Flash
(``glm4_moe_lite``) and of ``nemotron_h``, ``noaux_tc`` routing, with experts
and a shared expert of either form (``config.moe_expert_form``)::

    s = sigmoid(x W_r)                              float32, over the router's whole width
    T = top_k(s + b)                                b: the selection bias; it chooses and does not weigh
    w_e = scaling * s_e / (sum_{j in T} s_j + eps)  the sum over all chosen, held here or not
    y = S(x) + sum_{e in T and held} w_e E_e(x)

``b`` (``e_score_correction_bias``) gets no gradient: it is zero at
initialisation, and the published balancing rule that would move it, ``b_e +=
u * sign(mean load - load_e)`` after every step, is not applied by anything
here (ROADMAP R3), so it stays zero. ``eps`` is ``moe_topk_norm_eps``: 0 by
default (GLM-4.7-Flash, ``nemotron_h``, Xing4.0: no term at all), 1e-6 in
LFM2's ``lfm2_moe``.

The layer is told which experts it holds: ``n_routed_experts`` of the router's
``moe_router_width``, from ``moe_expert_offset`` on. What the experts on other
chips would add is left out, and no code stands in for them or their exchange
(docs/layer_kinds.md). No dropped row, whatever the load: the (row, expert)
pairs are ordered by expert with the held ones first into a buffer for the
worst case (every row's every choice held here), and the buffer is walked in
chunks as far as the held pairs reach (a loop with a traced trip count, forward
and backward). A chunk holds as many pairs as there are rows, and inside it the
grouped products (`ops/grouped_matmul.py`) run over the held pairs' row tiles
alone, so the cost follows the pairs really routed here. Where the held pairs
expected at even load (``rows x top_k x held / router width``) with a quarter
more on top would not fit in a chunk of rows, a chunk holds that many instead,
rounded up to the grouped product's row tile, and the buffer is padded to whole
chunks (`dispatch_chunk`). A layer whose expected load is one chunk of rows
exactly (LFM2's: 8 held of 32 at top-4) would otherwise walk one chunk or two,
as the seed's router sends a little more or a little less than its share here.

Each call sows ``[pairs computed here, largest load of one held expert]``
(int32) into the ``routing`` collection; the train step returns them beside
the health vector (`training/pretrain.py`).
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import ROW_TILE, grouped_matmul
from ..ops.impl_select import resolve_impl
from ..parallel.context import per_batch_shard
from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .latent_attention import bias_free_dense

ROUTING_COLLECTION = "routing"
# How far over the pairs expected at even load a chunk of the walk reaches, where they fill a chunk of rows.
CHUNK_HEADROOM = 1.25


class SwiGLU(nn.Module):
    config: StructuredTransformerConfig
    inner: int

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(bias_free_dense, self.config)
        return dense(self.config.hidden_size, "down_proj")(nn.silu(dense(self.inner, "gate_proj")(x)) * dense(self.inner, "up_proj")(x))


class Relu2(nn.Module):
    """The ungated feed-forward ``relu(x W_u)^2 W_d``."""

    config: StructuredTransformerConfig
    inner: int

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(bias_free_dense, self.config)
        return dense(self.config.hidden_size, "down_proj")(jnp.square(nn.relu(dense(self.inner, "up_proj")(x))))


# An expert form's module, and the names of its stacks of matrices in the order
# `_chunk_output` takes them: the last is the product back to the hidden size.
EXPERT_FORMS = {"swiglu": (SwiGLU, ("gate_proj", "up_proj", "down_proj")), "relu2": (Relu2, ("up_proj", "down_proj"))}


def route(scores, bias, top_k: int, scaling: float, normalize: bool, eps: float = 0.0):
    """``(N, W)`` float32 scores -> the chosen experts ``(N, k)``, by score
    plus selection bias, and their weights, by score alone."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen_scores = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        total = jnp.sum(chosen_scores, axis=-1, keepdims=True)
        chosen_scores = chosen_scores / (total + eps if eps else total)
    return chosen, scaling * chosen_scores


def dispatch_chunk(n_rows: int, top_k: int, held: int, router_width: int | None) -> int:
    """The pairs one chunk of the walk holds: ``n_rows``, unless
    `CHUNK_HEADROOM` times the held pairs expected at even load exceeds it;
    then that many, rounded up to `ROW_TILE` and at most the whole buffer.
    ``router_width`` None: ``n_rows``."""
    if router_width is None:
        return n_rows
    want = math.ceil(CHUNK_HEADROOM * n_rows * top_k * held / router_width)
    if want <= n_rows:
        return n_rows
    return min(-(-want // ROW_TILE) * ROW_TILE, n_rows * top_k)


def _chunk_output(rows, chunk_rows, chunk_weights, group_sizes, stacks, impl):
    """What one chunk of ordered pairs adds to every row's output (float32).
    ``stacks``: the held experts' matrices, three stacks for the gated form
    (gate, up, down: ``silu(a) * b``), two for the ungated (up, down:
    ``relu(a)^2``)."""
    with scope("moe_dispatch"):
        x = rows[chunk_rows]
    with scope("moe_experts"):
        *w_in, w_down = stacks
        into = [grouped_matmul(x, w, group_sizes, impl) for w in w_in]
        hidden = nn.silu(into[0]) * into[1] if len(into) == 2 else jnp.square(nn.relu(into[0]))
        y = grouped_matmul(hidden, w_down, group_sizes, impl)
    with scope("moe_dispatch"):
        # A pair past the held ones has a zero row of `y`, whatever its weight.
        y = y.astype(jnp.float32) * chunk_weights[:, None]
        return jnp.zeros(rows.shape, jnp.float32).at[chunk_rows].add(y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _walk_chunks(rows, pair_rows, pair_weights, chunk_sizes, n_chunks, stacks, impl):
    """The sum of `_chunk_output` over the first ``n_chunks`` chunks (a traced
    count: a loop whose trip count follows the pairs really held). The
    backward walks the same chunks and computes each one's forward again, so
    nothing the size of the buffer is kept or zero-filled."""

    def body(c, out):
        chunk = _chunk_output(rows, pair_rows[c], pair_weights[c], chunk_sizes[c], stacks, impl)
        with scope("moe_dispatch"):
            return out + chunk

    with scope("moe_dispatch"):
        return jax.lax.fori_loop(0, n_chunks, body, jnp.zeros(rows.shape, jnp.float32))


def _walk_chunks_fwd(rows, pair_rows, pair_weights, chunk_sizes, n_chunks, stacks, impl):
    out = _walk_chunks(rows, pair_rows, pair_weights, chunk_sizes, n_chunks, stacks, impl)
    return out, (rows, pair_rows, pair_weights, chunk_sizes, n_chunks, stacks)


def _walk_chunks_bwd(impl, residuals, g):
    rows, pair_rows, pair_weights, chunk_sizes, n_chunks, stacks = residuals

    def body(c, carry):
        d_rows, d_pair_weights, d_stacks = carry
        _, pull = jax.vjp(
            lambda r, pw, w: _chunk_output(r, pair_rows[c], pw, chunk_sizes[c], w, impl),
            rows, pair_weights[c], stacks,
        )
        r, pw, w = pull(g)
        with scope("moe_dispatch"):
            d_rows, d_pair_weights = d_rows + r, d_pair_weights.at[c].set(pw)
        with scope("moe_experts"):
            return d_rows, d_pair_weights, tuple(acc + d for acc, d in zip(d_stacks, w))

    # (A custom VJP's rules are traced without the caller's name stack: the
    # scopes are set here again.)
    with scope("moe_dispatch"):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, (rows, pair_weights, stacks))
        d_rows, d_pair_weights, d_stacks = jax.lax.fori_loop(0, n_chunks, body, zeros)
    return d_rows, None, d_pair_weights, None, None, d_stacks


_walk_chunks.defvjp(_walk_chunks_fwd, _walk_chunks_bwd)


def held_experts_output(rows, chosen, weights, *stacks, offset: int, impl=None, router_width: int | None = None):
    """``sum_{e chosen and held} w_e E_e(row)`` for every row, in float32, and
    the two routing counters as a ``(1, 2)`` row. ``stacks`` are ``(held,
    ...)`` stacks of the held experts' matrices in `EXPERT_FORMS`' order (three
    for the gated form, two for the ungated), expert ``offset + i`` at index
    ``i``; a row whose ``chosen`` is negative is routed nowhere. The walk's
    chunk is `dispatch_chunk`'s for ``router_width``."""
    n_rows, top_k = chosen.shape
    held = stacks[0].shape[0]
    chunk = dispatch_chunk(n_rows, top_k, held, router_width)
    n_buffer = -(-n_rows * top_k // chunk)
    with scope("moe_dispatch"):
        # Slot 0..held-1 are the experts held here; anything above lives on
        # another chip. (The router's width only bounds the ids.)
        slot = jnp.where((chosen >= offset) & (chosen < offset + held), chosen - offset, held).reshape(-1)
        order = jnp.argsort(slot, stable=True)  # pairs by slot: the held ones first
        sizes = jnp.sum(slot[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        n_pairs = ends[-1]

        def in_chunks(a):
            """Whole chunks: a padding pair lies past the held ones, so it adds a zero row."""
            pad = n_buffer * chunk - a.size
            return (jnp.pad(a, (0, pad)) if pad else a).reshape(n_buffer, chunk)

        pair_rows = in_chunks((order // top_k).astype(jnp.int32))
        pair_weights = in_chunks(weights.reshape(-1)[order])
        # Each chunk's own group sizes: the part of every held group inside it.
        lo = jnp.arange(n_buffer, dtype=jnp.int32)[:, None] * chunk
        clip = lambda a: jnp.clip(a[None, :], lo, lo + chunk)  # noqa: E731
        chunk_sizes = clip(ends) - clip(ends - sizes)
        chunk_sizes = jnp.concatenate([chunk_sizes, chunk - chunk_sizes.sum(-1, keepdims=True)], axis=-1)
        n_chunks = (n_pairs + chunk - 1) // chunk
    out = _walk_chunks(
        rows, pair_rows, pair_weights, chunk_sizes, n_chunks, tuple(stacks), resolve_impl(impl, "grouped_matmul")
    )
    return out, jnp.stack([n_pairs, jnp.max(sizes)])[None]


class RoutedFeedForward(nn.Module):
    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, x, row_mask=None):
        """``row_mask`` (the shape of ``x`` less its last axis): rows that hold
        no event are routed nowhere (the block zeroes them anyway)."""
        cfg = self.config
        dt = cfg.compute_dtype
        hidden, inner, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
        init = nn.initializers.normal(stddev=cfg.init_std)
        rows = x.reshape(-1, hidden)

        with scope("moe_router"):
            router = self.param("router", init, (hidden, cfg.moe_router_width), jnp.float32)
            bias = self.param("e_score_correction_bias", nn.initializers.zeros, (cfg.moe_router_width,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(rows.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
            chosen, weights = route(
                scores, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob,
                cfg.moe_topk_norm_eps,
            )
            if row_mask is not None:
                chosen = jnp.where(row_mask.reshape(-1, 1), chosen, -1)

        shared_cls, names = EXPERT_FORMS[cfg.moe_expert_form]
        with scope("moe_experts"):
            stacks = tuple(
                self.param(
                    f"experts_{name}", init, (held, inner, hidden) if name == "down_proj" else (held, hidden, inner),
                    jnp.float32,
                ).astype(dt)
                for name in names
            )
        # Each batch shard of a data-parallel mesh routes its own rows to its
        # own copy of the held experts (parallel/context.py).
        routed, counters = per_batch_shard(
            functools.partial(held_experts_output, offset=cfg.moe_expert_offset, router_width=cfg.moe_router_width),
            rows.astype(dt), chosen, weights,
            replicated=stacks,
        )
        if not self.is_initializing():  # `init` gives parameters alone
            counters = jnp.stack([counters[:, 0].sum(), counters[:, 1].max()])
            self.sow(ROUTING_COLLECTION, "counters", counters)
        with scope("moe_dispatch"):
            out = routed.astype(dt).reshape(x.shape)
        if cfg.n_shared_experts:
            with scope("moe_shared"):
                width = cfg.moe_shared_expert_intermediate_size or cfg.n_shared_experts * inner
                out = out + shared_cls(cfg, width, name="shared_experts")(x)
        return out


def routing_counters(collection) -> jax.Array:
    """One int32 ``[pairs computed here, largest load of one held expert]`` of
    a step, from what every routed layer sowed: the pairs summed over the
    layers, the load's maximum over them. Zeros where no layer routes."""
    sown = jax.tree_util.tree_leaves(collection)
    if not sown:
        return jnp.zeros(2, jnp.int32)
    sown = jnp.stack(sown)
    return jnp.stack([sown[:, 0].sum(), sown[:, 1].max()]).astype(jnp.int32)
