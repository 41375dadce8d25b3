"""Pallas TPU pass that finishes latent attention's query and key in the flash op's layout.

`models/latent_attention.py` hands `ops/pallas_flash.py` q and k as ``[B, S,
H * d]`` row-major, ``d = nope + rope``, and v as ``[B, S, H * v]``, whatever
the value's width. The projections leave all but a head's rope lanes (its last
``rope``) final: the query's nope part as ``q_b_proj`` writes it, the key's as
the product of the key columns of ``kv_b_proj`` (zero-padded to ``d`` a head).
What is left is RoPE on the query's rope lanes and the one rotated ``k_r``
written into every head's rope lanes of the key, and XLA does neither without
re-laying the whole arrays events-minor (a head's halves are ``rope / 2``
lanes wide). So it is one Mosaic pass, which pins its operands row-major as
the flash op does:

* **Where a head's rope lanes are.** Head ``h`` has them at lanes ``[(h d +
  nope) mod 128, ... + rope)`` of tile ``(h d + nope) div 128``: a span inside
  one 128-lane tile, at any offset of it (`rope_join_applies`). GLM-4.7-Flash's
  192 + 64 puts it at lanes 64-127 of every head's second tile; Xing4.0's
  128 + 64 at lanes 0-63 of tile ``3 j + 1`` for head ``2 j`` and at lanes
  64-127 of tile ``3 j + 2`` for head ``2 j + 1`` (two heads are three tiles).
* **In place.** A grid step takes that one tile of a block of rows, ``[rows,
  128]``, of the query and of the key; the outputs alias the inputs
  (``input_output_aliases``), so every other tile stays as the products wrote
  it and the pass moves ``2 * 128 / d`` of the two arrays at most.
* **The rotation inside a tile.** Rotate-half pairs lane ``i`` of a span's
  first half with lane ``i + rope / 2``: two lane rotations of the tile and a
  select, both inside the tile wherever the span starts. The arithmetic is
  `models.latent_attention.rotate`'s: float32, ``a cos - b sin`` and ``b cos +
  a sin``, one rounding to the operands' dtype. What all heads of a block of
  rows share comes as one tile that holds it at every span the heads use
  (spans at different offsets do not overlap), fetched once a block of rows:
  the cosine and the sine signed by half (1 and 0 between the spans), made
  once a call by XLA's own ``cos`` and ``sin`` from the positions, and
  ``k_r``, rotated once a block of rows. Only the mask that says which lanes
  are this head's follows the grid.
* **The transpose** is the same pass with the sine negated on ``dquery``;
  ``dk_r`` is the rotated sum of ``dkey``'s rope lanes over the heads, summed
  in float32 where they lie and brought to one offset before the rotation, and
  those lanes of ``dkey`` are then zeroed (the forward did not read them),
  again in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.scopes import scope
from .impl_select import LANE as LANES
from .pallas_flash import lane_tile_groups
from .rope import rope_cos_sin

__all__ = ["rope_join", "rope_join_applies", "rope_tables"]

# Rows a grid step takes. On a v5e, forward / transpose in ms (back-to-back donated calls), at `glm47flash_ep8`'s
# [16,384, 20 * 256] bf16 (one offset, lanes 64-127): 512 rows 0.73 / 0.75, 1,024 0.62 / 0.62, 2,048 0.58 / 0.58,
# 4,096 0.57 / 0.57, 8,192 0.56 / 0.57, the parent's kernel with its static mask the same to 0.3% at every size; at
# `xing40_a4b_ep8`'s [8,192, 32 * 192] (offsets 0 and 64 by head parity, the mask from `program_id`): 512 rows
# 0.60 / 0.61, 1,024 0.49 / 0.50, 2,048 0.45 / 0.45, 4,096 0.44 / 0.45, 8,192 0.45 / 0.45: 268 MB a call at 600 GB/s,
# 0.73 of the HBM peak, so the mask that follows the grid costs nothing and no static variant by head parity was
# built (PERF.md section 6, PR 35; PR 31 read 0.60 / 0.60 at 2,048).
ROWS = 2048


def _span_offsets(heads: int, nope: int, rope: int) -> list[int]:
    """The lane offsets inside a tile at which the heads' rope spans start."""
    return sorted({(h * (nope + rope) + nope) % LANES for h in range(heads)})


def rope_join_applies(nope: int, rope: int, v_head_dim: int, heads: int) -> bool:
    """Whether the pass can finish ``heads`` heads of these widths: some group
    of heads is whole lane tiles (the flash op's rule, `lane_tile_groups`),
    the rope lanes pair up, and every head's rope span lies inside one tile
    (spans at different offsets then never overlap: a group's offsets lie
    ``gcd(d, 128)`` apart up to the tile's end, so one table serves all), a
    tile of its own (a grid step rewrites one head's)."""
    if rope <= 0 or rope % 2 or not lane_tile_groups(heads, nope + rope, v_head_dim):
        return False
    starts = [h * (nope + rope) + nope for h in range(heads)]
    return all(s % LANES + rope <= LANES for s in starts) and len({s // LANES for s in starts}) == heads


def _at_the_spans(pieces, offsets, fill):
    """A ``[..., 128]`` tile that holds ``pieces`` (arrays ``[..., n]``, side
    by side one span wide) from each lane of ``offsets`` on and ``fill``
    between the spans."""
    span = sum(p.shape[-1] for p in pieces)
    gaps = [b - a for a, b in zip([0, *(o + span for o in offsets)], [*offsets, LANES])]  # before each span, after the last
    out = []
    for gap, held in zip(gaps, [pieces] * len(offsets) + [[]]):
        out += [jnp.full(pieces[0].shape[:-1] + (gap,), fill, pieces[0].dtype)] * (gap > 0) + held
    return jnp.concatenate(out, axis=-1)


def rope_tables(positions, rope: int, theta: float, scaling: dict | None = None, offsets=None):
    """``(cos, sin)`` float32 ``[B, S, 128]`` for the tiles that hold the
    heads' rope lanes: `models.latent_attention.rotate`'s angles (its
    frequencies and, under a ``yarn`` ``scaling``, its table scale) on the
    ``rope`` lanes from each of ``offsets`` on (a tile's last ``rope`` where
    not given), the sine negated on a span's first half; 1 and 0 on the lanes
    between the spans."""
    cos, sin = rope_cos_sin(positions, rope, theta, scaling)  # (B, S, rope / 2)
    offsets = (LANES - rope,) if offsets is None else offsets
    return _at_the_spans([cos, cos], offsets, 1.0), _at_the_spans([-sin, sin], offsets, 0.0)


def _lanes(shape, width, nope, rope, offsets):
    """Two masks over a ``[rows, 128]`` tile: the rope lanes of this grid
    step's head, and the first halves of every span the tile can hold."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    start = jax.lax.rem(pl.program_id(1) * width + nope, LANES)
    first_halves = functools.reduce(jnp.logical_or, [(lane >= o) & (lane < o + rope // 2) for o in offsets])
    return (lane >= start) & (lane < start + rope), first_halves


def _turn(x, cos, sin, first_halves, rope):
    """The tile ``x`` [rows, 128] float32 with each span lane's ``x cos +
    partner sin`` (``sin`` signed by half): lane ``i`` of a first half pairs
    with ``i + rope / 2``, of a second with ``i - rope / 2``."""
    half = rope // 2
    return x * cos + jnp.where(first_halves, pltpu.roll(x, LANES - half, 1), pltpu.roll(x, half, 1)) * sin


def _fwd_kernel(cos_ref, sin_ref, kr_ref, q_ref, k_ref, query_ref, key_ref, kr_turned_ref, *, width, nope, rope, offsets):
    rope_lanes, first_halves = _lanes(q_ref.shape, width, nope, rope, offsets)
    turn = functools.partial(_turn, cos=cos_ref[...], sin=sin_ref[...], first_halves=first_halves, rope=rope)

    @pl.when(pl.program_id(1) == 0)
    def _():  # the shared key part, rotated once a block of rows for all heads
        kr_turned_ref[...] = turn(kr_ref[...].astype(jnp.float32)).astype(kr_turned_ref.dtype)

    q = q_ref[...]
    query_ref[...] = jnp.where(rope_lanes, turn(q.astype(jnp.float32)).astype(q.dtype), q)
    key_ref[...] = jnp.where(rope_lanes, kr_turned_ref[...], k_ref[...])


def _bwd_kernel(cos_ref, sin_ref, dquery_ref, dkey_ref, dq_ref, dk_ref, dkr_ref, sum_ref, *, width, nope, rope, offsets):
    head, heads = pl.program_id(1), pl.num_programs(1)
    rope_lanes, first_halves = _lanes(dq_ref.shape, width, nope, rope, offsets)
    turn = functools.partial(_turn, cos=cos_ref[...], sin=-sin_ref[...], first_halves=first_halves, rope=rope)
    dquery = dquery_ref[...]
    dq_ref[...] = jnp.where(rope_lanes, turn(dquery.astype(jnp.float32)).astype(dquery.dtype), dquery)

    @pl.when(head == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    dkey = dkey_ref[...]
    nothing = jnp.zeros_like(dkey)
    sum_ref[...] += jnp.where(rope_lanes, dkey, nothing).astype(jnp.float32)
    dk_ref[...] = jnp.where(rope_lanes, nothing, dkey)

    @pl.when(head == heads - 1)
    def _():  # each offset's sum brought to the first head's; only that span is read back
        sums = sum_ref[...]
        total = sum([pltpu.roll(sums, (nope - o) % LANES, 1) for o in offsets if o != nope % LANES], sums)
        dkr_ref[...] = turn(total).astype(dkr_ref.dtype)


def _call(kernel, name, like, heads, nope, rope, shared_ins, shared_outs, scratch_dtype, interpret):
    """One pass in place over the tiles that hold the heads' rope lanes, of
    two arrays ``like`` ``[n_rows, heads * (nope + rope)]``: grid ``(row
    block, head)``. Operands: ``shared_ins`` ``[n_rows, 128]`` arrays whose
    block a row block's heads share (fetched once), then the two; outputs: the
    two, aliased, then ``shared_outs`` ``[n_rows, 128]`` arrays; one ``[rows,
    128]`` scratch."""
    n_rows, width = like.shape[0], nope + rope
    rows = next(r for r in range(min(ROWS, n_rows), 0, -1) if n_rows % r == 0 and (r % 16 == 0 or r == n_rows))
    shared = pl.BlockSpec((rows, LANES), lambda i, h: (i, 0))
    of_head = pl.BlockSpec((rows, LANES), lambda i, h: (i, (h * width + nope) // LANES))
    return pl.pallas_call(
        functools.partial(kernel, width=width, nope=nope, rope=rope, offsets=_span_offsets(heads, nope, rope)),
        grid=(n_rows // rows, heads),
        in_specs=[shared] * shared_ins + [of_head] * 2,
        out_specs=[of_head] * 2 + [shared] * shared_outs,
        out_shape=[like] * 2 + [jax.ShapeDtypeStruct((n_rows, LANES), like.dtype)] * shared_outs,
        scratch_shapes=[pltpu.VMEM((rows, LANES), scratch_dtype)],
        input_output_aliases={shared_ins: 0, shared_ins + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=interpret,
        name=name,
    )


def _flat(x):
    return x.reshape(-1, x.shape[-1])


# Jitted with everything but the arrays static, as `ops/pallas_flash.py`'s
# launchers are: a model's layers and their recomputation share one trace and
# one lowering of each kernel.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(q, k, k_r, cos, sin, heads, rope, interpret):
    like = jax.ShapeDtypeStruct(_flat(q).shape, q.dtype)
    nope = like.shape[1] // heads - rope
    k_r = _at_the_spans([_flat(k_r)], _span_offsets(heads, nope, rope), 0)  # at the lanes it takes in every span
    call = _call(_fwd_kernel, "rope_join", like, heads, nope, rope, 3, 0, q.dtype, interpret)
    query, key = call(_flat(cos), _flat(sin), k_r, _flat(q), _flat(k))
    return query.reshape(q.shape), key.reshape(q.shape)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(cos, sin, dquery, dkey, heads, rope, interpret):
    like = jax.ShapeDtypeStruct(_flat(dquery).shape, dquery.dtype)
    nope = like.shape[1] // heads - rope
    call = _call(_bwd_kernel, "rope_join_transpose", like, heads, nope, rope, 2, 1, jnp.float32, interpret)
    dq, dk, dk_r = call(_flat(cos), _flat(sin), _flat(dquery), _flat(dkey))
    first = nope % LANES  # the first head's span
    return dq.reshape(dquery.shape), dk.reshape(dquery.shape), dk_r[:, first : first + rope].reshape(*dquery.shape[:2], rope)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rope_join(q, k, k_r, cos, sin, heads, rope, interpret):
    with scope("attn_latent"):
        return _forward(q, k, k_r, cos, sin, heads, rope, interpret)


# JAX traces a custom_vjp's rules without the caller's name stack, so each
# rule enters the scope itself (PERF.md section 6, PR 28).
def _rope_join_fwd(q, k, k_r, cos, sin, heads, rope, interpret):
    with scope("attn_latent"):
        return _forward(q, k, k_r, cos, sin, heads, rope, interpret), (cos, sin)


def _rope_join_bwd(heads, rope, interpret, tables, cotangents):
    with scope("attn_latent"):
        return (*_backward(*tables, *cotangents, heads, rope, interpret), None, None)


_rope_join.defvjp(_rope_join_fwd, _rope_join_bwd)


def rope_join(
    q, k_nope, k_r, positions, *, heads: int, rope: int, theta: float, scaling: dict | None = None, interpret: bool = False
):
    """Latent attention's ``(query, key)``, both ``[B, S, heads * d]``.

    Args:
        q: ``[B, S, heads * d]`` as ``q_b_proj`` leaves it: a head's last
            ``rope`` lanes are rotated, the others are final.
        k_nope: ``[B, S, heads * d]``, a head's ``d - rope`` nope lanes final;
            what its rope lanes hold is not read.
        k_r: ``[B, S, rope]``, the key part all heads share, not yet rotated.
        positions: ``[B, S]`` int32, an event's index inside its segment.
        interpret: run the kernels in Pallas' interpreter (any backend).

    ``query`` is ``q`` and ``key`` is ``k_nope`` but for the rope lanes, which
    hold ``rotate(q's)`` and ``rotate(k_r)``. Differentiable in ``q``,
    ``k_nope`` and ``k_r``; the two big operands are consumed (aliased).
    """
    with scope("attn_latent"):
        cos, sin = rope_tables(positions, rope, theta, scaling, _span_offsets(heads, q.shape[-1] // heads - rope, rope))
    return _rope_join(q, k_nope, k_r, cos, sin, heads, rope, interpret)
