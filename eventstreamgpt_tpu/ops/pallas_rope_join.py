"""Pallas TPU pass that finishes latent attention's query and key in the flash op's layout.

`models/latent_attention.py` hands `ops/pallas_flash.py` q, k and v as ``[B, S,
H * d]`` row-major, ``d = nope + rope`` a whole number of 128-lane tiles. The
projections leave all but a head's rope lanes (its last ``rope``) final: the
query's nope part as ``q_b_proj`` writes it, the key's as the product of the
key columns of ``kv_b_proj`` (zero-padded to ``d`` a head). What is left is
RoPE on the query's rope lanes and the one rotated ``k_r`` written into every
head's rope lanes of the key, and XLA does neither without re-laying the whole
arrays events-minor (a head's halves are ``rope / 2`` lanes wide). So it is
one Mosaic pass, which pins its operands row-major as the flash op does:

* **In place.** A grid step takes one head's last lane tile of a block of rows,
  ``[rows, 128]``, of the query and of the key; the outputs alias the inputs
  (``input_output_aliases``), so every other tile stays as the products wrote
  it and the pass moves ``2 * rope / d`` of the two arrays.
* **The rotation inside a tile.** Rotate-half pairs lane ``i`` of the rope
  lanes with lane ``i + rope / 2``: two lane rotations of the tile and a
  select. The arithmetic is `models.latent_attention.rotate`'s: float32,
  ``a cos - b sin`` and ``b cos + a sin``, one rounding to the operands' dtype.
  Cosine and sine come as tile-wide tables (1 and 0 on the lanes that are not
  rotated, the sine signed by half), made once a call by XLA's own ``cos`` and
  ``sin`` from the positions and fetched once a block of rows for all heads.
* **The transpose** is the same pass with the sine negated on ``dquery``;
  ``dk_r`` is the rotated sum of ``dkey``'s rope lanes over the heads, summed
  in float32, and those lanes of ``dkey`` are then zeroed (the forward did not
  read them), again in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.scopes import scope
from .impl_select import LANE as LANES
from .rope import rope_cos_sin

__all__ = ["rope_join", "rope_join_applies", "rope_tables"]

# Rows a grid step takes. On a v5e at [16,384, 20 * 256] bf16, forward / transpose in ms: 256 rows 0.97 / 1.02,
# 512 0.75 / 0.77, 1,024 0.64 / 0.65, 2,048 0.60 / 0.60, 4,096 0.59 / 0.59 (PERF.md section 6, PR 31).
ROWS = 2048


def rope_join_applies(nope: int, rope: int, v_head_dim: int) -> bool:
    """Whether the pass can finish heads of these widths: a head is whole lane
    tiles, its rope lanes lie in the last one and pair up, and the value is as
    wide as the key (the flash op's condition)."""
    return (nope + rope) % LANES == 0 and rope % 2 == 0 and 0 < rope <= LANES and v_head_dim == nope + rope


def rope_tables(positions, rope: int, theta: float, scaling: dict | None = None):
    """``(cos, sin)`` float32 ``[B, S, 128]`` for a head's last lane tile:
    `models.latent_attention.rotate`'s angles (its frequencies and, under a
    ``yarn`` ``scaling``, its table scale) on the last ``rope`` lanes, the sine
    negated on their first half; 1 and 0 on the lanes before them."""
    cos, sin = rope_cos_sin(positions, rope, theta, scaling)  # (B, S, rope / 2)
    lead = positions.shape + (LANES - rope,)
    return (
        jnp.concatenate([jnp.ones(lead, jnp.float32), cos, cos], axis=-1),
        jnp.concatenate([jnp.zeros(lead, jnp.float32), -sin, sin], axis=-1),
    )


def _rope_lanes(shape, rope):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) >= LANES - rope


def _turn(x, cos, sin, rope):
    """The tile ``x`` [rows, 128] float32 with each rope lane's ``x cos +
    partner sin`` (``sin`` signed by half): lane ``i`` of the first half pairs
    with ``i + rope / 2``, of the second with ``i - rope / 2``."""
    half = rope // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    partner = jnp.where(lane < LANES - half, pltpu.roll(x, LANES - half, 1), pltpu.roll(x, half, 1))
    return x * cos + partner * sin


def _fwd_kernel(cos_ref, sin_ref, kr_ref, q_ref, k_ref, query_ref, key_ref, kr_turned_ref, *, rope):
    rope_lanes = _rope_lanes(q_ref.shape, rope)
    cos, sin = cos_ref[...], sin_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _():  # the shared key part, rotated once a block of rows for all heads
        kr_turned_ref[...] = _turn(kr_ref[...].astype(jnp.float32), cos, sin, rope).astype(kr_turned_ref.dtype)

    q = q_ref[...]
    query_ref[...] = jnp.where(rope_lanes, _turn(q.astype(jnp.float32), cos, sin, rope).astype(q.dtype), q)
    key_ref[...] = jnp.where(rope_lanes, kr_turned_ref[...], k_ref[...])


def _bwd_kernel(cos_ref, sin_ref, dquery_ref, dkey_ref, dq_ref, dk_ref, dkr_ref, sum_ref, *, rope):
    head, heads = pl.program_id(1), pl.num_programs(1)
    rope_lanes = _rope_lanes(dq_ref.shape, rope)
    cos, sin = cos_ref[...], -sin_ref[...]
    dquery = dquery_ref[...]
    dq_ref[...] = jnp.where(rope_lanes, _turn(dquery.astype(jnp.float32), cos, sin, rope).astype(dquery.dtype), dquery)

    @pl.when(head == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    dkey = dkey_ref[...]
    sum_ref[...] += dkey.astype(jnp.float32)
    dk_ref[...] = jnp.where(rope_lanes, jnp.zeros_like(dkey), dkey)

    @pl.when(head == heads - 1)
    def _():  # only the rope lanes are read back
        dkr_ref[...] = _turn(sum_ref[...], cos, sin, rope).astype(dkr_ref.dtype)


def _call(kernel, name, like, heads, shared_ins, shared_outs, scratch_dtype, interpret):
    """One pass in place over the heads' last tiles of two arrays ``like``
    ``[n_rows, heads * d]``: grid ``(row block, head)``. Operands:
    ``shared_ins`` ``[n_rows, 128]`` arrays whose block a row block's heads
    share (fetched once), then the two; outputs: the two, aliased, then
    ``shared_outs`` ``[n_rows, 128]`` arrays; one ``[rows, 128]`` scratch."""
    n_rows, total = like.shape
    rows = next(r for r in range(min(ROWS, n_rows), 0, -1) if n_rows % r == 0 and (r % 16 == 0 or r == n_rows))
    tiles = total // heads // LANES
    shared = pl.BlockSpec((rows, LANES), lambda i, h: (i, 0))
    of_head = pl.BlockSpec((rows, LANES), lambda i, h: (i, h * tiles + tiles - 1))
    return pl.pallas_call(
        kernel,
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
    k_r = jnp.pad(_flat(k_r), ((0, 0), (LANES - rope, 0)))  # at the lanes it takes in a head's last tile
    call = _call(functools.partial(_fwd_kernel, rope=rope), "rope_join", like, heads, 3, 0, q.dtype, interpret)
    query, key = call(_flat(cos), _flat(sin), k_r, _flat(q), _flat(k))
    return query.reshape(q.shape), key.reshape(q.shape)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(cos, sin, dquery, dkey, heads, rope, interpret):
    like = jax.ShapeDtypeStruct(_flat(dquery).shape, dquery.dtype)
    call = _call(
        functools.partial(_bwd_kernel, rope=rope), "rope_join_transpose", like, heads, 2, 1, jnp.float32, interpret
    )
    dq, dk, dk_r = call(_flat(cos), _flat(sin), _flat(dquery), _flat(dkey))
    return dq.reshape(dquery.shape), dk.reshape(dquery.shape), dk_r[:, LANES - rope :].reshape(*dquery.shape[:2], rope)


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
        cos, sin = rope_tables(positions, rope, theta, scaling)
    return _rope_join(q, k_nope, k_r, cos, sin, heads, rope, interpret)
