"""Pallas TPU flash attention for the global layers: causal, inside a segment.

The core under ``es.attn_global`` (`models/transformer.py`'s ``use_pallas``
branch, `models/latent_attention.py`): causal self-attention with
``sm_scale`` given, one int32 segment id an event (padding rides as segment
``-1``), q and kv lengths equal, a row a whole number of 128-event chunks.
Float32 logits, masks and softmax statistics; products in the operands' dtype
with float32 accumulation. A query sees exactly the keys with
``seg[k] == seg[q] and k <= q``, a padded query its padded predecessors.

Three kernels under one ``custom_vjp`` (forward, dkv, dq), written for what
this system sends them:

* **Compact statistics.** The forward saves one float32 log-sum-exp a query
  as lane-dense rows ``[B, H, S / chunk_q, chunk_q]``; ``di = sum(o * do)`` is
  one fused multiply-reduce in the same shape. The kernels hold logits as
  ``[keys, queries]``, so a chunk's row of statistics is used as it is
  stored; nothing is broadcast outside.
* **Visiting by segment.** `chunk_bounds` gives, per row and query chunk,
  the first and last key chunk that may hold a visible pair (and the same
  table read the other way for the dkv kernel), from the chunks' segment-id
  ranges and the diagonal. The bounds are scalar-prefetch operands; a grid
  step holds whole rows of a group of heads (q, k, v resident in VMEM) and
  walks the chunks between the bounds in a ``fori_loop``, so skipping costs
  no grid step. The mask inside a chunk pair stays the exact one, so the bounds need
  only be a superset: any segment layout is right, contiguous ascending ids
  skip most.
* **The projections' layout.** q, k, v and o are ``[B, S, H * d]``, a group
  of heads being a lane block and a head a slice of it: no transpose around
  the call.

`flash_block_sizes` chooses rows and heads a step and the two chunk widths
from static shapes; `visited_pairs` counts, row by row, the chunk pairs the
forward walks beside the dense ones (`visited_share`, their ratio).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.scopes import scope

__all__ = ["FlashSizes", "chunk_bounds", "flash_attention", "flash_block_sizes", "lane_tile_groups", "visited_pairs", "visited_share"]

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128
PAD_SEGMENT = -1
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


class FlashSizes(NamedTuple):
    rows: int  # batch rows one grid step holds
    heads: int  # heads of those rows one grid step holds
    chunk_q: int  # queries one step of the walk takes
    chunk_k: int  # keys one step of the walk takes


def lane_tile_groups(num_heads: int, head_dim: int, value_dim: int | None = None) -> list[int]:
    """The numbers of heads a grid step can take side by side as lane blocks
    of whole 128-lane tiles, at the key width and at the value width: the
    divisors ``g`` of the heads with ``g * head_dim`` and ``g * value_dim``
    multiples of 128 (256 / 256 and 128 / 128 from one head on; 192 / 128 from
    two). `flash_block_sizes` picks among them, and the kinds block's core
    hands the op what has any (`latent_attention.causal_core`)."""
    value_dim = value_dim or head_dim
    return [
        g for g in range(1, num_heads + 1)
        if num_heads % g == 0 and g * head_dim % LANES == 0 and g * value_dim % LANES == 0
    ]


def flash_block_sizes(
    batch: int, seq_len: int, num_heads: int, head_dim: int, itemsize: int = 2, value_dim: int | None = None
) -> FlashSizes:
    """Rows and heads a grid step and the walk's chunk widths, from static
    shapes: ``head_dim`` the queries' and keys' width, ``value_dim`` the
    values' and the output's where it differs (the wider of the two decides).

    Swept on a v5e with `scripts/probe_flash_blocks.py` at the benchmark
    cells' shapes (PERF.md section 6, PR 29):

    * a grid step holds ``rows`` whole rows, so that short rows (the padded
      cell's 256 events) still give it about 1,024 events of work, and as
      many heads of them as a 5 MiB block an operand allows: every head of a
      row walks the same chunk pairs, so the heads' chains (product, softmax,
      product) are independent work for the scheduler inside one step of the
      walk, whose trip count is data and which therefore overlaps nothing
      with its next step. One head a step is 1.8 times slower than eight at
      8 heads of 128, 1.5 times slower than ten at 20 heads of 256;
    * chunks of 256 x 256 up to a head width of 128 (a wider pair fills the
      matrix unit better than a narrower one skips: 128 x 128 visits 0.30 of
      the dense pairs of a packed row against 0.45 and is 2% slower), 128 x
      128 above it, where a 256-wide pair's accumulators no longer fit the
      registers (9% faster than 256 x 256 at width 256).
    """
    value_dim = value_dim or head_dim
    widest = max(head_dim, value_dim)
    chunk = next((c for c in ((256, 128) if widest <= 128 else (128,)) if seq_len % c == 0), seq_len)
    want = max(1, 1024 // seq_len)
    rows = max(r for r in range(1, want + 1) if batch % r == 0)
    # a grid step's lane blocks: whole heads, whole 128-lane tiles (or the array's whole width)
    groups = sorted({*lane_tile_groups(num_heads, head_dim, value_dim), num_heads})
    fits = [g for g in groups if rows * seq_len * g * widest * itemsize <= 5 * 2**20]
    return FlashSizes(rows, max(fits, default=groups[0]), chunk, chunk)


def chunk_bounds(segment_ids, chunk_q: int, chunk_k: int):
    """``(k_lo, k_hi, q_lo, q_hi)``: per query chunk the first and last key
    chunk to visit (``[..., S / chunk_q]``), per key chunk the first and last
    query chunk (``[..., S / chunk_k]``), inclusive.

    Chunk pair ``(i, j)`` may hold a visible pair iff ``j``'s first key is
    not after ``i``'s last query and the two chunks' ranges of segment ids
    overlap (padding counted as the largest id, where the packed rows put
    it); the bounds are the first and last such chunk. Works on numpy and on
    jax arrays alike.
    """
    xp = jnp if isinstance(segment_ids, jax.Array) else np
    seq_len = segment_ids.shape[-1]
    lead = segment_ids.shape[:-1]
    n_q, n_k = seq_len // chunk_q, seq_len // chunk_k
    ids = xp.where(segment_ids == PAD_SEGMENT, np.iinfo(np.int32).max, segment_ids)
    q = ids.reshape(lead + (n_q, chunk_q))
    k = ids.reshape(lead + (n_k, chunk_k))
    q_min, q_max = q.min(-1)[..., :, None], q.max(-1)[..., :, None]
    k_min, k_max = k.min(-1)[..., None, :], k.max(-1)[..., None, :]
    diagonal = (xp.arange(n_k) * chunk_k)[None, :] <= (xp.arange(n_q) * chunk_q + chunk_q - 1)[:, None]
    may = diagonal & (k_min <= q_max) & (q_min <= k_max)  # [..., n_q, n_k]
    k_lo = may.argmax(-1)
    k_hi = n_k - 1 - may[..., :, ::-1].argmax(-1)
    q_lo = may.argmax(-2)
    q_hi = n_q - 1 - may[..., ::-1, :].argmax(-2)
    return tuple(b.astype(np.int32) for b in (k_lo, k_hi, q_lo, q_hi))


def visited_pairs(segment_ids, chunk_q: int, chunk_k: int | None = None) -> tuple[np.ndarray, int]:
    """``(visited, dense)``: per row (``segment_ids.shape[:-1]``) the
    ``chunk_q x chunk_k`` pairs the forward (and the dq kernel) walks on a row
    with these segment ids (padding as ``-1``), and the pairs a dense walk of
    one row would."""
    chunk_k = chunk_k or chunk_q
    k_lo, k_hi, _, _ = chunk_bounds(segment_ids, chunk_q, chunk_k)
    return (k_hi - k_lo + 1).sum(-1), k_lo.shape[-1] * (segment_ids.shape[-1] // chunk_k)


def visited_share(segment_ids, chunk_q: int, chunk_k: int | None = None) -> float:
    """`visited_pairs` of all the rows as a share."""
    visited, dense = visited_pairs(segment_ids, chunk_q, chunk_k)
    return float(visited.sum()) / (visited.size * dense)


# ---------------------------------------------------------------- the kernels
# All three hold a chunk pair's logits as [keys, queries]: keys down the
# sublanes, queries along the lanes. A query's statistics (running maximum and
# sum, log-sum-exp, di) are then rows, read and written as they are stored and
# broadcast down the sublanes for nothing, and a softmax's reductions run over
# sublanes (elementwise between registers) instead of across lanes. (A first
# version with queries down the sublanes, its statistics turned into columns
# through a diagonal mask, measured the same a layer: PERF.md section 6, PR
# 29; this one has the fewer relayouts to reason about.) What the orientation
# needs transposed (V for the forward, K for dq, the key chunks' segment ids)
# is transposed once a grid step into VMEM scratch; the forward's and dq's
# accumulators are transposed back once a query chunk.
def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _rel(ck, cq):
    """Query position minus key position inside a chunk pair, before the chunks' offsets."""
    return jax.lax.broadcasted_iota(jnp.int32, (ck, cq), 1) - jax.lax.broadcasted_iota(jnp.int32, (ck, cq), 0)


def _along_the_lanes(x, width):
    """``[n, LANES]`` with equal lanes -> ``[n, width]``."""
    return x if width == LANES else jnp.concatenate([x] * (width // LANES), axis=1)


def _segments_down_the_sublanes(seg_ref, dst_ref, rows):
    """A grid step's prologue: the key chunks' segment ids ``[rows, S / ck,
    ck]`` into ``dst_ref`` ``[rows, S, LANES]`` with ``dst[r, k, :] = seg[r,
    k]``: each 128 ids broadcast to a square and transposed."""
    _, n_k, ck = seg_ref.shape
    for r in range(rows):
        for j in range(n_k):
            for a in range(0, ck, LANES):
                square = jnp.broadcast_to(seg_ref[r, j : j + 1, a : a + LANES], (LANES, LANES))
                dst_ref[r, j * ck + a : j * ck + a + LANES, :] = square.T


def _transpose_chunks(src_ref, dst_ref, rows, group):
    """A grid step's prologue: every key chunk of every head of ``src_ref``
    ``[rows, S, group * d]`` transposed into ``dst_ref`` ``[rows, group,
    S / ck, d, ck]``."""
    _, _, n_k, d, ck = dst_ref.shape

    def chunk(t, carry):
        r, j = t // n_k, t % n_k
        for g, x in enumerate(_heads(src_ref, r, pl.ds(pl.multiple_of(j * ck, ck), ck), group)):
            dst_ref[r, g, j] = x.astype(jnp.float32).T.astype(dst_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows * n_k, chunk, 0)


def _heads(ref, r, at, group):
    """The ``group`` heads' ``[chunk, d]`` pieces of rows ``at`` of ``ref`` ``[rows, S, group * d]``."""
    d = ref.shape[-1] // group
    return [ref[r, at, g * d : (g + 1) * d] for g in range(group)]


# A grid step holds `group` heads of its rows, and every step of a walk does
# the same chunk pair for each of them: the mask is made once, and the heads'
# chains (product, softmax, product) are independent, so the scheduler has
# one head's products to run under another's exponentials. The walk's trip
# count is data, so nothing else overlaps one chunk pair with the next. One
# head's share of a step is a jitted function of arrays, so that a kernel's
# trace holds it once however many heads call it (set-up time: a trace is
# Python on the host, PERF.md section 6, PR 29).
@functools.partial(jax.jit, static_argnums=0)
def _fwd_pair(scale, k, q, vt, mask, m, l, acc):
    """One head's online-softmax step: ``k`` [ck, d], ``q`` [cq, d], ``vt``
    [d, ck]; statistics ``m``, ``l`` [1, cq] and the output so far [d, cq]."""
    s = _dot(k, q, _NT)  # [keys, queries]
    if scale != 1.0:
        s = s * scale
    s = jnp.where(mask, s, MASK_VALUE)
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
    return m_new, l, alpha * acc + _dot(vt, p.astype(vt.dtype))


@functools.partial(jax.jit, static_argnums=0)
def _probabilities(scale, k, q, v, do, lse, di, mask):
    """One head's ``p`` and ``ds`` [keys, queries] of a chunk pair, from the
    saved log-sum-exp and ``di`` rows [1, cq]."""
    s = _dot(k, q, _NT)
    if scale != 1.0:
        s = s * scale
    p = jnp.exp(jnp.where(mask, s, MASK_VALUE) - lse)
    ds = p * (_dot(v, do, _NT) - di)
    if scale != 1.0:
        ds = ds * scale
    return p, ds


def _fwd_kernel(
    k_lo_ref, k_hi_ref, seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, vt_ref, seg_kd_ref, *, scale, sizes
):
    rows, group, cq, ck = sizes
    d = v_ref.shape[-1] // group  # the values' and the output's width
    n_q = q_ref.shape[1] // cq
    row0 = pl.program_id(0) * rows
    rel = _rel(ck, cq)
    _transpose_chunks(v_ref, vt_ref, rows, group)
    _segments_down_the_sublanes(seg_k_ref, seg_kd_ref, rows)

    def q_chunk(t, carry):
        r, i = t // n_q, t % n_q
        at_q = pl.ds(pl.multiple_of(i * cq, cq), cq)
        q = _heads(q_ref, r, at_q, group)
        seg_q = seg_q_ref[r, pl.ds(i, 1), :]

        def k_chunk(j, stats):
            at_k = pl.ds(pl.multiple_of(j * ck, ck), ck)
            mask = (_along_the_lanes(seg_kd_ref[r, at_k, :], cq) == seg_q) & (rel >= j * ck - i * cq)
            return tuple(
                _fwd_pair(scale, k, q[g], vt_ref[r, g, j], mask, *stats[g])
                for g, k in enumerate(_heads(k_ref, r, at_k, group))
            )

        b = (row0 + r) * n_q + i
        start = (
            jnp.full((1, cq), -jnp.inf, jnp.float32),
            jnp.zeros((1, cq), jnp.float32),
            jnp.zeros((d, cq), jnp.float32),
        )
        stats = jax.lax.fori_loop(k_lo_ref[b], k_hi_ref[b] + 1, k_chunk, (start,) * group)
        for g, (m, l, acc) in enumerate(stats):
            # every query sees itself, so l > 0 and m is a real logit
            o_ref[r, at_q, g * d : (g + 1) * d] = (acc * (1.0 / l)).T.astype(o_ref.dtype)
            lse_ref[r, g, pl.ds(i, 1), :] = m + jnp.log(l)
        return carry

    jax.lax.fori_loop(0, rows * n_q, q_chunk, 0)


def _dq_kernel(
    k_lo_ref, k_hi_ref, seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, kt_ref, seg_kd_ref,
    *, scale, sizes,
):
    rows, group, cq, ck = sizes
    d = q_ref.shape[-1] // group
    n_q = q_ref.shape[1] // cq
    row0 = pl.program_id(0) * rows
    rel = _rel(ck, cq)
    _transpose_chunks(k_ref, kt_ref, rows, group)
    _segments_down_the_sublanes(seg_k_ref, seg_kd_ref, rows)

    def q_chunk(t, carry):
        r, i = t // n_q, t % n_q
        at_q = pl.ds(pl.multiple_of(i * cq, cq), cq)
        q, do = _heads(q_ref, r, at_q, group), _heads(do_ref, r, at_q, group)
        seg_q = seg_q_ref[r, pl.ds(i, 1), :]
        lse = [lse_ref[r, g, pl.ds(i, 1), :] for g in range(group)]
        di = [di_ref[r, g, pl.ds(i, 1), :] for g in range(group)]

        def k_chunk(j, dq):  # group x [d, cq]
            at_k = pl.ds(pl.multiple_of(j * ck, ck), ck)
            mask = (_along_the_lanes(seg_kd_ref[r, at_k, :], cq) == seg_q) & (rel >= j * ck - i * cq)
            out = []
            for g, (k, v) in enumerate(zip(_heads(k_ref, r, at_k, group), _heads(v_ref, r, at_k, group))):
                _, ds = _probabilities(scale, k, q[g], v, do[g], lse[g], di[g], mask)
                out.append(dq[g] + _dot(kt_ref[r, g, j], ds.astype(kt_ref.dtype)))
            return tuple(out)

        b = (row0 + r) * n_q + i
        dq = jax.lax.fori_loop(
            k_lo_ref[b], k_hi_ref[b] + 1, k_chunk, (jnp.zeros((d, cq), jnp.float32),) * group
        )
        for g in range(group):
            dq_ref[r, at_q, g * d : (g + 1) * d] = dq[g].T.astype(dq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows * n_q, q_chunk, 0)


def _dkv_kernel(
    q_lo_ref, q_hi_ref, seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref, seg_kd_ref,
    *, scale, sizes,
):
    rows, group, cq, ck = sizes
    d, dv = k_ref.shape[-1] // group, v_ref.shape[-1] // group
    n_k = k_ref.shape[1] // ck
    row0 = pl.program_id(0) * rows
    rel = _rel(ck, cq)
    _segments_down_the_sublanes(seg_k_ref, seg_kd_ref, rows)

    def k_chunk(t, carry):
        r, j = t // n_k, t % n_k
        at_k = pl.ds(pl.multiple_of(j * ck, ck), ck)
        k, v = _heads(k_ref, r, at_k, group), _heads(v_ref, r, at_k, group)
        seg_k = _along_the_lanes(seg_kd_ref[r, at_k, :], cq)

        def q_chunk(i, grads):  # group x ([ck, d], [ck, d])
            at_q = pl.ds(pl.multiple_of(i * cq, cq), cq)
            mask = (seg_k == seg_q_ref[r, pl.ds(i, 1), :]) & (rel >= j * ck - i * cq)
            out = []
            for g, (q, do) in enumerate(zip(_heads(q_ref, r, at_q, group), _heads(do_ref, r, at_q, group))):
                dk, dvalue = grads[g]
                lse, di = lse_ref[r, g, pl.ds(i, 1), :], di_ref[r, g, pl.ds(i, 1), :]
                p, ds = _probabilities(scale, k[g], q, v[g], do, lse, di, mask)
                out.append((dk + _dot(ds.astype(q.dtype), q), dvalue + _dot(p.astype(do.dtype), do)))
            return tuple(out)

        b = (row0 + r) * n_k + j
        zeros = (jnp.zeros((ck, d), jnp.float32), jnp.zeros((ck, dv), jnp.float32))
        grads = jax.lax.fori_loop(q_lo_ref[b], q_hi_ref[b] + 1, q_chunk, (zeros,) * group)
        for g, (dk, dvalue) in enumerate(grads):
            dk_ref[r, at_k, g * d : (g + 1) * d] = dk.astype(dk_ref.dtype)
            dv_ref[r, at_k, g * dv : (g + 1) * dv] = dvalue.astype(dv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows * n_k, k_chunk, 0)


# ------------------------------------------------------------------ the calls
def _specs(batch, seq_len, heads, head_dim, sizes):
    """Block specs of one grid step ``(row block, head group)``: whole rows of
    ``group`` heads of width ``head_dim`` in the projections' layout, the
    statistics' rows, the segment rows."""
    rows, group, cq, ck = sizes
    qkv = pl.BlockSpec((rows, seq_len, group * head_dim), lambda b, h, *_: (b, 0, h))
    stat = pl.BlockSpec((rows, group, seq_len // cq, cq), lambda b, h, *_: (b, h, 0, 0))
    seg_q = pl.BlockSpec((rows, seq_len // cq, cq), lambda b, h, *_: (b, 0, 0))
    seg_k = pl.BlockSpec((rows, seq_len // ck, ck), lambda b, h, *_: (b, 0, 0))
    return (batch // rows, heads // group), qkv, stat, seg_q, seg_k


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret, scratch=()):
    # The trace names a Mosaic call by ``name``: `flash_attn_roofline`
    # (benchmark/metrics) finds the three kernels by theirs.
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=100 * 1024 * 1024
        ),
        interpret=interpret,
        name=name,
    )


def _scratch(seq_len, head_dim, dtype, sizes):
    """A grid step's transposed operand, chunk by chunk, and the key chunks'
    segment ids down the sublanes."""
    rows, group, _, ck = sizes
    return (
        pltpu.VMEM((rows, group, seq_len // ck, head_dim, ck), dtype),
        pltpu.VMEM((rows, seq_len, LANES), jnp.int32),
    )


def _walk(seg, sizes):
    """What the kernels read of the segment ids, made once a call: the ids as
    rows of query chunks and of key chunks, and the four bounds, flat."""
    batch, seq_len = seg.shape
    _, _, cq, ck = sizes
    bounds = chunk_bounds(seg, cq, ck)
    return (seg.reshape(batch, seq_len // cq, cq), seg.reshape(batch, seq_len // ck, ck), *(b.reshape(-1) for b in bounds))


# Jitted with everything but the arrays static: a model's layers (and a layer's
# recomputation) then share one trace of each kernel and one lowering, as
# they did the stock kernel's; the call sites keep their own names.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _forward(q, k, v, walk, heads, scale, sizes, interpret):
    batch, seq_len, _ = q.shape
    key_dim, value_dim = q.shape[-1] // heads, v.shape[-1] // heads
    grid, qk, stat, seg_q, seg_k = _specs(batch, seq_len, heads, key_dim, sizes)
    vo = _specs(batch, seq_len, heads, value_dim, sizes)[1]
    seg_q_rows, seg_k_rows, k_lo, k_hi, _, _ = walk
    return _call(
        functools.partial(_fwd_kernel, scale=scale, sizes=sizes),
        "flash_attention",
        grid,
        [seg_q, seg_k, qk, qk, vo],
        [vo, stat],
        [
            jax.ShapeDtypeStruct(v.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_len // sizes.chunk_q, sizes.chunk_q), jnp.float32),
        ],
        interpret,
        _scratch(seq_len, value_dim, q.dtype, sizes),
    )(k_lo, k_hi, seg_q_rows, seg_k_rows, q, k, v)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward(q, k, v, walk, o, lse, do, heads, scale, sizes, interpret):
    batch, seq_len, _ = q.shape
    key_dim, value_dim = q.shape[-1] // heads, v.shape[-1] // heads
    grid, qk, stat, seg_q, seg_k = _specs(batch, seq_len, heads, key_dim, sizes)
    vo = _specs(batch, seq_len, heads, value_dim, sizes)[1]
    seg_q_rows, seg_k_rows, k_lo, k_hi, q_lo, q_hi = walk
    # di = sum(o * do) over a head's width, in the statistics' compact shape.
    di = jnp.sum(
        o.astype(jnp.float32).reshape(batch, seq_len, heads, value_dim)
        * do.astype(jnp.float32).reshape(batch, seq_len, heads, value_dim),
        axis=-1,
    )
    di = di.transpose(0, 2, 1).reshape(lse.shape)
    operands = (seg_q_rows, seg_k_rows, q, k, v, do, lse, di)
    in_specs = [seg_q, seg_k, qk, qk, vo, vo, stat, stat]
    like_k, like_v = jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(v.shape, q.dtype)
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, sizes=sizes),
        "flash_mha_bwd_dkv", grid, in_specs, [qk, vo], [like_k, like_v], interpret,
        _scratch(seq_len, key_dim, q.dtype, sizes)[1:],
    )(q_lo, q_hi, *operands)
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, sizes=sizes),
        "flash_mha_bwd_dq", grid, in_specs, qk, like_k, interpret, _scratch(seq_len, key_dim, q.dtype, sizes),
    )(k_lo, k_hi, *operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, walk, heads, scale, sizes, interpret):
    with scope("attn_global"):
        return _forward(q, k, v, walk, heads, scale, sizes, interpret)[0]


# JAX traces a custom_vjp's rules without the caller's name stack, so each
# rule enters the scope itself (PERF.md section 6, PR 28).
def _flash_fwd(q, k, v, walk, heads, scale, sizes, interpret):
    with scope("attn_global"):
        o, lse = _forward(q, k, v, walk, heads, scale, sizes, interpret)
    return o, (q, k, v, walk, o, lse)


def _flash_bwd(heads, scale, sizes, interpret, residuals, do):
    with scope("attn_global"):
        return (*_backward(*residuals, do, heads, scale, sizes, interpret), None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    query, key, value, segment_ids, *, sm_scale: float, sizes: FlashSizes | None = None, interpret: bool = False
):
    """Causal attention inside segments: ``softmax(sm_scale * q k^T) v`` over
    the keys with the query's segment id at or before it.

    Args:
        query, key: ``[B, S, H, d]`` as the projections leave them.
        value: ``[B, S, H, dv]``; ``dv`` may differ from ``d`` (latent
            attention at 192 / 128). A group of heads of either width is whole
            128-lane tiles (`flash_block_sizes`); ``S`` a multiple of the
            chunk widths; one dtype.
        segment_ids: ``[B, S]`` int32, padding as ``-1``.
        sm_scale: the logits' scale.
        sizes: `flash_block_sizes` where not given (the probe sweeps them).
        interpret: run the kernels in Pallas' interpreter (any backend).

    Returns ``[B, S, H, dv]``. Differentiable in query, key and value.
    """
    batch, seq_len, heads, head_dim = query.shape
    sizes = sizes or flash_block_sizes(batch, seq_len, heads, head_dim, query.dtype.itemsize, value.shape[-1])
    flat = (batch, seq_len, -1)
    with scope("attn_global"):
        walk = _walk(segment_ids.astype(jnp.int32), sizes)
    out = _flash(query.reshape(flat), key.reshape(flat), value.reshape(flat), walk, heads, float(sm_scale), sizes, interpret)
    return out.reshape(value.shape)
