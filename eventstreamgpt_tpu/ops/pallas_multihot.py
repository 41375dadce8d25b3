"""Pallas TPU kernels for the planes that are as wide as the vocabulary.

**The event embedding's weighted-multihot plane** (`weighted_multihot`, PR
27). The data embedding sums, per event slot, M table rows with their weights
(`ops.embedding_bag`). At training shapes (N = 16,384 slots, M = 24,
V = 4,057 rows of D = 1,024, bf16) XLA's two formulations of that were the
train step's largest operations outside the dense matmuls (device trace,
PERF.md section 6, PR 26 and PR 27): the forward's ``take`` gathered
N*M = 393,216 rows of 2 KB in 9.0 ms, and the table gradient's plane build
made M read-modify-write passes over the ``(N, V)`` plane in 9.9 ms.
`weighted_multihot` builds that plane,

    ``mh[n, v] = sum_m weights[n, m] * (indices[n, m] == v)``,

in ONE pass: a tile of rows x vocabulary lanes is accumulated in float32
across the M slots without leaving the core, rounded once to the compute
dtype and written once. Both directions of the bag are then plain MXU
matmuls against it (``mh @ table`` and ``mh.T @ g``, `ops.tensor_ops`).

**The multi-label heads' label plane** (`multihot_any`, PR 37; called by
`models.model_output.GenerativeOutputLayerBase.get_classification_outputs`),

    ``any[n, v] = any_m (indices[n, m] == v)``,

exact 0s and 1s. XLA's broadcast compare-any over M = 24 slots x the
vocabulary wrote it events-minor in 3.5-17.8 ms a step and, where the loss
fusion reads the vocabulary along the lanes, re-laid it (PERF.md section 6,
PR 37). Which axis that fusion reads along the lanes is XLA's choice for the
head's ``[hidden, vocabulary]`` kernel: the vocabulary where the unified
vocabulary is a multiple of 128, the events where it is not. The plane has a
kernel for either, each one pass of compare + select a slot, so that what
the kernel writes is what the fusion reads and nothing is copied between.

Off-TPU `weighted_multihot` lowers to a ``fori_loop`` over the M slots (one
``(N, Vp)`` float32 accumulator, never the ``(N, M, V)`` one-hot) and
`multihot_any` to the broadcast compare-any, so traces stay portable;
``impl="pallas_interpret"`` runs the kernels in interpreter mode for
platform-independent parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .impl_select import LANE, resolve_impl
from .impl_select import round_up as _round_up

__all__ = ["multihot_any", "weighted_multihot"]

# Rows and vocabulary lanes of one grid step. The slot loop runs over groups
# of 16 rows (bf16's sublane tile) with the group's float32 accumulator held
# across the M slots. TPU v5e, N=16384 / M=24 / Vp=4096, bf16 (my chip run 1,
# PR 27): 256 x 2048 builds the plane in 1.02 ms, 256 x 4096 in 1.14,
# 512 x 1024 and 1024 x 1024 in 1.26; accumulating 1024 lanes at a time
# 1.10, 512 at a time 1.40, 256 at a time 2.29.
_ROW_TILE = 256
_LANE_TILE = 2048
_ROW_GROUP = 16
# The label plane (`multihot_any`), TPU v5e, N = 16,384 slots of M = 24, bf16
# out (my chip runs 1-4, PR 37; ms a plane of 3,500 / 12,827 / 15,803 columns).
# Compare + select a slot builds it in 0.67 where `weighted_multihot`'s
# compare, select and add take 1.36; compare + or of masks 1.09. Vocabulary
# on the lanes: a grid step of at most 2,048 lanes, all steps of one width and
# the plane padded up to them (1,792 x 2, 1,920 x 7, 2,048 x 8), in row groups
# of 32: 0.59 / 2.14 / 2.59; groups of 16: 0.68 / 2.40 / 2.87 (at most 4,096
# lanes 0.62 / 2.23 / 2.65, 1,024 lanes 0.88 / 2.89 / 3.55), of 8: 0.85 at
# 3,500; 128 or 512 rows a step the same. A lane tile that must divide
# round_up(V, 128) is 128 lanes at 12,928 = 101 x 128: 19.8 ms. Events on the
# lanes (S = 1,024; 64 rows of S = 256): 256 columns a step in groups whose
# float32 accumulator is 32 vregs (32 columns at 1,024 events): 0.57 / - /
# 2.46 (S = 256: groups of 128 0.56, of 64 0.59, of 32 0.63, of 256 0.71); groups of 16
# 0.65 / - / 2.81, of 64 0.64 / - / 2.75; 128, 512 or 1,024 columns a step
# within 0.01 but 1,024 (0.74). int8 out: within 0.03 either way.
# The kernels write int8 and the consumer's fusion converts: as bf16 the two
# planes took `memory_peak_bytes` of the hybrid cell from 14.08 to 14.31 GB,
# as int8 to 14.09 (my chip runs 4 and 5, PR 37; XLA's `pred` planes were a
# byte an element too), and the kernels take 5% longer for half the bytes
# (0.61 against 0.57 ms). int8's sublane tile is 32 rows: every group below
# is whole tiles of it.
_ANY_DTYPE = jnp.int8
_ANY_LANE_TILE = 2048
_ANY_ROW_GROUP = 32
_ANY_GROUP_ELEMENTS = 32 * 1024
_COLUMN_TILE = 256
_EVENT_TILES = (1024, 512, 256, 128)
# Fewest columns that take the kernel when nothing names an implementation.
# At 16 x 1,024 x 24 slots, the plane and one reduction over it, XLA's fused
# compare-any / vocabulary-minor / events-minor kernel (ms, my chip run 2, PR
# 37; 0.20 is one dispatch's floor): 127 columns 0.20 / 0.23 / 0.21, 128 0.32 /
# 0.22 / 0.21, 256 0.27 / 0.23 / 0.21, 500 0.45 / 0.23 / 0.20. Under one lane
# tile nothing separates them, the vocabulary-minor plane is mostly padding,
# and the tiny float32 models of the tests pin their losses on the parent's
# formulation (NA's per-level walk calls with spans of tens of columns).
_ANY_MIN_COLUMNS = LANE


def _lane_tile(vp: int, cap: int) -> int:
    """The widest multiple of `LANE` that divides ``vp`` and is <= ``cap``."""
    lanes = vp // LANE
    best = max(d for d in range(1, min(lanes, cap // LANE) + 1) if lanes % d == 0)
    return best * LANE


def _multihot_kernel(idx_ref, w_ref, out_ref):
    tn, m = idx_ref.shape
    tv = out_ref.shape[-1]
    base = pl.program_id(1) * tv
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROW_GROUP, tv), 1)

    def rows(r, carry):
        sl = pl.ds(pl.multiple_of(r * _ROW_GROUP, _ROW_GROUP), _ROW_GROUP)
        idx = idx_ref[sl, :] - base  # (16, M): each slot's lane in this tile
        w = w_ref[sl, :]
        acc = jnp.zeros((_ROW_GROUP, tv), jnp.float32)
        for s in range(m):
            acc = acc + jnp.where(lane == idx[:, s : s + 1], w[:, s : s + 1], 0.0)
        out_ref[sl, :] = acc.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tn // _ROW_GROUP, rows, 0)


@functools.partial(jax.jit, static_argnames=("vp", "dtype", "interpret"))
def _multihot_2d(
    idx: jnp.ndarray, w: jnp.ndarray, vp: int, dtype, interpret: bool = False
) -> jnp.ndarray:
    n, m = idx.shape
    tn = min(_ROW_TILE, _round_up(n, _ROW_GROUP))
    rows = _round_up(n, tn)
    if rows != n:
        # Padding rows carry weight 0: their plane rows are zeros, cut below.
        idx = jnp.pad(idx, ((0, rows - n), (0, 0)))
        w = jnp.pad(w, ((0, rows - n), (0, 0)))
    tv = _lane_tile(vp, _LANE_TILE)
    mh = pl.pallas_call(
        _multihot_kernel,
        grid=(rows // tn, vp // tv),
        in_specs=[
            pl.BlockSpec((tn, m), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, m), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tn, tv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, vp), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(idx.astype(jnp.int32), w.astype(jnp.float32))
    return mh[:n]


def _any_kernel(idx_ref, out_ref):
    """Vocabulary on the lanes: ``idx_ref`` ``(rows, M)``, ``out_ref`` ``(rows, lanes)``."""
    tn, m = idx_ref.shape
    tv = out_ref.shape[-1]
    base = pl.program_id(1) * tv
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ANY_ROW_GROUP, tv), 1)

    def rows(r, carry):
        sl = pl.ds(pl.multiple_of(r * _ANY_ROW_GROUP, _ANY_ROW_GROUP), _ANY_ROW_GROUP)
        idx = idx_ref[sl, :] - base
        hit = jnp.zeros((_ANY_ROW_GROUP, tv), jnp.float32)
        for s in range(m):
            hit = jnp.where(lane == idx[:, s : s + 1], 1.0, hit)
        out_ref[sl, :] = hit.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tn // _ANY_ROW_GROUP, rows, 0)


def _any_kernel_events_minor(idx_ref, out_ref):
    """Events on the lanes: ``idx_ref`` ``(1, M, events)``, ``out_ref``
    ``(1, columns, events)``. A slot's indices are one row, broadcast down the
    sublanes for nothing."""
    _, m, ts = idx_ref.shape
    tv = out_ref.shape[1]
    base = pl.program_id(1) * tv
    group = min(tv, _ANY_GROUP_ELEMENTS // ts)
    column = jax.lax.broadcasted_iota(jnp.int32, (group, ts), 0)

    def columns(r, carry):
        at = pl.multiple_of(r * group, group)
        here = column + (base + at)
        hit = jnp.zeros((group, ts), jnp.float32)
        for s in range(m):
            hit = jnp.where(here == idx_ref[0, s : s + 1, :], 1.0, hit)
        out_ref[0, pl.ds(at, group), :] = hit.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tv // group, columns, 0)


def _any_lane_tiles(vocab: int) -> tuple[int, int]:
    """``(lanes of a grid step, padded columns)``: the fewest steps of at most
    `_ANY_LANE_TILE` lanes, all of one width, the plane padded up to them. A
    tile that has to divide ``round_up(vocab, 128)`` is 128 lanes wide where
    that count of lane tiles is prime (12,928 = 101 x 128)."""
    steps = pl.cdiv(vocab, _ANY_LANE_TILE)
    tv = _round_up(pl.cdiv(vocab, steps), LANE)
    return tv, steps * tv


@functools.partial(jax.jit, static_argnames=("vocab", "interpret"))
def _anyhot_2d(idx: jnp.ndarray, vocab: int, interpret: bool = False) -> jnp.ndarray:
    """``(N, M)`` indices to the ``(N, vp)`` plane, ``vp`` as `_any_lane_tiles` pads it."""
    n, m = idx.shape
    tv, vp = _any_lane_tiles(vocab)
    tn = min(_ROW_TILE, _round_up(n, _ANY_ROW_GROUP))
    rows = _round_up(n, tn)
    if rows != n:
        idx = jnp.pad(idx, ((0, rows - n), (0, 0)), constant_values=-1)
    plane = pl.pallas_call(
        _any_kernel,
        grid=(rows // tn, vp // tv),
        in_specs=[pl.BlockSpec((tn, m), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((tn, tv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, vp), _ANY_DTYPE),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(idx)
    return plane[:n]


@functools.partial(jax.jit, static_argnames=("vocab", "interpret"))
def _anyhot_events_minor(idx: jnp.ndarray, vocab: int, interpret: bool = False) -> jnp.ndarray:
    """``(B, S, M)`` indices to the ``(B, vp, S)`` plane; ``S`` a multiple of
    `LANE`, ``vp`` whole `_COLUMN_TILE`s."""
    b, s, m = idx.shape
    vp = _round_up(vocab, _COLUMN_TILE)
    ts = max(t for t in _EVENT_TILES if s % t == 0)
    return pl.pallas_call(
        _any_kernel_events_minor,
        grid=(b, vp // _COLUMN_TILE, s // ts),
        in_specs=[pl.BlockSpec((1, m, ts), lambda i, j, k: (i, 0, k))],
        out_specs=pl.BlockSpec((1, _COLUMN_TILE, ts), lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct((b, vp, s), _ANY_DTYPE),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=interpret,
    )(jnp.swapaxes(idx, 1, 2))


def _multihot_xla(idx: jnp.ndarray, w: jnp.ndarray, vp: int) -> jnp.ndarray:
    n, m = idx.shape
    lane = jnp.arange(vp, dtype=idx.dtype)[None, :]
    w32 = w.astype(jnp.float32)

    def body(s, acc):
        return acc + jnp.where(lane == idx[:, s][:, None], w32[:, s][:, None], 0.0)

    return jax.lax.fori_loop(0, m, body, jnp.zeros((n, vp), jnp.float32)).astype(w.dtype)


def weighted_multihot(
    indices: jnp.ndarray, weights: jnp.ndarray, vocab: int, impl: str | None = None
) -> jnp.ndarray:
    """``mh[n, v] = sum_m weights[n, m] * (indices[n, m] == v)``, lane-padded.

    Args:
        indices: ``(N, M)`` int indices. Clipped to ``[0, vocab - 1]``, as
            `ops.embedding_bag`'s gather is (``mode="clip"``): an
            out-of-range index credits the edge row.
        weights: ``(N, M)`` weights in the compute dtype. The caller zeroes
            the weights of padding slots (index 0).
        vocab: number of table rows.
        impl: ``None``/"auto" (Pallas kernel on TPU backends, the XLA loop
            elsewhere; overridable via ``$ESGPT_PALLAS_IMPL`` —
            `ops.impl_select`), ``"pallas"``, ``"pallas_interpret"`` or
            ``"xla"``.

    Returns:
        ``(N, Vp)`` plane in ``weights.dtype`` with ``Vp = round_up(vocab,
        128)``; columns ``vocab..Vp`` are zero. Slots of one event that hold
        the same index are summed in float32 and rounded once.
    """
    impl = resolve_impl(impl, "weighted_multihot")
    # jnp arrays up front: eager callers may hand host numpy.
    indices = jnp.clip(jnp.asarray(indices), 0, vocab - 1)
    weights = jnp.asarray(weights)
    vp = _round_up(vocab, LANE)
    if impl == "xla":
        return _multihot_xla(indices, weights, vp)
    from ..parallel.context import per_batch_shard

    interpret, dtype = impl == "pallas_interpret", jnp.dtype(weights.dtype)
    return per_batch_shard(
        lambda i_, w_: _multihot_2d(i_, w_, vp=vp, dtype=dtype, interpret=interpret),
        indices,
        weights,
    )


def multihot_any(
    indices: jnp.ndarray, vocab: int, dtype, events_minor: bool = False, impl: str | None = None
) -> jnp.ndarray:
    """``any[..., v] = any_m (indices[..., m] == v)`` as 0 / 1 in ``dtype``.

    Args:
        indices: ``(..., M)`` int indices. One outside ``[0, vocab)`` names no
            column (the caller sends padding and foreign slots to -1).
        vocab: number of columns.
        dtype: the plane's element type as the consumer reads it.
        events_minor: which axis of the plane the consumer reads along the
            lanes. The kernel writes that one minor, so that the transpose
            below is XLA's choice of layout and no copy: the vocabulary
            (``False``), or the last axis before the slots (``True``;
            ``(B, S, M)`` indices with ``S`` a multiple of 128).
        impl: as `weighted_multihot`'s; the XLA formulation is the broadcast
            compare-any.

    Returns:
        ``(..., vocab)`` plane of exact 0s and 1s; slots of one event that
        hold the same index fold into one 1.
    """
    if impl in (None, "auto") and vocab < _ANY_MIN_COLUMNS:
        impl = "xla"
    impl = resolve_impl(impl, "multihot_any")
    indices = jnp.asarray(indices).astype(jnp.int32)
    if impl == "xla":
        return (indices[..., :, None] == jnp.arange(vocab)).any(axis=-2).astype(dtype)
    from ..parallel.context import per_batch_shard

    interpret = impl == "pallas_interpret"
    if events_minor and indices.ndim == 3 and indices.shape[1] % LANE == 0:
        plane = per_batch_shard(lambda i_: _anyhot_events_minor(i_, vocab, interpret), indices)
        return jnp.swapaxes(plane, 1, 2)[..., :vocab].astype(dtype)
    plane = per_batch_shard(
        lambda i_: _anyhot_2d(i_, vocab, interpret), indices.reshape(-1, indices.shape[-1])
    )
    return plane.reshape(*indices.shape[:-1], -1)[..., :vocab].astype(dtype)
