"""Pallas TPU kernel for the event embedding's weighted-multihot plane.

The data embedding sums, per event slot, M table rows with their weights
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

Off-TPU `weighted_multihot` lowers to a ``fori_loop`` over the M slots (one
``(N, Vp)`` float32 accumulator, never the ``(N, M, V)`` one-hot) so traces
stay portable; ``impl="pallas_interpret"`` runs the kernel in interpreter
mode for platform-independent parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .impl_select import LANE, resolve_impl
from .impl_select import round_up as _round_up

__all__ = ["weighted_multihot"]

# Rows and vocabulary lanes of one grid step. The slot loop runs over groups
# of 16 rows (bf16's sublane tile) with the group's float32 accumulator held
# across the M slots. TPU v5e, N=16384 / M=24 / Vp=4096, bf16 (my chip run 1,
# PR 27): 256 x 2048 builds the plane in 1.02 ms, 256 x 4096 in 1.14,
# 512 x 1024 and 1024 x 1024 in 1.26; accumulating 1024 lanes at a time
# 1.10, 512 at a time 1.40, 256 at a time 2.29.
_ROW_TILE = 256
_LANE_TILE = 2048
_ROW_GROUP = 16


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
