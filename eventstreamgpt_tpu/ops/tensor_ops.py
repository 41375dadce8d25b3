"""Masked reductions and sparse-feature ops with reference-exact semantics.

Reference contracts: ``/root/reference/EventStream/transformer/utils.py``
(``safe_masked_max`` ``:61``, ``safe_weighted_avg`` ``:134``, ``weighted_loss``
``:209``, ``expand_indexed_regression`` ``:33``) and the ``EmbeddingBag(mode=
"sum", padding_idx=0)`` behavior underlying the data embedding layer
(``data/data_embedding_layer.py:524-607``). All functions here are pure jnp
and jit/vmap/grad-safe; none rely on data-dependent shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .impl_select import LANE, round_up
from .pallas_multihot import weighted_multihot


def str_summary(T) -> str:
    """Returns a string summary of an array for debugging purposes.

    Examples:
        >>> import jax.numpy as jnp
        >>> T = jnp.asarray([[[1., 2., 3., 4., 5.], [6., 7., 8., 9., 10.]]])
        >>> str_summary(T)
        'shape: (1, 2, 5), type: float32, range: 1-10'
    """
    return f"shape: {tuple(T.shape)}, type: {T.dtype}, range: {T.min():n}-{T.max():n}"


def expand_indexed_regression(X: jnp.ndarray, idx: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """Expands sparse values ``X`` at indices ``idx`` into a dense last axis.

    Matches ``transformer/utils.py:33``: output shape ``[..., vocab_size]``
    with ``out[..., idx[..., i]] = X[..., i]`` and zeros elsewhere. Duplicate
    indices resolve to one of the written values (scatter semantics), as in
    torch's ``scatter``.

    Examples:
        >>> import jax.numpy as jnp
        >>> X = jnp.asarray([[1., 2., 3.], [4., 5., 6.]])
        >>> idx = jnp.asarray([[0, 1, 2], [1, 3, 0]])
        >>> expand_indexed_regression(X, idx, 5)
        Array([[1., 2., 3., 0., 0.],
               [6., 4., 0., 5., 0.]], dtype=float32)
    """
    # One-hot matmul formulation: MXU-friendly and avoids ragged scatters.
    # Where duplicate indices exist torch.scatter keeps an arbitrary one; a sum
    # is deterministic, and every caller passes distinct indices per row.
    one_hot = jnp.asarray(idx[..., None] == jnp.arange(vocab_size), dtype=X.dtype)
    return jnp.einsum("...mv,...m->...v", one_hot, X)


def safe_masked_max(X: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Max over the last axis of ``X`` where ``mask`` is True; 0 for empty rows.

    ``mask`` is either element-wise (same shape as ``X``) or column-wise (same
    shape as ``X`` minus the second-to-last axis). Reference:
    ``transformer/utils.py:61``.

    Examples:
        >>> import jax.numpy as jnp
        >>> X = jnp.asarray([[1., 2., 3.], [4., 5., 6.]])
        >>> mask = jnp.asarray([[True, True, False], [False, False, False]])
        >>> safe_masked_max(X, mask)
        Array([2., 0.], dtype=float32)
        >>> X = jnp.asarray([[[1., 2., 3.], [4., 5., 6.]], [[7., 8., 9.], [10., 11., 12.]]])
        >>> mask = jnp.asarray([[False, True, False], [True, False, True]])
        >>> safe_masked_max(X, mask)
        Array([[ 2.,  5.],
               [ 9., 12.]], dtype=float32)
    """
    if mask.ndim < X.ndim:
        if mask.shape != X.shape[:-2] + X.shape[-1:]:
            raise AssertionError(
                f"mask {mask.shape} must be the same shape as X {X.shape} "
                "or the same shape as X excluding the second to last dimension"
            )
        mask = jnp.broadcast_to(mask[..., None, :], X.shape)
    elif mask.shape != X.shape:
        raise AssertionError(
            f"mask {mask.shape} must be the same shape as X {X.shape} "
            "or the same shape as X excluding the second to last dimension"
        )
    maxes = jnp.max(jnp.where(mask, X, -jnp.inf), axis=-1)
    return jnp.where(jnp.isneginf(maxes), 0.0, maxes)


def safe_weighted_avg(X: jnp.ndarray, weights: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted average over the last axis; (0, 0) where weights sum to zero.

    Returns ``(avg, denom)``. ``weights`` is element-wise or column-wise as in
    `safe_masked_max`. Reference: ``transformer/utils.py:134``.

    Examples:
        >>> import jax.numpy as jnp
        >>> X = jnp.asarray([[1., 2., 3.], [4., 5., 6.]])
        >>> weights = jnp.asarray([[0., 0., 0.], [1., 0., 0.]])
        >>> safe_weighted_avg(X, weights)
        (Array([0., 4.], dtype=float32), Array([0., 1.], dtype=float32))
    """
    if weights.ndim < X.ndim:
        if weights.shape != X.shape[:-2] + X.shape[-1:]:
            raise AssertionError(
                f"weights {weights.shape} must be the same shape as X {X.shape} "
                "or the same shape as X excluding the second to last dimension"
            )
        weights = jnp.broadcast_to(weights[..., None, :], X.shape)
    elif weights.shape != X.shape:
        raise AssertionError(
            f"weights {weights.shape} must be the same shape as X {X.shape} "
            "or the same shape as X excluding the second to last dimension"
        )
    weights = weights.astype(jnp.float32)
    denom = weights.sum(axis=-1)
    safe_denom = jnp.where(denom > 0, denom, 1.0)
    avg = jnp.where(denom > 0, (X * weights).sum(axis=-1) / safe_denom, 0.0)
    return avg, denom


def weighted_loss(loss_per_event: jnp.ndarray, event_mask: jnp.ndarray) -> jnp.ndarray:
    """Macro-average: per-event → per-subject mean → mean over non-empty subjects.

    Reference: ``transformer/utils.py:209``. This nested-macro-average contract
    is the loss-parity-critical reduction used by every generative head.

    Examples:
        >>> import jax.numpy as jnp
        >>> loss_per_event = jnp.asarray([[1., 2., 3.], [4., 5., 6.]])
        >>> event_mask = jnp.asarray([[1., 1., 1.], [1., 0., 0.]])
        >>> weighted_loss(loss_per_event, event_mask)
        Array(3., dtype=float32)
    """
    loss_per_subject, events_per_subject = safe_weighted_avg(loss_per_event, event_mask)
    return safe_weighted_avg(loss_per_subject, (events_per_subject > 0))[0]


# Who takes the plane path (`_bag_2d`: the bag as two MXU matmuls against the
# weighted-multihot plane of `ops.pallas_multihot`) and who keeps the gather is
# read at trace time from static shapes, and from nothing else. Largest
# (N, Vp) plane that may be built and saved for the backward:
_BAG_PLANE_MAX_BYTES = 512 * 1024 * 1024
# Narrowest table that takes the plane. TPU v5e, bf16, M=24, V=4057 (the one
# vocabulary and dtype read), device ms of forward / table gradient (my chip
# run 3, PR 27; PERF.md section 6). At N=16384, plane against gather +
# scatter: D=1024 1.80 / 1.80 against 3.04 / 16.14; D=512 1.43 / 1.43 against
# 2.24 / 6.90; D=256 1.26 / 1.25 against 1.60 / 4.42; D=128 (and 64, 32: one
# lane tile) 1.24 / 1.24 against 0.90 / 2.94. So in training the plane wins
# at every width read (D=128: 2.48 against 3.84 for the pair): the width term
# is not there for training speed. It is there for callers that run the
# forward alone, where below 256 the gather is the faster (0.90 against
# 1.24), and for the tiny float32 models of the tests and the rehearsals
# (D=32), whose pinned losses hold to the bit on the gather. At D=1024 there
# is no least N: N=2048 0.23 / 0.25 against 0.33 / 1.92; N=128 0.018 / 0.028
# against 0.020 / 0.162; N=64 0.010 / 0.023 against 0.010 / 0.105 (static
# codes, N=64 and M=8: 0.008 / 0.020 against 0.006 / 0.061). A forward alone
# gains nothing at those N (a serving engine's decode step: 0.010 against
# 0.010) and now holds a Mosaic call; no benchmark cell runs a serving
# program yet (PERF.md section 7).
_BAG_PLANE_MIN_DIM = 256


def _plane_ok(table: jnp.ndarray, n_rows: int) -> bool:
    plane = n_rows * round_up(table.shape[0], LANE) * table.dtype.itemsize
    return plane <= _BAG_PLANE_MAX_BYTES and table.shape[1] >= _BAG_PLANE_MIN_DIM


def _plane_dot(spec: str, mh: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """A contraction against the plane, float32 out. The chip's default
    precision truncates float32 operands to bf16, a lower precision than a
    float32 model states: float32 runs at HIGHEST (as `vocab_gather` does)."""
    precision = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jnp.einsum(spec, mh, x, precision=precision, preferred_element_type=jnp.float32)


def _table_grad(mh: jnp.ndarray, g: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """``mh.T (Vp, N) @ g (N, D)`` in float32, cut to the table's rows.
    Duplicate indices accumulate in fp32 on the MXU; XLA's native backward
    is a serialized scatter-add of N*M rows."""
    return _plane_dot("nv,nd->vd", mh, g)[: table.shape[0]]


@jax.custom_vjp
def _bag_2d(table: jnp.ndarray, indices: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """``(N, M)`` bag through the plane, forward and table gradient (see
    `embedding_bag`)."""
    return _bag_2d_fwd(table, indices, weights)[0]


def _bag_2d_fwd(table, indices, weights):
    vocab = table.shape[0]
    mh = weighted_multihot(indices, weights, vocab)
    # Zero rows under the plane's lane padding: 8 MB of table copied, where
    # cutting the plane to (N, V) would copy the plane.
    padded = jnp.pad(table, ((0, mh.shape[1] - vocab), (0, 0)))
    out = _plane_dot("nv,vd->nd", mh, padded).astype(table.dtype)
    return out, (table, indices, mh)


def _bag_2d_bwd(res, g):
    table, indices, mh = res
    d_table = _table_grad(mh, g, table).astype(table.dtype)
    # Weight cotangent re-gathers rather than saving the (N, M, D) residual;
    # when weights are not on a differentiable path (the usual case — they
    # come from batch values), XLA dead-code-eliminates this entirely.
    d_w = jnp.einsum("nmd,nd->nm", jnp.take(table, indices, axis=0, mode="clip"), g).astype(
        mh.dtype
    )
    return d_table, None, d_w


_bag_2d.defvjp(_bag_2d_fwd, _bag_2d_bwd)


@jax.custom_vjp
def _grouped_bag_2d(table: jnp.ndarray, indices: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """``(N, G, M)``-weighted bag with a matmul table-gradient."""
    gathered = jnp.take(table, indices, axis=0, mode="clip")
    return jnp.einsum("nmd,ngm->ngd", gathered, weights)


def _grouped_bag_2d_fwd(table, indices, weights):
    return _grouped_bag_2d(table, indices, weights), (table, indices, weights)


def _grouped_bag_2d_bwd(res, g):
    table, indices, weights = res
    # One plane+matmul per group (G is the dep-graph depth, 2-4): the
    # per-(token, slot) cotangent is a D-vector, so a single flattened
    # multihot would need an (N·M, V) plane; per-group planes stay (N, Vp).
    # The forward keeps its one gather: G plane builds are not clearly
    # cheaper than it, and no benchmark cell runs this model to judge.
    d_table = jnp.zeros(table.shape, jnp.float32)
    for grp in range(weights.shape[1]):
        mh = weighted_multihot(indices, weights[:, grp, :], table.shape[0])
        d_table = d_table + _table_grad(mh, g[:, grp, :], table)
    d_w = jnp.einsum(
        "nmd,ngd->ngm", jnp.take(table, indices, axis=0, mode="clip"), g
    ).astype(weights.dtype)
    return d_table.astype(table.dtype), None, d_w


_grouped_bag_2d.defvjp(_grouped_bag_2d_fwd, _grouped_bag_2d_bwd)


def embedding_bag(
    table: jnp.ndarray,
    indices: jnp.ndarray,
    weights: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Sum-mode embedding bag with padding index 0.

    Equivalent to ``torch.nn.EmbeddingBag(mode="sum", padding_idx=0)`` with
    ``per_sample_weights``: rows with index 0 contribute nothing regardless of
    weight (reference behavior relied on at ``data_embedding_layer.py:524``).

    Two formulations, chosen from static shapes (`_plane_ok`). Wide tables
    whose ``(N, Vp)`` plane fits a fixed budget: the weighted-multihot plane
    is built once (`ops.pallas_multihot.weighted_multihot`), the forward is
    ``mh @ table``, the table gradient ``mh.T @ g`` from the saved plane; slots
    of one event that hold the same index are summed in float32 before the
    plane is rounded. Otherwise ``take`` + a weighted sum, XLA's scatter-add
    backward. On a TPU v5e at N=16384 / M=24 / V=4057 / D=1024 in bf16,
    forward + table gradient take 2.6 ms on the plane, 13.5 ms with the
    gather forward and a plane built in M passes (what this replaced), 19.0 ms
    with gather and scatter (PERF.md section 6, PR 27).

    Args:
        table: ``(n_embeddings, dim)`` embedding table.
        indices: int array ``(..., M)``.
        weights: optional float array ``(..., M)`` of per-sample weights.

    Returns:
        ``(..., dim)`` summed embeddings.
    """
    pad_mask = (indices != 0).astype(table.dtype)
    w = pad_mask if weights is None else weights.astype(table.dtype) * pad_mask
    lead = indices.shape[:-1]
    n = math.prod(lead)
    if _plane_ok(table, n):
        out = _bag_2d(table, indices.reshape(n, -1), w.reshape(n, -1))
        return out.reshape(lead + (table.shape[-1],))
    gathered = jnp.take(table, indices, axis=0, mode="clip")  # (..., M, dim)
    return jnp.einsum("...md,...m->...d", gathered, w)


def grouped_embedding_bag(
    table: jnp.ndarray,
    indices: jnp.ndarray,
    group_weights: jnp.ndarray,
) -> jnp.ndarray:
    """`embedding_bag` over G weight groups sharing ONE gather.

    Dep-graph bucketing sums the same tokens into every group with
    group-specific weights; gathering once and contracting against the
    ``(..., G, M)`` weights computes the identical result with a G-fold
    smaller gather and a G-fold smaller backward into the table (a per-group
    plane and matmul under the same gate as `embedding_bag`). Padding
    index 0 contributes nothing, as in `embedding_bag`; weights are cast to
    the table dtype so mixed precision is preserved regardless of the
    weights' dtype.

    Args:
        table: ``(n_embeddings, dim)`` embedding table.
        indices: int array ``(..., M)``.
        group_weights: float array ``(..., G, M)``.

    Returns:
        ``(..., G, dim)`` summed embeddings.
    """
    pad_mask = (indices != 0).astype(table.dtype)
    w = group_weights.astype(table.dtype) * pad_mask[..., None, :]
    lead = indices.shape[:-1]
    n = math.prod(lead)
    if _plane_ok(table, n):
        out = _grouped_bag_2d(
            table, indices.reshape(n, -1), w.reshape((n,) + w.shape[-2:])
        )
        return out.reshape(lead + w.shape[-2:-1] + (table.shape[-1],))
    gathered = jnp.take(table, indices, axis=0, mode="clip")  # (..., M, dim)
    return jnp.einsum("...md,...gm->...gd", gathered, w)


def measurement_index_normalization(measurement_indices: jnp.ndarray) -> jnp.ndarray:
    """Per-row weights giving each unique measurement equal total mass.

    Reference: ``data_embedding_layer.py:316-349``. Index 0 is padding and gets
    zero weight; rows with no observations return all zeros.

    Examples:
        >>> import jax.numpy as jnp
        >>> import numpy as np
        >>> mi = jnp.asarray([[1, 2, 5, 2, 2], [1, 3, 5, 3, 0]])
        >>> np.asarray(measurement_index_normalization(mi)).round(4)
        array([[0.3333, 0.1111, 0.3333, 0.1111, 0.1111],
               [0.3333, 0.1667, 0.3333, 0.1667, 0.    ]], dtype=float32)
    """
    # Pairwise-equality formulation needs no static vocab bound:
    # counts[i, j] = #{k : mi[i, k] == mi[i, j]}.
    eq = measurement_indices[..., :, None] == measurement_indices[..., None, :]
    counts = eq.sum(axis=-1)  # (..., M)
    vals = jnp.where(measurement_indices == 0, 0.0, 1.0 / counts)
    denom = vals.sum(axis=-1, keepdims=True)
    denom = jnp.where(denom == 0, 1.0, denom)
    return vals / denom


def take_event(x: jnp.ndarray, idx) -> jnp.ndarray:
    """``x[:, idx]`` for a traced scalar ``idx``: one masked-reduce pass.

    XLA lowers ``take_along_axis`` with a broadcast scalar index to a
    per-element gather; on TPU inside a decode scan that measured ~1 ms
    per call per event (~98% of generation decode time, device profile).
    A one-hot masked reduce is a single bandwidth-bound pass and exact:
    exactly one position contributes (NaN/inf at the selected position
    are preserved; other positions never multiply in).

    ``idx`` may also be a per-row vector ``(B,)`` (the serving engine's
    per-slot cursors): row ``b`` then selects ``x[b, idx[b]]``.

    Examples:
        >>> import jax.numpy as jnp
        >>> x = jnp.asarray([[[1, 2], [3, 4], [5, 6]], [[7, 8], [9, 10], [11, 12]]])
        >>> take_event(x, jnp.asarray(1))
        Array([[ 3,  4],
               [ 9, 10]], dtype=int32)
        >>> take_event(x, jnp.asarray([1, 2]))
        Array([[ 3,  4],
               [11, 12]], dtype=int32)
    """
    if isinstance(idx, int):
        return x[:, idx]
    length = x.shape[1]
    if getattr(idx, "ndim", 0) == 1:
        # Per-row indices: one-hot per row, same masked-reduce lowering.
        oh = (jnp.arange(length)[None, :] == idx[:, None]).reshape(
            x.shape[:2] + (1,) * (x.ndim - 2)
        )
    else:
        oh = (jnp.arange(length) == idx).reshape((1, length) + (1,) * (x.ndim - 2))
    if x.dtype == jnp.bool_:
        return jnp.any(jnp.logical_and(oh, x), axis=1)
    return jnp.where(oh, x, jnp.zeros((), x.dtype)).sum(axis=1)


def gather_last(plane: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis(plane, idx, axis=-1)`` as a compare-select-reduce.

    For small index counts over a wide last axis, XLA's gather lowering is
    per-element and (inside a decode scan) measured ~1-2 ms per call per
    event; the fused compare+select+reduce is one pass over
    ``len(idx)``x``width`` compares. Exact gather semantics: a NaN at a
    selected position is preserved, unselected positions never contribute.

    Examples:
        >>> import jax.numpy as jnp
        >>> plane = jnp.asarray([[10., 11., 12., 13.], [20., 21., 22., 23.]])
        >>> gather_last(plane, jnp.asarray([[2, 0], [1, 3]]))
        Array([[12., 10.],
               [21., 23.]], dtype=float32)
    """
    oh = idx[..., :, None] == jnp.arange(plane.shape[-1])
    expanded = plane[..., None, :]
    if plane.dtype == jnp.bool_:
        return jnp.any(jnp.logical_and(oh, expanded), axis=-1)
    return jnp.where(oh, expanded, jnp.zeros((), plane.dtype)).sum(axis=-1)


def segment_starts(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """True at each packed segment's first position.

    The shared boundary idiom for packed (segment-ID) rows: position 0 starts
    a segment, as does any position whose id differs from its predecessor.
    Used by the temporal encoding (time restarts per segment), the CI
    next-event shift (a segment's first event is predicted from zeros), and
    the NA history embedding (no cross-subject history).

    Examples:
        >>> import jax.numpy as jnp
        >>> import numpy as np
        >>> seg = jnp.asarray([[0, 0, 1, 1, 1], [0, 1, 1, 2, 2]])
        >>> np.asarray(segment_starts(seg))
        array([[ True, False,  True, False, False],
               [ True,  True, False,  True, False]])
    """
    return jnp.concatenate(
        [
            jnp.ones_like(segment_ids[:, :1], dtype=bool),
            segment_ids[:, 1:] != segment_ids[:, :-1],
        ],
        axis=1,
    )
