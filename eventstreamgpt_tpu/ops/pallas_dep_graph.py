"""Pallas TPU kernel for the NA per-event dependency-graph attention walk.

The fused-XLA formulation (`ops.band_attention._dep_graph_attention_xla`,
the r06 lever) already removed the dot_general relayout friction from the
``(B·L, G+1)`` walk, but XLA still schedules it as a handful of fusion
scopes with HBM round-trips between the logits, softmax, and PV stages.
This kernel is the deferred hand-tiled swing (BASELINE r06 "deliberately
deferred"): one grid pass over row tiles, with the causal/window mask, the
fp32 softmax, attention dropout, and both contractions resident in VMEM —
each Q/K/V element is read from HBM exactly once per direction.

Geometry: the graph depth ``S = G+1`` and query count ``Q`` are tiny
static constants (4 and 3 at the bench shape), so the kernel unrolls them
as Python loops, and every in-flight tensor is 2D: a lane-dense
``(row_tile, H*D)`` plane, or a per-head ``(row_tile, H)`` plane reached by
a 0/1 selector matmul on the otherwise idle MXU. Mosaic refuses the
``(rows, H*D) -> (rows, H, D)`` shape cast unless ``D`` is a multiple of the
128-lane tile (PR 22: the first compile for a real chip), so no tensor in
the body has more than two dimensions. The row tile is derived from the
shapes against a VMEM budget (`_geometry`), not a constant.

Numerics mirror the XLA formulation op for op (upcast-then-multiply
logits, fp32 softmax, probs dropped to the value dtype before the fp32 PV
accumulation), so the fp32 parity contract vs `dep_graph_attention` is
**bit-exact** and bf16 is exact to the same roundings — pinned by
``tests/test_pallas_dep_graph.py``. The backward is a second hand kernel
(`pallas_heads` custom_vjp precedent) recomputing the softmax from the
saved q/k/v residuals (S is tiny — recompute is cheaper than an
``(N, Q, S, H)`` probs round-trip through HBM) and emitting dq/dk/dv in
one pass, matching XLA's autodiff of the reference formulation.

Dropout rides as a precomputed keep-mask (+ static rate): the mask is
drawn OUTSIDE the kernel from the module's dropout rng (threefry stays an
XLA op), and both impls apply the identical ``where(keep, p/keep_prob, 0)``
— so kernel-vs-XLA parity holds under dropout too, which a kernel-internal
PRNG could never guarantee.

``interpret=True`` (``impl="pallas_interpret"``) runs the same kernel code
on any backend — CPU CI exercises the kernel in tier-1 under the
``pallas`` marker; ``impl`` resolution is shared package-wide
(`ops.impl_select`, ``$ESGPT_PALLAS_IMPL``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .impl_select import LANE
from .impl_select import round_up as _round_up

__all__ = ["dep_graph_attention_pallas"]

# Scoped-VMEM ceiling the kernel asks Mosaic for, and the share of it the
# row tile is sized against (the estimate below counts operand blocks and
# named temporaries, not the compiler's own spill slots — hence the slack).
# 48 MiB is below the physical VMEM of every current TPU generation
# (128 MiB on v5e/v6e, 64 MiB on v7x); the compiler's default scoped limit
# (16 MiB on v5e) is what the old constant 256-row tile overflowed at
# H*D = 1024.
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_VMEM_TILE_BUDGET = _VMEM_LIMIT_BYTES // 2
_ROW_ALIGN = 32  # int8 keep-mask sublane packing (covers bf16's 16, f32's 8)
_MAX_ROW_TILE = 512

_HI = jax.lax.Precision.HIGHEST


def _row_tile(N: int, io_cols: int, itemsize: int, f32_cols: int) -> int:
    """Rows per grid step, derived from the shapes against the VMEM budget.

    ``io_cols`` counts the lanes of every HBM-blocked operand and result
    (double-buffered by the pipeline), ``f32_cols`` the lanes of the fp32
    temporaries the kernel body keeps live per row.
    """
    per_row = 2 * io_cols * itemsize + 4 * f32_cols
    tile = max(_ROW_ALIGN, _VMEM_TILE_BUDGET // per_row // _ROW_ALIGN * _ROW_ALIGN)
    return min(tile, _MAX_ROW_TILE, _round_up(max(N, 1), _ROW_ALIGN))


def _mask_val(qi: int, s: int, q_offset: int, window: int | None) -> bool:
    """The static causal/window mask bit for query qi vs graph position s."""
    q_pos = qi + q_offset
    ok = s <= q_pos
    if window is not None:
        ok = ok and s > q_pos - window
    return ok


def _head_selectors(H: int, D: int, HDp: int, HP: int):
    """0/1 matrices moving between the lane-dense and the per-head space.

    ``seg (HDp, HP)`` sums each head's ``D`` lanes into that head's column
    (``x @ seg``); ``seg.T`` broadcasts a per-head scalar back over its
    lanes. Padding lanes (>= H*D) and padding columns (>= H) select nothing.
    Mosaic refuses the ``(tl, H*D) -> (tl, H, D)`` shape cast unless ``D``
    is a multiple of the 128-lane tile, so the per-head reduction rides the
    otherwise idle MXU instead, and every in-flight tensor stays 2D.
    """


    def selector(shape, lane_axis):
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_axis)
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_axis)
        return ((lane >= head * D) & (lane < (head + 1) * D) & (head < H)).astype(jnp.float32)

    return selector((HDp, HP), 0), selector((HP, HDp), 1)


def _dot(a, b):
    # Selector matmuls must not round their fp32 operand to one bf16 pass.
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=_HI)


def _keep_selector(drop, qi: int, s: int, S: int, H: int, HP: int):
    """The (tl, HP) 0/1 keep plane of pair (qi, s) out of the packed fp32
    ``(tl, DP)`` mask plane."""
    DP = drop.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (DP, HP), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (DP, HP), 1)
    sel = ((row == (qi * S + s) * H + col) & (col < H)).astype(jnp.float32)
    return _dot(drop, sel) > 0.5


def _softmax_terms(q_ref, k_ref, qi, *, S, HDp, seg, q_offset, window):
    """fp32 query block + per-position softmax probabilities (None = masked)."""
    qf = q_ref[:, qi * HDp : (qi + 1) * HDp].astype(jnp.float32)  # (tl, HDp)
    # Unrolled masked logits over the S graph positions (fp32, matching the
    # XLA path's upcast-then-multiply — exact for bf16 inputs).
    logits = []
    for s in range(S):
        if _mask_val(qi, s, q_offset, window):
            kf = k_ref[:, s * HDp : (s + 1) * HDp].astype(jnp.float32)
            logits.append(_dot(qf * kf, seg))  # (tl, HP)
        else:
            logits.append(None)  # statically masked: -inf
    # fp32 softmax over the unmasked set. jax.nn.softmax subtracts the
    # masked max; with -inf entries exp(-inf - m) == 0 exactly, so skipping
    # masked terms reproduces it.
    m = None
    for lg in logits:
        if lg is not None:
            m = lg if m is None else jnp.maximum(m, lg)
    exps = [None if lg is None else jnp.exp(lg - m) for lg in logits]
    denom = None
    for e in exps:
        if e is not None:
            denom = e if denom is None else denom + e
    return qf, [None if e is None else e / denom for e in exps]


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    drop_ref,
    out_ref,
    *,
    Q,
    S,
    H,
    D,
    q_offset,
    window,
    keep_prob,
    has_drop,
):
    """One row tile: logits -> masked fp32 softmax -> dropout -> PV.

    Block shapes: q (tl, Q*HDp), k/v (tl, S*HDp), drop (tl, DP) int8 keep
    mask (or a (tl, 1) dummy when dropout is off — ``has_drop`` is a STATIC
    flag, not a shape inference), out (tl, Q*HDp); ``HDp``/``DP`` are H*D
    and Q*S*H padded to the 128-lane tile by the wrapper. Every tensor in
    the body is 2D: lane-dense ``(tl, HDp)`` planes, or per-head
    ``(tl, HP)`` planes reached through `_head_selectors`.
    """
    HDp = q_ref.shape[1] // Q
    HP = _round_up(H, LANE)
    v_dtype = v_ref.dtype
    seg, seg_t = _head_selectors(H, D, HDp, HP)
    drop = drop_ref[...].astype(jnp.float32) if has_drop else None

    for qi in range(Q):
        _, probs = _softmax_terms(
            q_ref, k_ref, qi, S=S, HDp=HDp, seg=seg, q_offset=q_offset, window=window
        )
        acc = jnp.zeros((q_ref.shape[0], HDp), jnp.float32)
        for s, p in enumerate(probs):
            if p is None:
                continue
            if has_drop:
                p = jnp.where(_keep_selector(drop, qi, s, S, H, HP), p / keep_prob, 0.0)
            # Match the XLA path's probs dtype drop before the fp32 PV
            # accumulation (bf16 round-trip under bf16 values).
            p = p.astype(v_dtype).astype(jnp.float32)
            vf = v_ref[:, s * HDp : (s + 1) * HDp].astype(jnp.float32)
            acc = acc + _dot(p, seg_t) * vf
        out_ref[:, qi * HDp : (qi + 1) * HDp] = acc.astype(v_dtype)


def _bwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    drop_ref,
    g_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    *,
    Q,
    S,
    H,
    D,
    q_offset,
    window,
    keep_prob,
    has_drop,
):
    """Backward in one pass: recompute the tiny softmax, emit dq/dk/dv.

    Mirrors XLA's autodiff of the reference formulation: all intermediate
    cotangents accumulate in fp32; the probs' value-dtype round-trip in the
    forward re-enters the chain as a cast (its derivative is the identity
    convert, exactly as XLA differentiates ``astype``).
    """
    tl = q_ref.shape[0]
    HDp = q_ref.shape[1] // Q
    HP = _round_up(H, LANE)
    v_dtype = v_ref.dtype
    seg, seg_t = _head_selectors(H, D, HDp, HP)
    drop = drop_ref[...].astype(jnp.float32) if has_drop else None

    def blk(ref, i):
        return ref[:, i * HDp : (i + 1) * HDp].astype(jnp.float32)

    dk_acc = [jnp.zeros((tl, HDp), jnp.float32) for _ in range(S)]
    dv_acc = [jnp.zeros((tl, HDp), jnp.float32) for _ in range(S)]
    for qi in range(Q):
        qf, probs = _softmax_terms(
            q_ref, k_ref, qi, S=S, HDp=HDp, seg=seg, q_offset=q_offset, window=window
        )  # probs are pre-dropout
        gf = blk(g_ref, qi)  # (tl, HDp) cotangent

        # dP (post-dropout, post-cast) = <g, v_s>; chain back through the
        # value-dtype cast (identity-convert) and the dropout select.
        dp = [None] * S
        for s, p in enumerate(probs):
            if p is None:
                continue
            pd = p
            dps = _dot(gf * blk(v_ref, s), seg)  # (tl, HP)
            if has_drop:
                keep = _keep_selector(drop, qi, s, S, H, HP)
                pd = jnp.where(keep, pd / keep_prob, 0.0)
                dps = jnp.where(keep, dps / keep_prob, 0.0)
            pd_cast = pd.astype(v_dtype).astype(jnp.float32)
            dv_acc[s] = dv_acc[s] + _dot(pd_cast, seg_t) * gf
            dp[s] = dps
        # Softmax backward on the pre-dropout probs:
        # dL_s = P_s * (dP_s - sum_t P_t dP_t).
        inner = None
        for s, p in enumerate(probs):
            if p is None:
                continue
            term = p * dp[s]
            inner = term if inner is None else inner + term
        dq_acc = jnp.zeros((tl, HDp), jnp.float32)
        for s, p in enumerate(probs):
            if p is None:
                continue
            dl = _dot(p * (dp[s] - inner), seg_t)  # (tl, HDp) fp32
            dq_acc = dq_acc + dl * blk(k_ref, s)
            dk_acc[s] = dk_acc[s] + dl * qf
        dq_ref[:, qi * HDp : (qi + 1) * HDp] = dq_acc.astype(dq_ref.dtype)
    for s in range(S):
        dk_ref[:, s * HDp : (s + 1) * HDp] = dk_acc[s].astype(dk_ref.dtype)
        dv_ref[:, s * HDp : (s + 1) * HDp] = dv_acc[s].astype(dv_ref.dtype)


def _flatten_rows(x, rows, HDp):
    """(N, P, H, D) -> (rows, P*HDp): rows padded to the tile, H*D to lanes."""
    N, P, H, D = x.shape
    x = x.reshape(N, P, H * D)
    if rows != N or HDp != H * D:  # graftcheck: allow GC004 -- static Python ints (shapes rounded up to the tile), not traced values
        x = jnp.pad(x, ((0, rows - N), (0, 0), (0, HDp - H * D)))
    return x.reshape(rows, P * HDp)


def _unflatten_rows(x2, N, P, H, D):
    """Inverse of `_flatten_rows`: drop the row and lane padding."""
    return x2.reshape(x2.shape[0], P, -1)[:N, :, : H * D].reshape(N, P, H, D)


def _drop_operand(dropout_mask, rows):
    """The dropout keep-mask as an int8 block operand, or a (rows, 1) dummy.

    Block shapes are static per compiled kernel, so "dropout off" rides a
    1-lane dummy rather than a second pallas_call variant. The packed
    ``Q*S*H`` trailing width pads up to the lane tile (`_keep_selector`
    contracts over it).
    """
    if dropout_mask is None:
        return jnp.zeros((rows, 1), jnp.int8)
    N = dropout_mask.shape[0]
    flat = dropout_mask.astype(jnp.int8).reshape(N, -1)
    return jnp.pad(flat, ((0, rows - N), (0, _round_up(flat.shape[1], LANE) - flat.shape[1])))


def _geometry(N, Q, S, H, D, itemsize, backward):
    """(row tile, padded rows, padded H*D) for one direction of the kernel."""
    HDp = _round_up(H * D, LANE)
    HP = _round_up(H, LANE)
    DP = _round_up(Q * S * H, LANE)
    if backward:  # graftcheck: allow GC004 -- static Python bool naming the kernel direction, not a traced value
        # in: q, k, v, g; out: dq, dk, dv.  live fp32: dk/dv accumulators,
        # dq_acc, qf, gf + a few product temporaries; per-head planes.
        io_cols = (3 * Q + 4 * S) * HDp + DP
        f32_cols = (2 * S + 8) * HDp + (4 * S + 6) * HP + DP
    else:
        io_cols = (2 * Q + 2 * S) * HDp + DP
        f32_cols = 6 * HDp + (3 * S + 4) * HP + DP
    tile = _row_tile(N, io_cols, itemsize, f32_cols)
    return tile, _round_up(max(N, 1), tile), HDp


def _row_spec(tile, x2):
    return pl.BlockSpec((tile, x2.shape[1]), lambda i: (i, 0))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
    )


@functools.partial(
    jax.jit,
    static_argnames=("tile", "q_offset", "window", "keep_prob", "has_drop", "interpret", "shapes"),
)
def _fwd_call(q2, k2, v2, drop2, *, tile, q_offset, window, keep_prob, has_drop, interpret, shapes):
    (Q, S, H, D) = shapes
    kern = functools.partial(
        _fwd_kernel,
        Q=Q,
        S=S,
        H=H,
        D=D,
        q_offset=q_offset,
        window=window,
        keep_prob=keep_prob,
        has_drop=has_drop,
    )
    return pl.pallas_call(
        kern,
        grid=(q2.shape[0] // tile,),
        in_specs=[_row_spec(tile, x) for x in (q2, k2, v2, drop2)],
        out_specs=_row_spec(tile, q2),
        out_shape=jax.ShapeDtypeStruct(q2.shape, v2.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="dep_graph_attention_fwd",
    )(q2, k2, v2, drop2)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "q_offset", "window", "keep_prob", "has_drop", "interpret", "shapes"),
)
def _bwd_call(
    q2, k2, v2, drop2, g2, *, tile, q_offset, window, keep_prob, has_drop, interpret, shapes
):
    (Q, S, H, D) = shapes
    kern = functools.partial(
        _bwd_kernel,
        Q=Q,
        S=S,
        H=H,
        D=D,
        q_offset=q_offset,
        window=window,
        keep_prob=keep_prob,
        has_drop=has_drop,
    )
    return pl.pallas_call(
        kern,
        grid=(q2.shape[0] // tile,),
        in_specs=[_row_spec(tile, x) for x in (q2, k2, v2, drop2, g2)],
        out_specs=[_row_spec(tile, x) for x in (q2, k2, v2)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q2, k2, v2)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="dep_graph_attention_bwd",
    )(q2, k2, v2, drop2, g2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _dep_graph_pallas(query, key, value, dropout_mask, q_offset, window, keep_prob, interpret):
    N, Q, H, D = query.shape
    S = key.shape[1]
    tile, rows, HDp = _geometry(N, Q, S, H, D, query.dtype.itemsize, backward=False)
    out = _fwd_call(
        _flatten_rows(query, rows, HDp),
        _flatten_rows(key, rows, HDp),
        _flatten_rows(value, rows, HDp),
        _drop_operand(dropout_mask, rows),
        tile=tile,
        q_offset=q_offset,
        window=window,
        keep_prob=keep_prob,
        has_drop=dropout_mask is not None,
        interpret=interpret,
        shapes=(Q, S, H, D),
    )
    return _unflatten_rows(out, N, Q, H, D)


def _dep_graph_pallas_fwd(query, key, value, dropout_mask, q_offset, window, keep_prob, interpret):
    out = _dep_graph_pallas(
        query, key, value, dropout_mask, q_offset, window, keep_prob, interpret
    )
    return out, (query, key, value, dropout_mask)


def _dep_graph_pallas_bwd(q_offset, window, keep_prob, interpret, res, g):
    query, key, value, dropout_mask = res
    N, Q, H, D = query.shape
    S = key.shape[1]
    tile, rows, HDp = _geometry(N, Q, S, H, D, query.dtype.itemsize, backward=True)
    dq, dk, dv = _bwd_call(
        _flatten_rows(query, rows, HDp),
        _flatten_rows(key, rows, HDp),
        _flatten_rows(value, rows, HDp),
        _drop_operand(dropout_mask, rows),
        _flatten_rows(g.astype(value.dtype), rows, HDp),
        tile=tile,
        q_offset=q_offset,
        window=window,
        keep_prob=keep_prob,
        has_drop=dropout_mask is not None,
        interpret=interpret,
        shapes=(Q, S, H, D),
    )
    ddrop = None
    if dropout_mask is not None:
        import numpy as np

        ddrop = np.zeros(dropout_mask.shape, dtype=jax.dtypes.float0)
    return (
        _unflatten_rows(dq, N, Q, H, D),
        _unflatten_rows(dk, N, S, H, D),
        _unflatten_rows(dv, N, S, H, D),
        ddrop,
    )


_dep_graph_pallas.defvjp(_dep_graph_pallas_fwd, _dep_graph_pallas_bwd)


def dep_graph_attention_pallas(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    q_offset: int = 0,
    window: int | None = None,
    dropout_mask: jnp.ndarray | None = None,
    dropout_rate: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """The hand-tiled kernel behind ``dep_graph_attention(impl="pallas")``.

    Same contract as the XLA formulation (``(N, Q, H, D)`` queries against
    ``(N, S, H, D)`` keys/values, unscaled logits, fp32 softmax); see
    `ops.band_attention.dep_graph_attention` for the dispatching wrapper
    and the dropout-mask convention.
    """
    keep_prob = 1.0 - float(dropout_rate)
    if dropout_mask is None:
        keep_prob = 1.0
    return _dep_graph_pallas(
        query, key, value, dropout_mask, int(q_offset), window, keep_prob, bool(interpret)
    )
