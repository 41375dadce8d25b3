"""Pallas TPU kernels for the chunked (SSD) scan of a Mamba-2 layer.

The recurrence and its chunked form are `ops/ssd_scan.py`'s (that module's
docstring has the algebra and stays the one entry point); this is the same
arithmetic with a chunk's decay plane ``L`` made, used and dropped in VMEM and
the carried state riding along the grid, so that no ``Q x Q`` plane is written
to HBM. Two kernels under one ``custom_vjp``:

* **Forward** (``ssd_scan_fwd``). A grid of (row, group of heads, chunk), the
  chunk axis innermost and sequential. A step holds one ``B``/``C`` group's
  ``R`` heads of one chunk: ``x`` as the lane block ``[Q, R * P]`` of ``[B, S,
  H * P]`` (a head is a slice of it: a head width of half a lane tile needs no
  padding), ``B`` and ``C`` as ``[Q, N]``. ``C B^T`` once for the group; per
  head ``W = L o C B^T o dt_j`` with ``L`` from the float32 running sums,
  masked before the exponential, and ``W x`` on the heads' inputs as they come
  (the step size rides in the plane, where it is a row), plus the skip ``D
  x``. What does not hold ``L`` is one product for all the heads of the
  group: ``C S_in`` against the state ``[N, R * P]`` (kept transposed, a head a
  lane slice, float32, in VMEM scratch, zeroed at a row's first chunk) and the
  state's update ``S <- carried * S + B^T (x o dt o to_end)``.
* **Backward** (``ssd_scan_bwd``). The same grid walked from a row's last
  chunk to its first, carrying ``dS``; ``L`` and ``C B^T`` are computed again
  in VMEM from the scan's inputs, the chunks' entering states are what the
  forward saved (``[B, G, S / Q, N, R * P]`` in the operands' dtype: 134 MB a
  layer at 16 rows of 1,024 events in bfloat16, alive for that layer's
  backward only; the primal forward writes none). ``dB`` and ``dC`` are
  summed over the group's heads inside the kernel.

Everything an event or a chunk has one number of a head (the running sums,
the step size, the decay from the chunk's start and to its end, the factor on
the carried state) is made by XLA outside the kernels on 4 MB planes, with the
masks by segment ordinal as `ops/ssd_scan.py` makes them, and handed over in
the orientation the kernel uses it in: down the sublanes (``[B, G, S, 3 R +
1]``, a head a lane, the ordinal last) for what scales an event's row, along
the lanes (``[B, G, S / Q, 2 R + 1, Q]``) for the ``j`` of ``W[i, j]``. The
kernels return the gradients of those planes and XLA's own transposes of the
cumulative sum, the exponentials and the masks turn them into ``d dt`` and
``d a``.

Operands go to the matrix unit in their own dtype with float32 accumulation;
decays, running sums and the carried state are float32: `ops/ssd_scan.py`'s
plan. On a v5e at the cell's shapes (16 rows of 1,024 events, 64 heads of 64
in 8 groups, bfloat16) the forward takes 1.97 ms a layer and the backward 3.02
(PERF.md section 6, PR 33).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.scopes import scope
from .impl_select import LANE, round_up
from .ssd_scan import chunk_decays

__all__ = ["ssd_scan_applies", "ssd_scan_kernels"]

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_COLUMNS = 3  # per head down the sublanes: running sum | decay from the chunk's start | step size x decay to its end
_ROWS = 2  # per head along the lanes: running sum | step size


class ScanSizes(NamedTuple):
    chunk: int  # events a chunk (Q)
    heads: int  # heads a group (R)
    head_dim: int  # P
    state: int  # N


def ssd_scan_applies(chunk: int, heads: int, head_dim: int, groups: int, state: int) -> bool:
    """Whether the kernels take these shapes: a chunk, a state and a group's
    heads side by side are each whole 128-lane tiles."""
    return (
        chunk % LANE == 0 and state % LANE == 0 and heads % groups == 0 and (heads // groups * head_dim) % LANE == 0
    )


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _turned(x):
    """``x.T`` in ``x``'s dtype (the transpose itself in float32, as `ops/pallas_flash.py` does)."""
    return x.astype(jnp.float32).T.astype(x.dtype)


def _visible(col, rows, sizes):
    """``[Q, Q]``: event ``j`` (lanes) is at or before ``i`` (sublanes) in
    ``i``'s segment. The ordinals ride as the planes' last column and row."""
    q, r = sizes.chunk, sizes.heads
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return (col[:, _COLUMNS * r :] == rows[_ROWS * r :, :]) & causal


def _columns(col, h, r):
    """Head ``h``'s ``[Q, 1]`` columns of the ``[Q, 3 R + 1]`` plane."""
    return tuple(col[:, k * r + h : k * r + h + 1] for k in range(_COLUMNS))


def _lower(visible, cum_c, cum_r):
    """``L``: masked before the exponential, so no difference of the wrong sign is exponentiated."""
    return jnp.exp(jnp.where(visible, cum_c - cum_r, -jnp.inf))


# A per-event factor is cheap along the lanes (a row broadcast down the sublanes) and dear down the
# sublanes (a column broadcast along the lanes: 0.3 ms a layer and factor on a v5e, PERF.md section 6, PR
# 33). So the step size rides in the decay plane, ``W = L o C B^T o dt_j``, and the heads' inputs go to
# the matrix unit as they come; what must scale an event's row (the decay from the chunk's start on ``C
# S_in``, ``dt`` times the decay to the chunk's end on the state's update) is one column a head each.
def _fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, gamma_ref, skip_ref, y_ref, *rest, sizes, save):
    _, r, p, _ = sizes
    st_ref, xe_ref = rest[-2:]
    dtype, f32 = x_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    bm, cm = b_ref[...], c_ref[...]
    bmt = _turned(bm)
    cb = _dot(cm, bmt)  # C B^T: [Q, Q]
    col, rows, skip = col_ref[...], row_ref[...], skip_ref[...]
    visible = _visible(col, rows, sizes)
    state = st_ref[...]  # S_in, transposed: [N, R * P]
    entering = state.astype(dtype)
    if save:
        rest[0][...] = entering
    from_state = _dot(cm, entering)  # C S_in: [Q, R * P]
    for h in range(r):
        at = slice(h * p, (h + 1) * p)
        cum_c, from_start, to_end = _columns(col, h, r)
        weights = (_lower(visible, cum_c, rows[h : h + 1, :]) * cb * rows[r + h : r + h + 1, :]).astype(dtype)
        x = x_ref[:, at]
        xf = x.astype(f32)
        y_ref[:, at] = (_dot(weights, x) + from_start * from_state[:, at] + skip[h : h + 1, :p] * xf).astype(dtype)
        xe_ref[:, at] = (xf * to_end).astype(dtype)
    st_ref[...] = gamma_ref[...] * state + _dot(bmt, xe_ref[...])


# The sums along the lanes that the columns' gradients are (``sum_j dW W`` a head, ``sum_p dy C S_in`` and the
# like over a head's width) are cross-lane reductions, a head at a time the dearest thing in a first version
# of this kernel (half of its 7.4 ms a layer). They are products with one-hot matrices for the whole group
# instead, which leave head ``h``'s sum in the lane the plane keeps it in. ``dW o W`` goes there rounded to
# the operands' dtype, and the same rounded plane is summed down the sublanes for ``d cum_j``: the two sums
# cancel but for a chunk's own decay, and the running sums' transpose adds them up again.
def _bwd_kernel(
    x_ref, b_ref, c_ref, col_ref, row_ref, gamma_ref, skip_ref, sin_ref, dy_ref, onehot_ref, ones_ref,
    dx_ref, db_ref, dc_ref, dcol_ref, drow_ref, dgamma_ref, dskip_ref, dst_ref, dz_ref, xe_ref, through_ref, *, sizes,
):
    q, r, p, _ = sizes
    dtype, f32 = x_ref.dtype, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    bm, cm = b_ref[...], c_ref[...]
    bmt = _turned(bm)
    cb = _dot(cm, bmt)
    col, rows, skip = col_ref[...], row_ref[...], skip_ref[...]
    visible = _visible(col, rows, sizes)
    entering = sin_ref[...]  # S_in, transposed: [N, R * P]
    dstate = dst_ref[...]  # the gradient of this chunk's outgoing state, transposed
    dstate_c = dstate.astype(dtype)
    from_state = _dot(cm, entering)  # C S_in: [Q, R * P]
    to_state = _dot(bm, dstate_c)  # B dS_out: [Q, R * P]
    dcb = jnp.zeros((q, q), f32)
    drows = jnp.zeros(rows.shape, f32)
    sublane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    for h in range(r):
        at = slice(h * p, (h + 1) * p)
        cum_c, from_start, to_end = _columns(col, h, r)
        dt_r = rows[r + h : r + h + 1, :]
        lower = _lower(visible, cum_c, rows[h : h + 1, :])
        x, dy = x_ref[:, at], dy_ref[:, at]
        through = _dot(dy, x, _NT) * lower  # dW o L: [Q, Q]
        dcb = dcb + through * dt_r
        through = through * cb
        d_dt = jnp.sum(through, axis=0, keepdims=True)  # of the step size in W
        through = (through * dt_r).astype(dtype)  # dW o W
        through_ref[:, h * q : (h + 1) * q] = through
        d_cum = -jnp.sum(through.astype(f32), axis=0, keepdims=True)  # d cum_j
        drows = jnp.where(sublane == h, d_cum, jnp.where(sublane == r + h, d_dt, drows))
        weights = (lower * cb * dt_r).astype(dtype)
        dyf = dy.astype(f32)
        dx_ref[:, at] = (_dot(_turned(weights), dy) + to_end * to_state[:, at] + skip[h : h + 1, :p] * dyf).astype(dtype)
        dz_ref[:, at] = (from_start * dyf).astype(dtype)
        xe_ref[:, at] = (x.astype(f32) * to_end).astype(dtype)
    dz, xe, xf, dyf = dz_ref[...], xe_ref[...], x_ref[...].astype(f32), dy_ref[...].astype(f32)
    sums = (
        _dot(through_ref[...], ones_ref[...])  # lanes [0, R): d cum_i
        + _dot((dyf * from_state).astype(dtype), onehot_ref[0])  # [R, 2 R): d from_start
        + _dot((xf * to_state).astype(dtype), onehot_ref[1])  # [2 R, 3 R): d (dt to_end)
    )
    dskip_ref[...] = jnp.sum(dyf * xf, axis=0, keepdims=True)
    dcol_ref[...] = sums[:, : col.shape[1]]
    drow_ref[...] = drows
    dc_ref[...] = (_dot(dcb.astype(dtype), bm) + _dot(dz, entering, _NT)).astype(dc_ref.dtype)
    db_ref[...] = (_dot(_turned(dcb).astype(dtype), cm) + _dot(xe, dstate_c, _NT)).astype(db_ref.dtype)
    dgamma_ref[...] = jnp.sum(dstate * entering.astype(f32), axis=0, keepdims=True)
    dst_ref[...] = gamma_ref[...] * dstate + _dot(_turned(cm), dz)


def _specs(sizes, n_chunks, reverse):
    """Block specs of one grid step ``(row, group, chunk)``; ``reverse`` walks a row's chunks last to first."""
    q, r, p, n = sizes
    at = (lambda c: n_chunks - 1 - c) if reverse else (lambda c: c)
    return {
        "x": pl.BlockSpec((None, q, r * p), lambda b, g, c: (b, at(c), g)),
        "bc": pl.BlockSpec((None, q, n), lambda b, g, c: (b, at(c), g)),
        "col": pl.BlockSpec((None, None, q, _COLUMNS * r + 1), lambda b, g, c: (b, g, at(c), 0)),
        "rows": pl.BlockSpec((None, None, None, _ROWS * r + 1, q), lambda b, g, c: (b, g, at(c), 0, 0)),
        "gamma": pl.BlockSpec((None, None, None, 1, r * p), lambda b, g, c: (b, g, at(c), 0, 0)),
        "skip": pl.BlockSpec((None, r, round_up(p, LANE)), lambda b, g, c: (g, 0, 0)),  # a head a row of equal lanes
        "state": pl.BlockSpec((None, None, None, n, r * p), lambda b, g, c: (b, g, at(c), 0, 0)),
        "onehot": pl.BlockSpec((_COLUMNS - 1, r * p, LANE), lambda b, g, c: (0, 0, 0)),
        "ones": pl.BlockSpec((r * q, LANE), lambda b, g, c: (0, 0)),
    }


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, interpret):
    # The trace names a Mosaic call by ``name``: the two kernels in a cell's `breakdown.device_ops` are
    # this mechanism's engagement counter.
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=interpret,
        name=name,
    )


# Jitted with everything but the arrays static, as `ops/pallas_flash.py`'s launchers are: a model's layers
# and their recomputation share one trace and one lowering of each kernel.
@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _forward(x, bm, cm, col, rows, gamma, skip, sizes, save, interpret):
    q, r, p, n = sizes
    n_rows, groups, n_chunks = rows.shape[:3]
    spec = _specs(sizes, n_chunks, False)
    out_specs, out_shape = [spec["x"]], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if save:
        out_specs.append(spec["state"])
        out_shape.append(jax.ShapeDtypeStruct((n_rows, groups, n_chunks, n, r * p), x.dtype))
    return _call(
        functools.partial(_fwd_kernel, sizes=sizes, save=save),
        "ssd_scan_fwd",
        (n_rows, groups, n_chunks),
        [spec[k] for k in ("x", "bc", "bc", "col", "rows", "gamma", "skip")],
        out_specs,
        out_shape,
        [pltpu.VMEM((n, r * p), jnp.float32), pltpu.VMEM((q, r * p), x.dtype)],
        interpret,
    )(x, bm, cm, col, rows, gamma, skip)


@functools.partial(jax.jit, static_argnums=(9, 10))
def _backward(x, bm, cm, col, rows, gamma, skip, entering, dy, sizes, interpret):
    q, r, p, n = sizes
    n_rows, groups, n_chunks = rows.shape[:3]
    spec = _specs(sizes, n_chunks, True)
    like = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    # What sums along the lanes into the columns' lanes: a head's chunk of `through` into lane h, a head's width
    # of the group's [Q, R * P] planes into lane R + h and 2 R + h.
    lanes = jnp.arange(LANE)
    ones = (lanes == (jnp.arange(r * q) // q)[:, None]).astype(x.dtype)
    onehot = (lanes == (jnp.arange(1, _COLUMNS)[:, None, None] * r + (jnp.arange(r * p) // p)[:, None])).astype(x.dtype)
    scratch = [pltpu.VMEM(shape, x.dtype) for shape in ((q, r * p), (q, r * p), (q, r * q))]
    *grads, dskip = _call(
        functools.partial(_bwd_kernel, sizes=sizes),
        "ssd_scan_bwd",
        (n_rows, groups, n_chunks),
        [spec[k] for k in ("x", "bc", "bc", "col", "rows", "gamma", "skip", "state", "x", "onehot", "ones")],
        [spec[k] for k in ("x", "bc", "bc", "col", "rows", "gamma", "gamma")],
        [like(x), like(bm), like(cm), like(col), like(rows), like(gamma), like(gamma)],
        [pltpu.VMEM((n, r * p), jnp.float32), *scratch],
        interpret,
    )(x, bm, cm, col, rows, gamma, skip, entering, dy, onehot, ones)
    dskip = dskip.sum(axis=(0, 2)).reshape(groups, r, p)  # a step's share of dD, over the rows and the chunks
    return (*grads, jnp.pad(dskip, ((0, 0), (0, 0), (0, skip.shape[-1] - p))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(x, bm, cm, col, rows, gamma, skip, sizes, interpret):
    with scope("ssm_scan"):
        return _forward(x, bm, cm, col, rows, gamma, skip, sizes, False, interpret)[0]


# JAX traces a custom_vjp's rules without the caller's name stack, so each
# rule enters the scope itself (PERF.md section 6, PR 28).
def _scan_fwd(x, bm, cm, col, rows, gamma, skip, sizes, interpret):
    with scope("ssm_scan"):
        y, entering = _forward(x, bm, cm, col, rows, gamma, skip, sizes, True, interpret)
    return y, (x, bm, cm, col, rows, gamma, skip, entering)


def _scan_bwd(sizes, interpret, residuals, dy):
    with scope("ssm_scan"):
        return _backward(*residuals, dy, sizes, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_kernels(x, dt, a, bmat, cmat, ordinal, skip, *, chunk: int, interpret: bool = False):
    """`ops.ssd_scan.ssd_scan` on whole chunks through the kernels: ``x`` ``[B,
    S, H, P]`` with ``S`` a multiple of ``chunk`` and the shapes
    `ssd_scan_applies` takes; ``skip`` ``[H]`` float32. Differentiable in
    ``x``, ``dt``, ``a``, ``bmat``, ``cmat`` and ``skip``."""
    n_rows, s, heads, p = x.shape
    groups, n = bmat.shape[2:]
    r, n_chunks, f32 = heads // groups, s // chunk, jnp.float32
    sizes = ScanSizes(chunk, r, p, n)

    od, cum, from_start, to_end, carried = chunk_decays(dt.astype(f32), a, ordinal, chunk)
    dt = dt.astype(f32).reshape(cum.shape)

    # The planes the kernels read, a group's heads together and the ordinal (exact in float32) behind them.
    by_group = lambda v: v.reshape(n_rows, n_chunks, chunk, -1, groups, r)  # noqa: E731
    od_f = jnp.broadcast_to(od.astype(f32)[..., None, None], (n_rows, n_chunks, chunk, groups, 1))
    col = jnp.stack([cum, from_start, dt * to_end], axis=3)  # [B, nc, Q, 3, H]
    col = by_group(col).transpose(0, 4, 1, 2, 3, 5).reshape(n_rows, groups, n_chunks, chunk, _COLUMNS * r)
    col = jnp.concatenate([col, od_f.transpose(0, 3, 1, 2, 4)], axis=-1).reshape(n_rows, groups, s, _COLUMNS * r + 1)
    rows = by_group(jnp.stack([cum, dt], axis=3)).transpose(0, 4, 1, 3, 5, 2)
    rows = rows.reshape(n_rows, groups, n_chunks, _ROWS * r, chunk)
    rows = jnp.concatenate([rows, od_f.transpose(0, 3, 1, 4, 2)], axis=3)  # [B, G, nc, 2 R + 1, Q]
    gamma = jnp.repeat(carried.reshape(n_rows, n_chunks, groups, r).transpose(0, 2, 1, 3), p, axis=-1)[:, :, :, None]
    skip = jnp.broadcast_to(skip.astype(f32).reshape(groups, r, 1), (groups, r, round_up(p, LANE)))
    y = _scan(
        x.reshape(n_rows, s, heads * p), bmat.reshape(n_rows, s, groups * n), cmat.reshape(n_rows, s, groups * n),
        col, rows, gamma, skip, sizes, interpret,
    )  # fmt: skip
    return y.reshape(x.shape)
