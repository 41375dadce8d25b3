"""Pallas TPU kernels for the head stack's vocabulary-plane gathers.

Device profiling of the production train step (before PR 22) attributed
~40% of the toy-shape head cost to XLA's lowering of
the multivariate-regression head's last-axis gathers and their backward
scatter on the ``(B, L, 2*vocab)`` projection plane
(``generative_layers.py`` `GaussianIndexedRegressionLayer`, mirroring the
reference's indexed-parameter extraction at
``/root/reference/EventStream/transformer/generative_layers.py:124-147``):
each ``take_along_axis`` reads the full plane (~115 MB at bench shape) yet
lowers to per-element gathers against the matmul-output layout, and the
backward materializes the plane again through a serialized scatter.

`vocab_gather` replaces both directions with a *factored one-hot
contraction*, tiled over rows so nothing but the plane itself touches HBM:

* decompose each index ``i`` into ``(i // 128, i % 128)`` — the lane
  dimension of the plane's native ``(8, 128)`` tiling;
* one-hot the high digit against the plane reshaped ``(rows, H, 128)``
  and contract on the MXU, giving a ``(rows, M, 128)`` candidate tile;
* select the low digit on the VPU and reduce.

The backward runs the transposed contraction, accumulating duplicate
indices in fp32 on the MXU (the ``take_along_axis`` fallback's scatter
accumulates in the plane dtype). One HBM pass per direction, no scatter,
and ~40x less VPU compare work than a full-width one-hot. The forward is
bit-exact vs. gather-then-upcast: each output element is a single plane
element converted to fp32.

Off-TPU (CPU test meshes, the multichip dry run) `vocab_gather` lowers to
``take_along_axis`` so traces stay portable; ``impl="pallas_interpret"``
runs the kernel in interpreter mode for platform-independent parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .impl_select import LANE, resolve_impl
from .impl_select import round_up as _round_up

# jaxlib-compat shim (TPUCompilerParams → CompilerParams) lives in
# impl_select so all kernel modules track renames in one place.

__all__ = ["vocab_gather"]

_ROW_TILE = 32


def _fwd_kernel(z_ref, ci_ref, out_ref):
    z = z_ref[...]  # (tl, vp)
    ci = ci_ref[...]  # (tl, mp) int32; -1 marks padding (one-hot row of zeros)
    tl, vp = z.shape
    mp = ci.shape[-1]
    h = vp // LANE
    hi = ci // LANE
    lo = ci % LANE
    oh_hi = (hi[..., None] == jax.lax.broadcasted_iota(jnp.int32, (tl, mp, h), 2)).astype(z.dtype)
    zr = z.reshape(tl, h, LANE)
    # (tl, mp, h) x (tl, h, LANE) -> (tl, mp, LANE): batched MXU contraction.
    # Precision: the MXU's default f32 path truncates inputs to bf16, so
    # f32 planes need HIGHEST to recover the exact element. bf16 planes are
    # exact at DEFAULT (one-hot products are exact bf16 values, fp32
    # accumulation) — and Mosaic rejects fp32 contract precision on bf16.
    prec = jax.lax.Precision.HIGHEST if z.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    cand = jax.lax.dot_general(
        oh_hi,
        zr,
        (((2,), (1,)), ((0,), (0,))),
        precision=prec,
        preferred_element_type=jnp.float32,
    )
    oh_lo = lo[..., None] == jax.lax.broadcasted_iota(jnp.int32, (tl, mp, LANE), 2)
    out_ref[...] = jnp.where(oh_lo, cand, 0.0).sum(axis=-1)


def _bwd_kernel(g_ref, ci_ref, dz_ref):
    g = g_ref[...]  # (tl, mp) fp32 cotangent
    ci = ci_ref[...]
    tl, mp = g.shape
    vp = dz_ref.shape[-1]
    h = vp // LANE
    hi = ci // LANE
    lo = ci % LANE
    oh_lo = (lo[..., None] == jax.lax.broadcasted_iota(jnp.int32, (tl, mp, LANE), 2)).astype(
        jnp.float32
    )
    spread = g[..., None] * oh_lo  # (tl, mp, LANE)
    oh_hi = (hi[..., None] == jax.lax.broadcasted_iota(jnp.int32, (tl, mp, h), 2)).astype(
        jnp.float32
    )
    # Contract over mp: (tl, mp, h) x (tl, mp, LANE) -> (tl, h, LANE).
    # Duplicate indices accumulate here, in fp32, on the MXU.
    dzr = jax.lax.dot_general(
        oh_hi,
        spread,
        (((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    dz_ref[...] = dzr.reshape(tl, vp).astype(dz_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_2d(z: jnp.ndarray, ci: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    n, v = z.shape
    m = ci.shape[-1]
    vp, mp, rows = _round_up(v, LANE), _round_up(m, LANE), _round_up(n, _ROW_TILE)
    if (rows, vp) != (n, v):
        z = jnp.pad(z, ((0, rows - n), (0, vp - v)))
    if (rows, mp) != (n, m):
        ci = jnp.pad(ci, ((0, rows - n), (0, mp - m)), constant_values=-1)
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, vp), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, mp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, mp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(z, ci.astype(jnp.int32))
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("v", "dtype", "interpret"))
def _scatter_2d(
    g: jnp.ndarray, ci: jnp.ndarray, v: int, dtype, interpret: bool = False
) -> jnp.ndarray:
    n, m = g.shape
    vp, mp, rows = _round_up(v, LANE), _round_up(m, LANE), _round_up(n, _ROW_TILE)
    if (rows, mp) != (n, m):
        g = jnp.pad(g, ((0, rows - n), (0, mp - m)))
        ci = jnp.pad(ci, ((0, rows - n), (0, mp - m)), constant_values=-1)
    dz = pl.pallas_call(
        _bwd_kernel,
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, mp), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, mp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, vp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, vp), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(g.astype(jnp.float32), ci.astype(jnp.int32))
    return dz[:n, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _vocab_gather_kernel(z, ci, interpret, v, dtype):
    out = _gather_2d(z.reshape(-1, z.shape[-1]), ci.reshape(-1, ci.shape[-1]), interpret=interpret)
    return out.reshape(ci.shape)


def _vocab_gather_fwd(z, ci, interpret, v, dtype):
    return _vocab_gather_kernel(z, ci, interpret, v, dtype), ci


def _vocab_gather_bwd(interpret, v, dtype, ci, g):
    dz = _scatter_2d(
        g.reshape(-1, g.shape[-1]),
        ci.reshape(-1, ci.shape[-1]),
        v=v,
        dtype=dtype,
        interpret=interpret,
    ).reshape(ci.shape[:-1] + (v,))
    return dz, np.zeros(ci.shape, dtype=jax.dtypes.float0)


_vocab_gather_kernel.defvjp(_vocab_gather_fwd, _vocab_gather_bwd)


def vocab_gather(z: jnp.ndarray, ci: jnp.ndarray, impl: str | None = None) -> jnp.ndarray:
    """``take_along_axis(z, ci, axis=-1)`` upcast to fp32, TPU-kernel-fast.

    Args:
        z: ``(..., V)`` projection plane (bf16 or fp32).
        ci: ``(..., M)`` int indices into the last axis. MUST be in
            ``[0, V)``: out-of-range behavior is impl-defined (the kernel
            yields 0 for negative indices — used internally for tile
            padding — while the XLA fallback wraps NumPy-style).
        impl: ``None``/"auto" (Pallas kernel on TPU backends, XLA gather
            elsewhere; overridable via ``$ESGPT_PALLAS_IMPL`` —
            `ops.impl_select`), ``"pallas"``, ``"pallas_interpret"``
            (interpreter mode, any backend — tests), or ``"xla"``.

    Returns:
        ``(..., M)`` fp32 gathered values. The backward pass produces a
        ``z``-dtype cotangent, accumulating duplicate indices in fp32 on
        the kernel path.
    """
    impl = resolve_impl(impl, "vocab_gather")
    if impl == "xla":
        return jnp.take_along_axis(z, ci, axis=-1).astype(jnp.float32)
    from ..parallel.context import per_batch_shard

    interpret, v, dtype = impl == "pallas_interpret", z.shape[-1], jnp.dtype(z.dtype)
    return per_batch_shard(
        lambda z_, ci_: _vocab_gather_kernel(z_, ci_, interpret, v, dtype), z, ci
    )
