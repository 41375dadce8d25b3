"""Grouped matrix products over the experts a chip holds.

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` lie in groups,
one after another, ``group_sizes[g]`` rows in group ``g``; ``rhs`` holds one
matrix for each of the FIRST ``rhs.shape[0]`` groups (the experts held here).
Row ``r`` of a held group ``g`` gives ``lhs[r] @ rhs[g]``; every row of a
later group (an expert that lives on another chip, or padding of the buffer)
gives zeros and costs nothing: the kernel's grid runs over the held groups'
row tiles alone, so the time follows the rows really routed here and not the
size of the buffer.

``impl`` follows `ops/impl_select.py`: the Pallas kernel (JAX's megablox
``gmm``/``tgmm``, in the sharded-groups form made for expert parallelism) on
TPU, the same kernel interpreted anywhere, or ``jax.lax.ragged_dot`` as the
XLA formulation. The custom VJP here is megablox's own without its
``existing_out`` argument.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.scopes import scope
from .impl_select import resolve_impl

# (rows, contraction, columns) of one grid step: the largest listed tile that
# divides the dimension, else the dimension itself. Chosen for the expert
# shapes 2,048 x 1,536 and 1,536 x 2,048 (`glm47flash_ep8`: 1,024 / 768 and
# 512 / 512), where they compile for the v5e and were not swept (PERF.md
# section 7, PR 28). At 2,688 x 1,856 and 1,856 x 2,688
# (`nemotron_twotower_ep16`, PR 32) only the 128 tile divides 2,688 (= 21 x
# 128) and no listed tile divides 1,856 (= 14.5 x 128), so that dimension is
# taken whole; Mosaic compiles `gmm` and `tgmm` so, and the lists are as they
# were: what `glm47flash_ep8`'s shapes pick is pinned in
# tests/models/test_hybrid_kinds.py, and a sweep at the new shapes is open
# (PERF.md section 7, PR 32).
_TILES = {"m": (512, 256, 128), "k": (1024, 512, 256, 128), "n": (768, 512, 256, 128)}
ROW_TILE = _TILES["m"][0]  # a grid step's rows wherever a walk's chunk is a multiple of it


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    def pick(size, options):
        return next((t for t in options if size % t == 0), size)

    tm = pick(m, _TILES["m"])
    if m % tm:
        raise ValueError(f"grouped_matmul: {m} rows do not divide into tiles of {_TILES['m']}")
    return tm, pick(k, _TILES["k"]), pick(n, _TILES["n"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    with scope("moe_experts"):
        return gmm(
            lhs, rhs, group_sizes, lhs.dtype, _tiling(m, k, rhs.shape[2]),
            jnp.zeros((), jnp.int32), interpret=interpret,
        )


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, residual, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = residual
    m, k = lhs.shape
    n = rhs.shape[2]
    first = jnp.zeros((), jnp.int32)
    # JAX traces a custom VJP's rules without the caller's name stack: the
    # kernels name their scope themselves, or a trace shows them under none.
    with scope("moe_experts"):
        grad_lhs = gmm(
            grad, rhs, group_sizes, lhs.dtype, _tiling(m, n, k), first, transpose_rhs=True, interpret=interpret,
        )
        grad_rhs = tgmm(
            lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype, _tiling(m, k, n), first, rhs.shape[0],
            interpret=interpret,
        )
    return grad_lhs, grad_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl: str | None = None):
    """``(m, k) x (held, k, n) -> (m, n)`` by groups of rows; see the module."""
    impl = resolve_impl(impl, "grouped_matmul")
    held = rhs.shape[0]
    if group_sizes.shape[0] < held:
        raise ValueError(f"{held} matrices for {group_sizes.shape[0]} groups")
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "xla":
        with scope("moe_experts"):
            return jax.lax.ragged_dot(lhs, rhs, group_sizes[:held])
    return _gmm(lhs, rhs, group_sizes, impl == "pallas_interpret")
