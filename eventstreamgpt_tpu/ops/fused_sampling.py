"""Fused categorical sampling for the serving engine's decode tail.

The engine's per-step sampling tail (``serving/engine.py`` →
``generation.sampling.sample_predictions``) draws every categorical head
with ``jax.random.categorical``: per head, XLA schedules the gumbel
generation, the logits add, and the argmax as separate ops over the
``(n_slots, V)`` plane, and any top-k/top-p filtering would add a
sort + cumsum + masking chain of its own. `fused_categorical` collapses
the per-head tail into one pass:

* the **filter thresholds** (k-th-largest logit for top-k, the nucleus
  probability cutoff for top-p) are computed once with XLA's sort/top_k —
  tiny ``(rows, V) -> (rows,)`` reductions shared verbatim by every impl,
  so impl parity is exact by construction (both are *tie-inclusive*:
  every token tied with the k-th / the cutoff survives);
* the **hot plane pass** — masked-fill, gumbel add, argmax, and the
  per-slot ``where(active)``/fill merge — runs as one Pallas kernel
  (``impl="pallas"``): a single VMEM-resident sweep of the logits tile
  instead of XLA's op-by-op HBM round-trips.

Determinism contract: with no filters, every impl reproduces
``jax.random.categorical(key, logits)`` **bit-exactly** — the gumbel noise
is drawn with the identical ``gumbel(key, logits.shape, logits.dtype)``
call (threefry stays an XLA op; a kernel-internal PRNG could never match),
the add is elementwise (no reduction-order freedom), and the kernel's
max-then-first-index argmax breaks ties exactly like ``jnp.argmax``
(lowest index wins). This is what lets the engine default to the fused
tail while keeping its bit-exact ``generate()`` parity contract
(``tests/test_fused_sampling.py``, ``tests/test_engine.py``).

``impl`` resolution is shared package-wide (`ops.impl_select`,
``$ESGPT_PALLAS_IMPL``); ``"pallas_interpret"`` runs the kernel on any
backend for CPU CI.

Multi-device mesh rule (r09, retired r20): the r09 rule forced ``impl in
(None, "auto")`` to the fused-XLA tail on any multi-device mesh, because
the kernel's grid slices the slot axis — exactly the sharded mesh axis —
so plain SPMD lowering would all-gather the ``(n_slots, V)`` logits plane
into the decode hot loop. r20 retires that fallback on data-sharded
meshes: the engine now wraps the whole vmapped sampling call in
``shard_map`` over the ``data`` axis, so each device runs the kernel grid
on its own slot shard and the logits plane never crosses the mesh — the
committed ``engine_sampling_shard_dp8`` budget pins zero collectives in
the sharded decode tail (no slot-plane gather, "zero new collective
kinds" vs ``engine_dp8``). Per-shard draws are bit-identical to the
unsharded kernel's (the gumbel fold is per-row), so the engine's
``generate()`` parity contract survives sharding. The one surviving
fallback: tensor-parallel meshes keep the fused-XLA tail, because the
vocab axis itself may be ``model``-sharded and the per-row kernel would
force an all-gather of every head's logits. The speculative-decoding
verify forward samples every head through the same tail; the committed
``engine_spec_verify_dp8`` budget still pins zero new collective kinds vs
the baseline decode (``tests/test_graftcheck.py::TestTierB::
test_spec_verify_budget_has_no_new_collective_kinds``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .impl_select import LANE, resolve_impl
from .impl_select import round_up as _round_up


__all__ = ["fused_categorical", "topk_topp_mask"]

_ROW_TILE = 8
_NEG = float(jnp.finfo(jnp.float32).min)


def topk_topp_mask(
    logits: jnp.ndarray, top_k: int | None = None, top_p: float | None = None
) -> jnp.ndarray | None:
    """The boolean keep mask for tie-inclusive top-k / nucleus filtering.

    Shared by every `fused_categorical` impl (and usable standalone):

    * top-k keeps every logit ``>=`` the k-th largest (ties included);
    * top-p keeps every token whose probability ``>=`` the smallest
      probability in the nucleus — the descending-sorted prefix whose
      *exclusive* cumulative probability is still ``< top_p`` (so the
      token that crosses ``top_p`` is kept, plus all its ties).

    Returns ``None`` when both filters are off.
    """
    if top_k is None and top_p is None:
        return None
    keep = jnp.ones(logits.shape, bool)
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        k = min(int(top_k), logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        keep = keep & (logits >= kth)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        sp = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)  # descending
        csum = jnp.cumsum(sp, axis=-1)
        in_nucleus = (csum - sp) < jnp.float32(top_p)  # exclusive prefix < p
        cutoff = jnp.min(
            jnp.where(in_nucleus, sp, jnp.inf), axis=-1, keepdims=True
        )
        keep = keep & (probs >= cutoff)
    return keep


def _sample_kernel(z_ref, g_ref, keep_ref, out_ref, *, V):
    """One row tile: masked-fill + gumbel add + first-max argmax.

    The add must carry the LOGITS dtype's rounding — ``jax.random
    .categorical`` adds bf16 gumbel to bf16 logits, and a full-precision
    add orders near-tied tokens differently (a bit-exactness violation a
    multi-seed sweep catches). Every backend emulates the bf16 add as
    f32-add + round-to-bf16, so the kernel performs exactly that chain
    EXPLICITLY: a bare bf16 add would let XLA's bf16 normalization elide
    the rounding in interpret mode (observed: 9.0 + 0.65625 -> 9.65625
    instead of the reference's 9.625). The max/compare then runs on the
    exactly-converted fp32 values, preserving the native ordering/ties.
    """
    z = z_ref[...]  # (tl, Vp); padding lanes hold _NEG (-inf in bf16)
    g = g_ref[...]
    tl, vp = z.shape
    if keep_ref.shape[-1] != 1:  # (tl, 1) dummy when filters are off
        z = jnp.where(keep_ref[...] != 0, z, jnp.asarray(_NEG, z.dtype))
    # gumbel-first add order; f32 accumulate + explicit input-dtype round.
    score = (
        (g.astype(jnp.float32) + z.astype(jnp.float32)).astype(z.dtype)
    ).astype(jnp.float32)
    m = jnp.max(score, axis=-1, keepdims=True)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tl, vp), 1)
    # First occurrence of the max — jnp.argmax's tie-break.
    idx = jnp.min(jnp.where(score == m, lanes, V), axis=-1)
    out_ref[...] = idx[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sample_2d(z, g, keep, interpret=False):
    rows, V = z.shape
    vp, rp = _round_up(V, LANE), _round_up(max(rows, 1), _ROW_TILE)
    if (rp, vp) != (rows, V):
        z = jnp.pad(z, ((0, rp - rows), (0, vp - V)), constant_values=_NEG)
        g = jnp.pad(g, ((0, rp - rows), (0, vp - V)))
        if keep is not None:
            keep = jnp.pad(keep, ((0, rp - rows), (0, vp - V)))
    keep_op = (
        jnp.zeros((rp, 1), jnp.int8) if keep is None else keep.astype(jnp.int8)
    )
    out = pl.pallas_call(
        functools.partial(_sample_kernel, V=V),
        grid=(rp // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, vp), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, vp), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, keep_op.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(z, g, keep_op)
    return out[:rows, 0]


def fused_categorical(
    logits: jnp.ndarray,
    key: jax.Array,
    top_k: int | None = None,
    top_p: float | None = None,
    active: jnp.ndarray | None = None,
    fill: int = 0,
    impl: str | None = None,
) -> jnp.ndarray:
    """One fused categorical draw: filter + gumbel + argmax (+ active merge).

    Args:
        logits: ``(..., V)`` unnormalized log-probabilities.
        key: PRNG key — the draw reproduces
            ``jax.random.categorical(key, logits)`` bit-exactly when both
            filters are off (module docs).
        top_k / top_p: optional tie-inclusive filters (`topk_topp_mask`).
        active: optional boolean (broadcastable to the batch shape): rows
            with ``active=False`` return ``fill`` — the engine's per-slot
            freeze merge, fused into the sampling epilogue.
        fill: the inactive-row value.
        impl: ``None``/"auto"/"pallas"/"pallas_interpret"/"xla"
            (`ops.impl_select`; ``$ESGPT_PALLAS_IMPL`` overrides auto).

    Returns:
        ``(...,)`` int32 sampled indices.
    """
    impl = resolve_impl(impl, "fused_categorical")
    gumbel = jax.random.gumbel(key, logits.shape, logits.dtype)
    keep = topk_topp_mask(logits, top_k, top_p)
    if impl == "xla":
        masked = logits if keep is None else jnp.where(keep, logits, _NEG)
        # Verbatim jax.random.categorical tail (gumbel-first add, argmax
        # first-max tie-break) — bit-exact by construction.
        idx = jnp.argmax(gumbel + masked, axis=-1).astype(jnp.int32)
    else:
        batch_shape = logits.shape[:-1]
        V = logits.shape[-1]
        idx = _sample_2d(
            logits.reshape(-1, V),
            gumbel.reshape(-1, V),
            None if keep is None else keep.reshape(-1, V),
            interpret=impl == "pallas_interpret",
        ).reshape(batch_shape)
    if active is not None:
        idx = jnp.where(active, idx, jnp.int32(fill))
    return idx
