"""The state-space recurrence of a Mamba-2 layer in its chunked (SSD) form.

Per head, with a state ``S`` of ``P x N``, a decay ``a_t = dt_t * A <= 0``
(as a logarithm) and the input already scaled, ``dt_t * x_t``::

    S_t = exp(a_t) S_{t-1} + (dt_t x_t) B_t^T,    y_t = S_t C_t

with **``S`` zero before a segment's first event**: a packed row holds several
subjects, and nothing of one reaches the next. The caller hands the segments
over as an *ordinal* that never decreases along a row (the running count of
segment starts), so that two positions ``j <= i`` lie in one segment exactly
where their ordinals are equal, whatever the ids were.

The chunked form, at a chunk of ``Q`` events (the published 128): inside a
chunk ``Y = (L o C B^T) X`` with ``L[i, j] = exp(cum_i - cum_j)`` for ``j <= i``
in ``i``'s segment and 0 elsewhere (``cum`` the running sum of ``a`` inside the
chunk); a chunk's own end state ``sum_j exp(cum_last - cum_j) X_j B_j^T`` over
the last event's segment; the carried state ``S_in(c + 1) = keep(c) exp(cum_last(c))
S_in(c) + S_end(c)``, kept only where the chunk holds no segment start after
the state's segment; and ``C_i S_in exp(cum_i)`` for the events of a chunk
that still belong to the segment the state came from. The products are batched
matrix products in the operands' dtype with float32 accumulation; the decays
and their running sums are float32, and a masked entry is masked before the
exponential, so no difference of the wrong sign is ever exponentiated.

**What runs where.** On a TPU backend, at shapes whose chunk, state and group
of heads are whole 128-lane tiles (`pallas_ssd_scan.ssd_scan_applies`), the scan
is `ops/pallas_ssd_scan.py`'s two Mosaic kernels: a chunk's ``L`` is made, used
and dropped in VMEM, the carried state rides along the grid, and the backward
is the kernels' own. `ops.impl_select.resolve_impl` chooses as for every op
(``$ESGPT_PALLAS_IMPL=pallas_interpret`` runs the same kernels in Pallas'
interpreter on any backend). Elsewhere, and at shapes the kernels do not take
(a TPU backend says so once), the scan is the XLA formulation below, the
portable reference the kernels are tested against: batched products around
``L``, which is ``Q x Q`` a head and chunk (537 MB in float32 at 16 rows of
1,024 events and 64 heads), so the rows are walked in blocks (`lax.map` over a
checkpointed body: what is kept for the backward is the block's inputs, and
the temporaries are a block's), sized so that a block's ``L`` stays under
`_L_BYTES`. The heads' skip ``D x_t`` is the scan's to add, on either path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .impl_select import resolve_impl

_L_BYTES = 2**27  # a block of rows' float32 `L`
_NEVER = -2  # an ordinal no event has (a row's first chunk takes over no state)


def _decay(mask, log):
    """``exp(log)`` where ``mask``, 0 elsewhere: masked before the exponential."""
    return jnp.exp(jnp.where(mask, log, -jnp.inf))


def chunk_decays(dt, a, ordinal, chunk: int):
    """What a chunk's events and the chunk itself have one float32 number a
    head of, from ``dt`` ``[b, S, H]``, ``a`` ``[H]`` and the ordinals ``[b,
    S]``: ``(od, cum, from_start, to_end, carried)`` with ``od`` ``[b, nc, Q]``
    the ordinals by chunk, ``cum`` ``[b, nc, Q, H]`` the running sum of ``dt a``
    inside the chunk (inclusive), ``from_start = exp(cum)`` on the events still
    in the segment of the chunk before's last event and ``to_end = exp(cum_last
    - cum)`` on those in the segment of this chunk's last (0 elsewhere), and
    ``carried`` ``[b, nc, H]`` the factor on the state a chunk takes over,
    ``exp(cum_last)`` where the chunk holds no later segment's start."""
    b_, s, heads = dt.shape
    nc = s // chunk
    od = ordinal.reshape(b_, nc, chunk)
    cum = jnp.cumsum((dt * a).reshape(b_, nc, chunk, heads), axis=2)
    last, last_ord = cum[:, :, -1], od[:, :, -1]
    prev_ord = jnp.concatenate([jnp.full_like(last_ord[:, :1], _NEVER), last_ord[:, :-1]], axis=1)
    from_start = _decay((od == prev_ord[..., None])[..., None], cum)
    to_end = _decay((od == last_ord[..., None])[..., None], last[:, :, None] - cum)
    carried = jnp.where((last_ord == prev_ord)[..., None], jnp.exp(last), 0.0)
    return od, cum, from_start, to_end, carried


def _rows_block(x, dt, bmat, cmat, ordinal, a, *, chunk: int):
    """The scan over whole rows ``[b, S, ...]``, ``S`` a multiple of ``chunk``."""
    b_, s, heads, p = x.shape
    groups, n = bmat.shape[2:]
    r, nc, f32, dtype = heads // groups, s // chunk, jnp.float32, x.dtype
    grouped = lambda v: v.reshape(b_, nc, chunk, groups, r, -1)  # noqa: E731  (per head -> per group and head of it)

    xs = grouped((x.astype(f32) * dt[..., None]).astype(dtype))  # dt_t x_t
    bm, cm = bmat.reshape(b_, nc, chunk, groups, n), cmat.reshape(b_, nc, chunk, groups, n)
    od, cum, from_start, to_end, carried = chunk_decays(dt, a, ordinal, chunk)

    # Inside a chunk.
    cum_h = cum.transpose(0, 1, 3, 2)  # [b, nc, H, Q]
    visible = (od[:, :, :, None] == od[:, :, None, :]) & jnp.tril(jnp.ones((chunk, chunk), bool))
    lower = _decay(visible[:, :, None], cum_h[..., :, None] - cum_h[..., None, :])  # L: [b, nc, H, Q, Q]
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cm, bm, preferred_element_type=f32)
    weights = (lower.reshape(b_, nc, groups, r, chunk, chunk) * cb[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bcgrqk,bckgrp->bcqgrp", weights, xs, preferred_element_type=f32)

    # A chunk's own end state, and the state carried into every chunk.
    ended = jnp.einsum(
        "bckgn,bckgrp->bcgrpn", bm, (xs.astype(f32) * grouped(to_end)).astype(dtype), preferred_element_type=f32
    )
    carried = carried.reshape(b_, nc, groups, r, 1, 1)

    def step(state, per_chunk):
        factor, end = per_chunk
        return factor * state + end, state

    _, entering = jax.lax.scan(step, jnp.zeros_like(ended[:, 0]), (carried.swapaxes(0, 1), ended.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1).astype(dtype)  # S_in: [b, nc, G, R, P, N]

    y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", cm, entering, preferred_element_type=f32) * grouped(from_start)
    return y.astype(dtype).reshape(b_, s, heads, p)


def ssd_scan(x, dt, a, bmat, cmat, ordinal, *, chunk: int, skip=None):
    """``y_t = S_t C_t + D x_t`` of the recurrence in the module's docstring.

    Args:
        x: ``[B, S, H, P]``, the heads' inputs (zero on a padding slot).
        dt: ``[B, S, H]`` float32, the step sizes after the softplus (zero on
            a padding slot).
        a: ``[H]`` float32, negative.
        bmat, cmat: ``[B, S, G, N]``; head ``h`` reads group ``h // (H / G)``.
        ordinal: ``[B, S]`` int32, never decreasing along a row and at least 0:
            the running count of segment starts.
        chunk: events a chunk; a row is padded to whole chunks here.
        skip: ``[H]`` float32, the heads' ``D``; left out, no ``D x_t``.

    Returns ``[B, S, H, P]`` in ``x``'s dtype. Differentiable in ``x``, ``dt``,
    ``a``, ``bmat``, ``cmat`` and ``skip``.
    """
    from ..parallel.context import per_batch_shard
    from .pallas_ssd_scan import ssd_scan_applies, ssd_scan_kernels

    seq_len = x.shape[1]
    chunk = min(chunk, seq_len)
    pad = -seq_len % chunk
    if pad:  # whole chunks: the tail takes no step and belongs to no segment
        tail = lambda v, fill=0: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2), constant_values=fill)  # noqa: E731
        x, dt, bmat, cmat, ordinal = tail(x), tail(dt), tail(bmat), tail(cmat), tail(ordinal, -1)

    heads, groups = x.shape[2], bmat.shape[2]
    impl = resolve_impl(None, "ssd_scan")
    takes = ssd_scan_applies(chunk, heads, x.shape[3], groups, bmat.shape[3])
    if impl == "pallas" and not takes:
        import warnings

        warnings.warn(
            "the state-space scan is taking XLA's batched products and not its kernels: "
            f"chunk {chunk}, {heads} heads of {x.shape[3]} in {groups} groups, state {bmat.shape[3]} (the kernels need "
            "a chunk, a state and a group's heads side by side of whole 128-lane tiles)",
            stacklevel=2,
        )

    skipped, skip = skip is not None, jnp.zeros_like(a) if skip is None else skip

    def scan_rows(x, dt, bmat, cmat, ordinal, a, skip):
        if impl != "xla" and takes:
            return ssd_scan_kernels(
                x, dt, a, bmat, cmat, ordinal, skip, chunk=chunk, interpret=impl == "pallas_interpret"
            )
        rows, s, heads = dt.shape
        per_row = (s // chunk) * heads * chunk * chunk * 4
        block = max(d for d in range(1, rows + 1) if rows % d == 0 and (d == 1 or d * per_row <= _L_BYTES))
        body = jax.checkpoint(functools.partial(_rows_block, a=a, chunk=chunk))
        blocks = jax.tree_util.tree_map(
            lambda v: v.reshape((rows // block, block) + v.shape[1:]), (x, dt, bmat, cmat, ordinal)
        )
        y = jax.lax.map(lambda args: body(*args), blocks).reshape(x.shape)
        return (y.astype(jnp.float32) + skip[:, None] * x.astype(jnp.float32)).astype(x.dtype) if skipped else y

    y = per_batch_shard(scan_rows, x, dt, bmat, cmat, ordinal, replicated=(a, skip))
    return y[:, :seq_len]
