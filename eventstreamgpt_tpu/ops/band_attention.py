"""Narrow-window local attention as a chunked band einsum.

The reference's "local" attention layers (sliding window, default 32 —
``/root/reference/EventStream/transformer/transformer.py:109-118``) touch a
band of at most ``window`` keys per query, so any formulation that sweeps an
``(L, L)`` plane — blocked or not — is overhead. Device measurements at
production width before PR 22 (B=8, L=1024, window=32, fwd+bwd per layer; not
measured on the current code):

* splash kernel, best block shape (its 128x128 default): 1.45 ms
* this band einsum: measured ~35-45% faster in the same windows

The trick: reshape the sequence into window-sized chunks; a query in chunk
``n`` attends only keys in chunks ``n-1`` and ``n`` (which cover exactly the
causal window ``(q - W, q]``), so the logits plane is ``(C, 2C)`` per chunk
instead of any ``(L, L)`` structure. Everything is a dense einsum: XLA fuses
the masking/softmax, differentiates it natively, and the formulation runs on
every backend (the parity test pins it against the full-mask einsum path on
CPU, exact to bf16 rounding).

Packed-segment convention matches the fused kernels in
``models/transformer.py``: padding rides as segment id -1, so padded queries
attend only among padded keys and stay finite; a chunk's "previous" chunk at
row start is given segment -2 so it can never match.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["band_local_attention", "dep_graph_attention"]


def band_local_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    segment_ids: jnp.ndarray,
    window: int,
    chunk_size: int | None = None,
) -> jnp.ndarray:
    """Exact sliding-window attention: ``k <= q`` and ``k > q - window``.

    Args:
        query / key / value: ``(B, H, L, D)`` with ``L`` divisible by the
            chunk size (``window`` itself under the default).
        segment_ids: ``(B, L)`` int segment ids; queries attend only keys of
            the same segment (use -1 for padding positions).
        window: the local window width ``W``.
        chunk_size: the chunk width ``C >= W`` (must divide ``L``). Any such
            ``C`` computes the identical result — two consecutive chunks
            always cover the window — so it is purely a performance knob:
            fatter chunks mean fewer, bigger einsums against a wider
            ``(C, 2C)`` masked plane. ``None`` means ``C = W``, which wins
            at the *step* level: a standalone layer microbench favored
            C=128 at head_dim 128 (0.99 vs 1.55 ms/layer fwd+bwd), but an
            interleaved A/B of the full rematerialized width train step
            measured C=W 2 ms/step faster (108.7 vs 110.9 at
            hidden-1024/12L) — fatter chunks lose once remat doubles the
            forward and XLA fuses the band into its neighbors.

    Returns:
        ``(B, H, L, D)`` attention outputs (same dtype as ``value``).
        Logits are NOT scaled by ``1/sqrt(D)`` (GPT-Neo lineage, matching the
        einsum path); softmax statistics are computed in fp32.
    """
    B, H, L, D = query.shape
    if chunk_size is None:
        chunk_size = window
    if chunk_size < window:
        raise ValueError(
            f"chunk_size {chunk_size} must be >= window {window}: a chunk and its "
            "predecessor must cover the full attention window"
        )
    C = chunk_size
    if L % C != 0:
        raise ValueError(
            f"sequence length {L} must be divisible by the chunk size {C} "
            f"(window {window})"
        )
    nc = L // C

    def chunk(x):  # (B, H, L, D) -> (B, H, nc, C, D)
        return x.reshape(B, H, nc, C, D)

    def with_prev(x):  # (B, H, nc, C, D) -> (B, H, nc, 2C, D)
        prev = jnp.pad(x[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
        return jnp.concatenate([prev, x], axis=3)

    qc = chunk(query)
    k2 = with_prev(chunk(key))
    v2 = with_prev(chunk(value))

    # Relative positions: query n*C + c vs key (n-1)*C + j, j in [0, 2C).
    c_off = jnp.arange(C)
    j_off = jnp.arange(2 * C)
    rel = (C + c_off[:, None]) - j_off[None, :]  # (C, 2C) = q_pos - k_pos
    band = (rel >= 0) & (rel < window)

    seg_c = segment_ids.reshape(B, 1, nc, C)
    seg_prev = jnp.pad(
        seg_c[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0)), constant_values=-2
    )
    seg2 = jnp.concatenate([seg_prev, seg_c], axis=3)  # (B, 1, nc, 2C)
    seg_ok = seg_c[..., :, None] == seg2[..., None, :]  # (B, 1, nc, C, 2C)
    mask = band[None, None, None] & seg_ok

    logits = jnp.einsum(
        "bhncd,bhnjd->bhncj", qc, k2, preferred_element_type=jnp.float32
    )
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhncj,bhnjd->bhncd", probs.astype(v2.dtype), v2)
    return out.reshape(B, H, L, D)


def dep_graph_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    q_offset: int = 0,
    window: int | None = None,
    probs_transform: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    dropout_mask: jnp.ndarray | None = None,
    dropout_rate: float = 0.0,
    impl: str | None = None,
) -> jnp.ndarray:
    """Fused causal attention over tiny per-event dependency-graph rows.

    The NA dep-graph walk attends over ``S = G+1`` positions per flattened
    event row (history token + G graph levels; ``S`` is 4 at the bench
    shape). At that size a batched ``dot_general`` formulation is all
    overhead: XLA tiles each (Q, S) logits plane as an MXU matmul against
    the ``(B·L, H, G, d)`` layout and pays relayout copies comparable to
    the matmuls themselves (~1.5 ms/step at the bench shape) plus lost
    loop fusion in the backward (~1.1 ms) — the r05 op-level attribution.

    This formulation contains **no dot_general at all**: logits and the
    probability-weighted value sum are broadcast-multiply + lane-reduction
    contractions, which XLA fuses — together with the causal/window mask,
    the fp32 softmax, and optional attention dropout — into one fusion
    scope per direction on every backend. FLOP count is identical to the
    einsum path (2·N·H·Q·S·D per contraction ≈ 50 MFLOPs at bench shape:
    VPU-trivial); what it removes is the layout friction around
    MXU-shaped ops that are far too small to tile.

    Args:
        query: ``(N, Q, H, D)`` — ``N`` flattened event rows, ``Q`` query
            positions (``S - q_offset`` when the first graph position is
            key/value-only history).
        key / value: ``(N, S, H, D)``.
        q_offset: absolute position of query 0 (1 under ``static_kv_first``).
        window: optional sliding-window width over graph positions
            (``dep_graph_attention_types="local"``); ``None`` = global.
        probs_transform: optional hook applied to the ``(N, Q, S, H)``
            fp32 attention probabilities — XLA impl only (a host-side
            closure cannot cross into a Pallas kernel); mutually exclusive
            with ``dropout_mask``.
        dropout_mask: optional precomputed ``(N, Q, S, H)`` boolean keep
            mask for attention dropout, applied identically by every impl
            as ``where(keep, p / (1 - dropout_rate), 0)`` — drawn by the
            caller from its dropout rng so the kernel and the XLA fallback
            see the same mask (`pallas_dep_graph` module docs).
        dropout_rate: the dropout rate the mask was drawn at.
        impl: ``None``/"auto" (the Pallas kernel on TPU, the fused-XLA
            formulation elsewhere; ``$ESGPT_PALLAS_IMPL`` overrides —
            `ops.impl_select`), ``"pallas"``, ``"pallas_interpret"``, or
            ``"xla"``.

    Returns:
        ``(N, Q, H, D)`` attention outputs in ``value``'s dtype. Logits are
        NOT scaled by ``1/sqrt(D)`` (GPT-Neo lineage) and softmax runs in
        fp32, exactly like the einsum path in ``models/transformer.py``.
        Parity contract: the Pallas kernel is bit-exact vs the XLA impl in
        fp32 (fwd and bwd) and exact to the same value-dtype roundings in
        bf16 (``tests/test_pallas_dep_graph.py``).
    """
    from .impl_select import resolve_impl

    explicit_kernel = impl in ("pallas", "pallas_interpret")
    impl = resolve_impl(impl, "dep_graph_attention")
    if probs_transform is not None and dropout_mask is not None:
        raise ValueError("pass either probs_transform or dropout_mask, not both")
    if probs_transform is not None and impl in ("pallas", "pallas_interpret"):
        # A host-side closure cannot cross into the kernel. Auto (and env)
        # resolution degrades to the XLA formulation, which supports it;
        # only an explicitly requested kernel impl is an error.
        if not explicit_kernel:
            impl = "xla"
        else:
            raise ValueError(
                "the Pallas dep-graph kernel takes dropout as a precomputed "
                "dropout_mask, not a probs_transform closure"
            )
    if impl in ("pallas", "pallas_interpret"):
        from ..parallel.context import per_batch_shard
        from .pallas_dep_graph import dep_graph_attention_pallas

        # Rows are batch-major flattened events, independent of each other.
        return per_batch_shard(
            lambda q, k, v, *m: dep_graph_attention_pallas(
                q,
                k,
                v,
                q_offset=q_offset,
                window=window,
                dropout_mask=m[0] if m else None,
                dropout_rate=dropout_rate,
                interpret=impl == "pallas_interpret",
            ),
            query,
            key,
            value,
            *(() if dropout_mask is None else (dropout_mask,)),
        )
    return _dep_graph_attention_xla(
        query,
        key,
        value,
        q_offset=q_offset,
        window=window,
        probs_transform=probs_transform,
        dropout_mask=dropout_mask,
        dropout_rate=dropout_rate,
    )


def _dep_graph_attention_xla(
    query, key, value, q_offset, window, probs_transform, dropout_mask, dropout_rate
):
    """The fused-XLA formulation (the r06 lever) — also the parity reference."""
    N, Q, H, D = query.shape
    S = key.shape[1]
    q_pos = jnp.arange(Q) + q_offset
    k_pos = jnp.arange(S)
    mask = k_pos[None, :] <= q_pos[:, None]  # causal over graph positions
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)

    # bf16 products are exact in fp32, so upcast-then-multiply reproduces the
    # MXU's bf16-multiply/fp32-accumulate numerics of the einsum path.
    qf = query.astype(jnp.float32)
    kf = key.astype(jnp.float32)
    logits = (qf[:, :, None] * kf[:, None, :]).sum(axis=-1)  # (N, Q, S, H)
    logits = jnp.where(mask[None, :, :, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=2)
    if probs_transform is not None:
        probs = probs_transform(probs)
    if dropout_mask is not None:
        # Identical semantics to nn.Dropout (and to the kernel impl):
        # keep -> p / keep_prob, drop -> 0.
        probs = jnp.where(dropout_mask, probs / (1.0 - float(dropout_rate)), 0.0)
    # Match the einsum path's probs dtype drop before the PV contraction,
    # then accumulate in fp32.
    pv = probs.astype(value.dtype).astype(jnp.float32)[..., None] * value.astype(
        jnp.float32
    )[:, None]
    return pv.sum(axis=2).astype(value.dtype)  # (N, Q, H, D)
