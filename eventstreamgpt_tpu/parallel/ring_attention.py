"""Ring attention: sequence-parallel causal attention over a mesh axis.

Blockwise-softmax attention (the flash-attention recurrence) distributed
over a ``context`` mesh axis: queries stay resident, key/value blocks (and
their segment IDs) rotate device-to-device with ``lax.ppermute`` each step,
and the online max/sum statistics merge partial blocks exactly — the
distributed result equals single-device softmax attention up to fp rounding.

Semantics match the model's attention (``models/transformer.py``):
**unscaled** QK^T logits (GPT-Neo lineage), fp32 softmax statistics, causal
masking on global positions, optional sliding window (``k > q - window``),
packed-sequence segment isolation, and padding keys excluded via a
``-1``-segment convention. Fully-masked query rows degrade to a uniform
average (finite), mirroring the einsum path's clamp — such rows are always
event-masked downstream.

References (public technique, reimplemented): Liu et al., "Ring Attention
with Blockwise Transformers" (arXiv 2310.01889); the jax ``shard_map`` all-
gather/ppermute patterns of the scaling playbook. No reference-repo
counterpart exists (SURVEY §5.7: absent upstream).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

MASK_VALUE = -1e30


def _block_logits_mask(q_pos, kv_pos, q_seg, kv_seg, window_size):
    """(B, S_q, S_kv) boolean mask for one (query block, kv block) pair."""
    causal = kv_pos[None, None, :] <= q_pos[None, :, None]
    if window_size is not None:
        causal = causal & (kv_pos[None, None, :] > q_pos[None, :, None] - window_size)
    seg_ok = q_seg[:, :, None] == kv_seg[:, None, :]
    return causal & seg_ok


def ring_attention_shard(
    q,
    k,
    v,
    seg,
    axis_name: str,
    window_size: int | None = None,
):
    """Per-shard ring attention body (call inside ``shard_map``).

    Args:
        q, k, v: ``(B_local, H, S_local, D)`` — this shard's blocks.
        seg: ``(B_local, S_local)`` int32 segment IDs; ``-1`` marks padding
            (padding attends only to padding, as in the Pallas kernel paths).
        axis_name: the mesh axis the sequence is sharded over.
        window_size: optional sliding-window width (local attention).

    Returns:
        ``(B_local, H, S_local, D)`` attention output for this shard's
        queries over the **global** key/value sequence.
    """
    n_shards = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, S, D = q.shape

    q_pos = my_idx * S + jnp.arange(S)

    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def step(carry, r):
        o, m, l, k_blk, v_blk, seg_blk = carry
        # After r rotations this shard holds the block originally on shard
        # (my_idx - r) mod n — its global positions anchor the causal mask.
        src = (my_idx - r) % n_shards
        kv_pos = src * S + jnp.arange(S)

        # Operands stay in the input dtype (bf16 rides the MXU); accumulation
        # and all softmax statistics are fp32 — the same convention as the
        # model's einsum path.
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        )
        mask = _block_logits_mask(q_pos, kv_pos, seg, seg_blk, window_size)
        logits = jnp.where(mask[:, None], logits, MASK_VALUE)

        blk_max = logits.max(axis=-1)  # (B, H, S)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])
        l = l * correction + p.sum(axis=-1)
        o = o * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )

        # Rotate kv (+ its segment ids) one step around the ring. The final
        # rotation restores the original layout, keeping the scan carry
        # shape-stable and the blocks where they started.
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
        return (o, new_m, l, k_blk, v_blk, seg_blk), None

    # Initial accumulators derive from q so they carry q's device-varying
    # axes — a plain constant would fail shard_map's vma check against the
    # scan body's (varying) outputs.
    o0 = q.astype(jnp.float32) * 0.0
    m0 = o0[..., 0] + MASK_VALUE
    l0 = o0[..., 0]
    (o, m, l, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v, seg), jnp.arange(n_shards)
    )

    out = o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    segment_ids,
    mesh: Mesh,
    axis_name: str = "context",
    data_axis: str | None = "data",
    window_size: int | None = None,
    head_axis: str | None = "model",
):
    """Sequence-parallel attention over ``mesh[axis_name]``.

    Args:
        q, k, v: ``(B, H, S, D)`` with ``S`` divisible by the context axis
            size (global views; jit/GSPMD shards them per ``in_specs``).
        segment_ids: ``(B, S)`` int32; ``-1`` marks padding keys/queries.
        mesh: mesh containing ``axis_name`` (and optionally ``data_axis``).
        data_axis: mesh axis sharding the batch dim, or None if replicated.
        window_size: optional sliding-window width.
        head_axis: mesh axis sharding the head dim, or None. Attention is
            per-head independent, so composing with Megatron tensor
            parallelism (head-split q/k/v projections; ``training/sharding.py``)
            needs no collectives over this axis — each shard rings its local
            heads' kv blocks over ``axis_name`` only. Ignored when absent
            from the mesh or when the head count doesn't divide it (the
            heads then enter the ring replicated via an XLA all-gather).

    Returns:
        ``(B, H, S, D)`` attention output, sharded like ``q``.
    """
    if mesh.shape[axis_name] > 1 and q.shape[2] % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"Sequence length {q.shape[2]} must be divisible by the '{axis_name}' "
            f"axis size ({mesh.shape[axis_name]})."
        )
    b_spec = data_axis if data_axis in mesh.shape else None
    h_spec = (
        head_axis
        if head_axis is not None
        and head_axis in mesh.shape
        and q.shape[1] % mesh.shape[head_axis] == 0
        else None
    )
    qkv_spec = P(b_spec, h_spec, axis_name, None)
    seg_spec = P(b_spec, axis_name)

    fn = jax.shard_map(
        partial(ring_attention_shard, axis_name=axis_name, window_size=window_size),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, segment_ids.astype(jnp.int32))
