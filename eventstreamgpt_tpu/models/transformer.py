"""Point-process transformer encoders, TPU-native.

Re-design of ``/root/reference/EventStream/transformer/transformer.py`` for
XLA: GPT-Neo-style blocks (pre-LN attention with **unscaled** QK^T logits and
fp32 softmax, exactly as the reference's ``InnerSelfAttention._attn``
``transformer.py:171-217``), continuous-time sinusoidal position encodings over
cumulative minutes (``transformer.py:539-620``), and global or local
(sliding-window) causal masking built from position indices instead of a dense
``(max_seq_len, max_seq_len)`` tril buffer (``transformer.py:109-118``) so
memory stays O(L) outside the attention computation itself.

The KV cache diverges deliberately: the reference grows caches by tensor
concatenation per step (``transformer.py:261-270``), which cannot compile
under ``jit``. Here a cache is a fixed-size `KVCache` pytree — preallocated
``(B, H, max_len, D)`` buffers plus a write cursor — updated with
``lax.dynamic_update_slice`` so the whole generation loop stays on device
inside ``lax.scan``/``while_loop``.
"""

from __future__ import annotations

import math
import re
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct
from jax.ad_checkpoint import checkpoint_name

from ..data.types import EventStreamBatch
from ..ops import segment_starts
from ..utils.scopes import scope, scoped
from .config import StructuredTransformerConfig
from .embedding import DataEmbeddingLayer
from .structured_attention import StructuredAttention

Array = Any

ACT2FN = {
    "gelu": nn.gelu,
    "gelu_new": nn.gelu,
    "relu": nn.relu,
    "silu": nn.silu,
    "swish": nn.silu,
    "tanh": jnp.tanh,
}

MASK_VALUE = -1e9

# The checkpoint_name every attention path tags its output with; the
# "save_attention" remat policy saves exactly these tensors (plus matmul
# outputs via dots_no_batch) so the backward never re-executes attention.
ATTENTION_CHECKPOINT_NAME = "attention_output"


@struct.dataclass
class KVCache:
    """A fixed-size per-layer key/value cache with a write cursor.

    ``key``/``value`` have shape ``(B, H, max_len, head_dim)``; ``mask`` is the
    accumulated key-padding mask ``(B, max_len)`` (True = real event) so that
    cached decoding preserves each past position's event-mask bit; ``length``
    is the number of positions already written — a scalar int32 on the
    cohort generation path, or a per-row ``(B,)`` vector on the serving
    engine's slot-decode path (each slot advances its own cursor).

    Quantized decode caches (``key.dtype`` int8/fp8 — the serving engine's
    ``kv_cache_dtype`` lever, `ops.kv_quant`) additionally carry
    ``key_scale``/``value_scale``: per-head-per-row fp32 scale tables of
    shape ``(B, H, max_len)``, written alongside the quantized planes at
    the cursor and consumed by the dequantize-on-read multiply fused into
    the attention contraction. ``None`` on float caches — the pytree then
    has exactly its historical leaves, so checkpoints and donation
    signatures are unchanged.
    """

    key: Array
    value: Array
    mask: Array
    length: Array  # scalar int32 or per-row (B,) int32
    key_scale: Optional[Array] = None  # (B, H, max_len) fp32 when quantized
    value_scale: Optional[Array] = None

    @classmethod
    def init(cls, batch_size: int, num_heads: int, max_len: int, head_dim: int, dtype=jnp.float32):
        from ..ops.kv_quant import is_quantized_dtype

        quantized = is_quantized_dtype(dtype)

        def scale():
            # Distinct buffers per field: donation rejects aliased arguments.
            return (
                jnp.ones((batch_size, num_heads, max_len), jnp.float32)
                if quantized
                else None
            )

        return cls(
            key=jnp.zeros((batch_size, num_heads, max_len, head_dim), dtype=dtype),
            value=jnp.zeros((batch_size, num_heads, max_len, head_dim), dtype=dtype),
            mask=jnp.zeros((batch_size, max_len), dtype=bool),
            length=jnp.zeros((), dtype=jnp.int32),
            key_scale=scale(),
            value_scale=scale(),
        )


@struct.dataclass
class PagedKVCache:
    """A block-pool (paged) per-layer key/value cache with per-row tables.

    The serving engine's copy-on-write decode cache: keys/values live in a
    device-resident pool of fixed-size blocks (``pool_key``/``pool_value``
    of shape ``(num_blocks, H, block_size, head_dim)``) instead of one
    monolithic ``(B, max_len)`` buffer per row. Each row owns a
    ``block_table`` row of ``(max_len // block_size)`` physical block ids;
    the attention read gathers the row's dense ``(H, max_len, head_dim)``
    view through the table, so two rows whose tables share block ids share
    the bytes — the `fork()` copy-on-write prefix-sharing substrate.

    **Block 0 is the reserved zero block**: it backs every unallocated
    table entry, is never allocated and never written (the write path
    redirects any ``phys == 0`` target out of range and drops it), so an
    unallocated position gathers exactly the zeros a freshly admitted
    monolithic buffer holds there — the structural half of the paged ≡
    monolithic bit-identity contract. ``mask`` and ``length`` stay dense
    per-row (``(B, max_len)`` / ``(B,)``) exactly as in the vector-length
    `KVCache`; only the key/value planes (and the quantized scale tables,
    ``(num_blocks, H, block_size)``) are paged.
    """

    pool_key: Array  # (num_blocks, H, block_size, head_dim)
    pool_value: Array
    block_table: Array  # (B, max_len // block_size) int32; 0 = zero block
    mask: Array  # (B, max_len) bool — dense, as in the monolithic cache
    length: Array  # (B,) int32 per-row cursors
    pool_key_scale: Optional[Array] = None  # (num_blocks, H, block_size) fp32
    pool_value_scale: Optional[Array] = None

    @property
    def block_size(self) -> int:
        return self.pool_key.shape[2]

    @property
    def num_blocks(self) -> int:
        return self.pool_key.shape[0]

    @property
    def max_len(self) -> int:
        return self.block_table.shape[1] * self.pool_key.shape[2]

    @classmethod
    def init(
        cls,
        batch_size: int,
        num_heads: int,
        num_blocks: int,
        block_size: int,
        max_len: int,
        head_dim: int,
        dtype=jnp.float32,
    ):
        from ..ops.kv_quant import is_quantized_dtype

        if max_len % block_size != 0:
            raise ValueError(
                f"paged cache needs block_size ({block_size}) to divide "
                f"max_len ({max_len})"
            )
        quantized = is_quantized_dtype(dtype)

        def scale():
            # Ones, matching the monolithic scale-table init: the zero
            # block then dequantizes to exactly 0.0 (0 * 1.0), the same
            # bytes a zero-initialized monolithic buffer dequantizes to.
            return (
                jnp.ones((num_blocks, num_heads, block_size), jnp.float32)
                if quantized
                else None
            )

        return cls(
            pool_key=jnp.zeros((num_blocks, num_heads, block_size, head_dim), dtype=dtype),
            pool_value=jnp.zeros((num_blocks, num_heads, block_size, head_dim), dtype=dtype),
            block_table=jnp.zeros((batch_size, max_len // block_size), jnp.int32),
            mask=jnp.zeros((batch_size, max_len), dtype=bool),
            length=jnp.zeros((batch_size,), jnp.int32),
            pool_key_scale=scale(),
            pool_value_scale=scale(),
        )


def paged_kv_bytes_per_block(
    num_layers: int, num_heads: int, block_size: int, head_dim: int, cache_dtype, compute_dtype
) -> int:
    """HBM bytes one block pins across all layers (planes + scale rows)."""
    from ..ops.kv_quant import is_quantized_dtype, resolve_cache_dtype

    dtype, _ = resolve_cache_dtype(cache_dtype, compute_dtype)
    plane = num_heads * block_size * head_dim * jnp.dtype(dtype).itemsize
    scale = (
        num_heads * block_size * jnp.dtype(jnp.float32).itemsize
        if is_quantized_dtype(dtype)
        else 0
    )
    return num_layers * 2 * (plane + scale)


NO_DECODE_STATE = (
    "the kinds block has no decode cache yet (latent attention: a latent paged cache and the absorbed "
    "decode path; a state-space mixer: its recurrent state and the convolution's last taps in a "
    "generation slot; a gated short convolution: its last L - 1 gated inputs in a generation slot; "
    "grouped key/value heads in the caches: ROADMAP R3, R4): generate() and the serving engine cannot "
    "run this backbone"
)


def init_paged_kv_caches(
    config: StructuredTransformerConfig,
    batch_size: int,
    num_blocks: int,
    block_size: int,
    max_len: int | None = None,
    cache_dtype: str | None = None,
) -> tuple[PagedKVCache, ...]:
    """Preallocates one `PagedKVCache` per hidden layer (engine paged mode)."""
    if max_len is None:
        max_len = config.max_seq_len
    if cache_dtype is not None:
        from ..ops.kv_quant import resolve_cache_dtype

        dtype, _ = resolve_cache_dtype(cache_dtype, config.compute_dtype)
    else:
        dtype = config.compute_dtype
    return tuple(
        PagedKVCache.init(
            batch_size,
            config.num_attention_heads,
            num_blocks,
            block_size,
            max_len,
            config.head_dim,
            dtype,
        )
        for _ in range(config.num_hidden_layers)
    )


def init_kv_caches(
    config: StructuredTransformerConfig,
    batch_size: int,
    max_len: int | None = None,
    dtype=None,
    cache_dtype: str | None = None,
) -> tuple[KVCache, ...]:
    """Preallocates one `KVCache` per hidden layer.

    Cache buffers default to the model's compute dtype so bf16 keys/values
    written by ``lax.dynamic_update_slice`` match the buffer dtype.
    ``cache_dtype`` names a storage type instead (``"bf16"``/``"fp32"``/
    ``"int8"``/``"fp8"`` — `ops.kv_quant.resolve_cache_dtype`); quantized
    names allocate the per-head-per-row scale tables alongside.
    """
    if max_len is None:
        max_len = config.max_seq_len
    if cache_dtype is not None:
        from ..ops.kv_quant import resolve_cache_dtype

        dtype, _ = resolve_cache_dtype(cache_dtype, config.compute_dtype)
    elif dtype is None:
        dtype = config.compute_dtype
    return tuple(
        KVCache.init(batch_size, config.num_attention_heads, max_len, config.head_dim, dtype)
        for _ in range(config.num_hidden_layers)
    )


@struct.dataclass
class TransformerOutputWithPast:
    """Encoder output (reference: ``model_output.py:208``)."""

    last_hidden_state: Array
    past_key_values: Optional[tuple] = None
    hidden_states: Optional[tuple] = None
    attentions: Optional[tuple] = None
    # Per-layer contextualized (whole-event, seq-attended) embeddings of an
    # NA forward — the speculative-decoding verify's history head state
    # (requested via return_contextualized; None otherwise).
    contextualized: Optional[tuple] = None


def time_from_deltas(batch: EventStreamBatch) -> Array:
    """Cumulative time-since-start from per-event deltas.

    Reference: ``transformer.py:539-561``.

    Examples:
        >>> import jax.numpy as jnp
        >>> from eventstreamgpt_tpu.data.types import EventStreamBatch
        >>> batch = EventStreamBatch(
        ...     event_mask=jnp.asarray([[True, True, True], [True, True, False]]),
        ...     time_delta=jnp.asarray([[1.0, 3.2, 0.0], [1.4, 0.0, 1.0]]),
        ... )
        >>> time_from_deltas(batch)
        Array([[0. , 1. , 4.2],
               [0. , 1.4, 1.4]], dtype=float32)
    """
    t_deltas = batch.time_delta
    if batch.event_mask is not None:
        t_deltas = jnp.where(batch.event_mask, t_deltas, 0.0)
    csum = jnp.cumsum(t_deltas, axis=-1)
    t = jnp.concatenate([jnp.zeros_like(csum[:, :1]), csum[:, :-1]], axis=1)
    if batch.segment_ids is not None:
        # Packed rows: time restarts at each segment. The offset for every
        # position is t at its segment's first event; t is nondecreasing
        # (deltas ≥ 0), so a running max over segment-start values forward-
        # fills the current segment's offset.
        seg_start = segment_starts(batch.segment_ids)
        offsets = jax.lax.cummax(jnp.where(seg_start, t, -jnp.inf), axis=1)
        t = t - offsets
    return t


class TemporalPositionEncoding(nn.Module):
    """Sinusoidal position encoding over continuous time values (minutes).

    Reference: ``transformer.py:564-620``. Supports odd embedding dims by
    truncating the cos half.
    """

    embedding_dim: int
    max_timepoint: float = 10000.0

    @nn.compact
    def __call__(self, t: Array) -> Array:
        div_term = jnp.exp(
            jnp.arange(0, self.embedding_dim, 2) * (-math.log(self.max_timepoint) / self.embedding_dim)
        )
        sin_div = div_term
        cos_div = div_term if self.embedding_dim % 2 == 0 else div_term[:-1]

        t = t[..., None]
        sin_emb = jnp.sin(t * sin_div)
        cos_emb = jnp.cos(t * cos_div)
        # Interleave: out[..., 0::2] = sin, out[..., 1::2] = cos.
        out = jnp.zeros(t.shape[:-1] + (self.embedding_dim,), dtype=sin_emb.dtype)
        out = out.at[..., 0::2].set(sin_emb)
        out = out.at[..., 1::2].set(cos_emb)
        return out


def make_causal_mask(
    q_positions: Array, k_positions: Array, window_size: int | None = None
) -> Array:
    """Boolean (…, Q, K) mask: True where query may attend to key.

    Global: ``k <= q``. Local: additionally ``k > q - window_size`` — the
    sliding-window rule the reference encodes in its XOR'd tril buffer
    (``transformer.py:109-118``).
    """
    q = q_positions[..., :, None]
    k = k_positions[..., None, :]
    mask = k <= q
    if window_size is not None:
        mask = mask & (k > q - window_size)
    return mask


class InnerSelfAttention(nn.Module):
    """Multi-head causal self-attention with optional local windowing.

    Numerics match the reference (``transformer.py:171-217``): no ``1/sqrt(d)``
    scaling of logits, softmax in fp32, additive padding mask. Supports an
    optional fixed-size `KVCache` and the ``static_kv_first`` trick where the
    first position is key/value-only (``transformer.py:256-259``).
    """

    config: StructuredTransformerConfig
    attention_type: str = "global"
    window_size: int | None = None
    is_dep_graph: bool = False

    @nn.compact
    def __call__(
        self,
        hidden_states: Array,
        attention_mask: Array | None = None,  # (B, K) boolean: True = attend
        layer_past: KVCache | None = None,
        use_cache: bool = False,
        output_attentions: bool = False,
        static_kv_first: bool = False,
        segment_ids: Array | None = None,  # (B, S): packed-sequence segments
    ):
        cfg = self.config
        embed_dim = cfg.hidden_size
        num_heads = cfg.num_attention_heads
        head_dim = cfg.head_dim
        if head_dim * num_heads != embed_dim:
            raise ValueError(
                f"embed_dim must be divisible by num_heads (got `embed_dim`: {embed_dim} and "
                f"`num_heads`: {num_heads})."
            )
        dense_init = nn.initializers.normal(stddev=cfg.init_std)
        # Mixed precision: matmuls in cfg.compute_dtype (params stay fp32),
        # logits/softmax always fp32 (see below).
        dt = cfg.compute_dtype
        q_proj = nn.Dense(embed_dim, use_bias=False, kernel_init=dense_init, dtype=dt, name="q_proj")
        k_proj = nn.Dense(embed_dim, use_bias=False, kernel_init=dense_init, dtype=dt, name="k_proj")
        v_proj = nn.Dense(embed_dim, use_bias=False, kernel_init=dense_init, dtype=dt, name="v_proj")
        out_proj = nn.Dense(embed_dim, use_bias=True, kernel_init=dense_init, dtype=dt, name="out_proj")

        B, S = hidden_states.shape[0], hidden_states.shape[1]

        # Projections stay in (B, S, H, D) — the matmul's natural layout. The
        # transpose to heads-first (B, H, S, D) happens ONLY for consumers
        # whose contract needs it (KV caches, the fused kernels); the einsum
        # fallback contracts directly from (B, S, H, D), which removes the
        # q/k/v/output transposes that dominated the NA dep-graph blocks'
        # "data formatting" time in the r05 profile (tiny G-wide graphs pay
        # relayout copies comparable to their matmuls).
        def split_heads(x):
            return x.reshape(x.shape[:-1] + (num_heads, head_dim))

        with scope("attn_proj"):
            query = split_heads(q_proj(hidden_states))  # (B, S, H, D)
            key = split_heads(k_proj(hidden_states))
            value = split_heads(v_proj(hidden_states))

        if static_kv_first:
            query = query[:, 1:]

        q_len = query.shape[1]

        def heads_first(x):
            return x.swapaxes(-3, -2)  # (B, S, H, D) -> (B, H, S, D)

        if layer_past is not None or use_cache:
            query, key, value = heads_first(query), heads_first(key), heads_first(value)

        present = None
        if isinstance(layer_past, PagedKVCache):
            # Paged block-pool cache (the serving engine's copy-on-write
            # decode path): writes scatter each row's chunk into the
            # physical block its table maps the cursor position to; the
            # read gathers the row's dense view through the table and then
            # runs EXACTLY the vector-length branch's position/mask math.
            # Because every allocated block holds byte-identical content to
            # the corresponding monolithic buffer span and every
            # unallocated position gathers the zero block's zeros (the
            # bytes monolithic admission leaves there), the dense view —
            # and therefore the attention output — is bit-identical to the
            # monolithic cache at every step.
            bs_blk = layer_past.block_size
            n_blocks = layer_past.num_blocks
            T_blk = layer_past.block_table.shape[1]
            max_len = T_blk * bs_blk
            start = layer_past.length  # (B,)
            pos = jnp.arange(max_len)
            if S == 1:
                write = pos[None, :] == start[:, None]  # (B, max_len)
                gather_mask = lambda m: m  # (B, 1)  # noqa: E731
            else:
                # Speculative multi-event range write, preserved on the
                # block path: same dense write mask / source gather as the
                # monolithic S > 1 branch; the pool scatter below walks the
                # S chunk positions with a static loop.
                write = (pos[None, :] >= start[:, None]) & (
                    pos[None, :] < start[:, None] + S
                )
                src = jnp.clip(pos[None, :] - start[:, None], 0, S - 1)
                gather_mask = lambda m: jnp.take_along_axis(m, src, axis=1)  # noqa: E731
            quantized = layer_past.pool_key_scale is not None
            if quantized:
                from ..ops.kv_quant import dequantize_kv, quantize_kv

                k_chunk, k_s = quantize_kv(key, layer_past.pool_key.dtype)
                v_chunk, v_s = quantize_kv(value, layer_past.pool_value.dtype)
            else:
                k_chunk = key.astype(layer_past.pool_key.dtype)
                v_chunk = value.astype(layer_past.pool_value.dtype)
                k_s = v_s = None
            pk, pv = layer_past.pool_key, layer_past.pool_value
            pks, pvs = layer_past.pool_key_scale, layer_past.pool_value_scale
            for j in range(S):
                pos_j = start + j  # (B,)
                blk = jnp.clip(pos_j // bs_blk, 0, T_blk - 1)
                off = pos_j % bs_blk
                phys = jnp.take_along_axis(
                    layer_past.block_table, blk[:, None], axis=1
                )[:, 0]
                # Write-drop rule: the zero block (phys == 0) is never a
                # legitimate target — it backs unallocated entries (rows
                # never admitted, positions past a row's allocation), so
                # their writes redirect out of range and drop. Positions
                # past the buffer drop too (the monolithic one-hot write
                # matches nothing there).
                phys = jnp.where((phys == 0) | (pos_j >= max_len), n_blocks, phys)
                pk = pk.at[phys, :, off, :].set(k_chunk[:, :, j, :], mode="drop")
                pv = pv.at[phys, :, off, :].set(v_chunk[:, :, j, :], mode="drop")
                if quantized:
                    pks = pks.at[phys, :, off].set(k_s[:, :, j], mode="drop")
                    pvs = pvs.at[phys, :, off].set(v_s[:, :, j], mode="drop")
            chunk_mask = (
                attention_mask if attention_mask is not None else jnp.ones((B, S), dtype=bool)
            )
            new_mask = jnp.where(write, gather_mask(chunk_mask), layer_past.mask)

            def gather_pool(pool):  # (N, H, bs, D) -> (B, H, max_len, D)
                g = pool[layer_past.block_table]  # (B, T, H, bs, D)
                g = g.swapaxes(1, 2)  # (B, H, T, bs, D)
                return g.reshape(g.shape[0], g.shape[1], max_len, *g.shape[4:])

            new_key = gather_pool(pk)
            new_value = gather_pool(pv)
            if use_cache:
                present = PagedKVCache(
                    pool_key=pk,
                    pool_value=pv,
                    block_table=layer_past.block_table,
                    mask=new_mask,
                    length=start + S,
                    pool_key_scale=pks,
                    pool_value_scale=pvs,
                )
            if quantized:
                key = dequantize_kv(new_key, gather_pool(pks), dt)
                value = dequantize_kv(new_value, gather_pool(pvs), dt)
            else:
                key, value = new_key, new_value
            k_positions = pos
            q_positions = start[:, None] + jnp.arange(q_len)[None, :] + (
                1 if static_kv_first else 0
            )
            valid_k = pos[None, :] < (start[:, None] + S)
            attention_mask = new_mask
        elif layer_past is not None and getattr(layer_past.length, "ndim", 0) == 1:
            # Per-row cache cursors (the serving engine's decode slots): each
            # row writes its ``S`` new keys/values starting at its own
            # ``length[b]``. S == 1 is the decode hot loop (one-hot select,
            # the r07-audited lowering); S > 1 is the speculative-decoding
            # verify window (per-row *range* scatter: buffer position ``p``
            # takes chunk element ``p - start[b]`` via a clipped
            # take_along_axis gather masked to the written range — a
            # selection, no arithmetic, so values land bit-identically to S
            # sequential one-event writes).
            max_len = layer_past.key.shape[2]
            start = layer_past.length  # (B,)
            pos = jnp.arange(max_len)
            if S == 1:
                write = pos[None, :] == start[:, None]  # (B, max_len)
                gather4 = lambda chunk: chunk  # (B, H, 1, D) broadcasts  # noqa: E731
                gather3 = lambda chunk: chunk  # (B, H, 1) scale tables  # noqa: E731
                gather_mask = lambda m: m  # (B, 1)  # noqa: E731
            else:
                write = (pos[None, :] >= start[:, None]) & (
                    pos[None, :] < start[:, None] + S
                )  # (B, max_len)
                src = jnp.clip(pos[None, :] - start[:, None], 0, S - 1)  # (B, max_len)
                gather4 = lambda chunk: jnp.take_along_axis(  # noqa: E731
                    chunk, src[:, None, :, None], axis=2
                )
                gather3 = lambda chunk: jnp.take_along_axis(  # noqa: E731
                    chunk, src[:, None, :], axis=2
                )
                gather_mask = lambda m: jnp.take_along_axis(m, src, axis=1)  # noqa: E731
            # key/value are (B, H, S, D): broadcast/gather over the buffer
            # axis and write exactly each row's cursor range. The explicit
            # astype pins the buffer dtype: jnp.where would otherwise silently
            # promote a narrower cache (bf16 buffers under fp32 compute) to
            # the chunk dtype — the regression `TestKVCacheDtypePreservation`
            # guards. Quantized caches (int8/fp8 + scale tables) instead
            # quantize-on-write here — the per-row cursor scatter — and the
            # scale tables ride the same one-hot select.
            quantized = layer_past.key_scale is not None
            if quantized:
                from ..ops.kv_quant import dequantize_kv, quantize_kv

                k_q, k_s = quantize_kv(key, layer_past.key.dtype)
                v_q, v_s = quantize_kv(value, layer_past.value.dtype)
                new_key = jnp.where(write[:, None, :, None], gather4(k_q), layer_past.key)
                new_value = jnp.where(write[:, None, :, None], gather4(v_q), layer_past.value)
                new_key_scale = jnp.where(write[:, None, :], gather3(k_s), layer_past.key_scale)
                new_value_scale = jnp.where(
                    write[:, None, :], gather3(v_s), layer_past.value_scale
                )
            else:
                new_key = jnp.where(
                    write[:, None, :, None],
                    gather4(key.astype(layer_past.key.dtype)),
                    layer_past.key,
                )
                new_value = jnp.where(
                    write[:, None, :, None],
                    gather4(value.astype(layer_past.value.dtype)),
                    layer_past.value,
                )
                new_key_scale = new_value_scale = None
            chunk_mask = (
                attention_mask if attention_mask is not None else jnp.ones((B, S), dtype=bool)
            )
            new_mask = jnp.where(write, gather_mask(chunk_mask), layer_past.mask)
            if use_cache:
                present = KVCache(
                    key=new_key,
                    value=new_value,
                    mask=new_mask,
                    length=start + S,
                    key_scale=new_key_scale,
                    value_scale=new_value_scale,
                )
            if quantized:
                # Dequantize-on-read: the multiply sits directly before the
                # QK^T / PV contractions and fuses into their operand scope.
                key = dequantize_kv(new_key, new_key_scale, dt)
                value = dequantize_kv(new_value, new_value_scale, dt)
            else:
                key, value = new_key, new_value
            k_positions = pos
            q_positions = start[:, None] + jnp.arange(q_len)[None, :] + (
                1 if static_kv_first else 0
            )  # (B, q_len)
            valid_k = pos[None, :] < (start[:, None] + S)  # (B, max_len)
            attention_mask = new_mask
        elif layer_past is not None:
            # Fixed-buffer cache: write new keys/values (and the chunk's
            # padding-mask bits) at the cursor, then attend over the full
            # buffer with validity masking.
            max_len = layer_past.key.shape[2]
            start = layer_past.length
            # Same dtype/quantization contract as the vector branch: explicit
            # astype pins narrower float buffers; quantized caches quantize
            # the chunk on write (scale tables updated at the same cursor)
            # and dequantize the full buffer on read, fused into the
            # attention contraction.
            quantized = layer_past.key_scale is not None
            if quantized:
                from ..ops.kv_quant import dequantize_kv, quantize_kv

                k_q, k_s = quantize_kv(key, layer_past.key.dtype)
                v_q, v_s = quantize_kv(value, layer_past.value.dtype)
                new_key = jax.lax.dynamic_update_slice(layer_past.key, k_q, (0, 0, start, 0))
                new_value = jax.lax.dynamic_update_slice(
                    layer_past.value, v_q, (0, 0, start, 0)
                )
                new_key_scale = jax.lax.dynamic_update_slice(
                    layer_past.key_scale, k_s, (0, 0, start)
                )
                new_value_scale = jax.lax.dynamic_update_slice(
                    layer_past.value_scale, v_s, (0, 0, start)
                )
            else:
                new_key = jax.lax.dynamic_update_slice(
                    layer_past.key, key.astype(layer_past.key.dtype), (0, 0, start, 0)
                )
                new_value = jax.lax.dynamic_update_slice(
                    layer_past.value, value.astype(layer_past.value.dtype), (0, 0, start, 0)
                )
                new_key_scale = new_value_scale = None
            chunk_mask = (
                attention_mask if attention_mask is not None else jnp.ones((B, S), dtype=bool)
            )
            new_mask = jax.lax.dynamic_update_slice(layer_past.mask, chunk_mask, (0, start))
            if use_cache:
                present = KVCache(
                    key=new_key,
                    value=new_value,
                    mask=new_mask,
                    length=start + S,
                    key_scale=new_key_scale,
                    value_scale=new_value_scale,
                )
            if quantized:
                key = dequantize_kv(new_key, new_key_scale, dt)
                value = dequantize_kv(new_value, new_value_scale, dt)
            else:
                key, value = new_key, new_value
            k_positions = jnp.arange(max_len)
            q_positions = start + jnp.arange(q_len) + (1 if static_kv_first else 0)
            valid_k = k_positions < (start + S)
            attention_mask = new_mask  # (B, max_len): full-buffer padding mask
        else:
            k_positions = jnp.arange(S)
            q_positions = jnp.arange(q_len) + (1 if static_kv_first else 0)
            valid_k = None
            if use_cache:
                chunk_mask = (
                    attention_mask if attention_mask is not None else jnp.ones((B, S), dtype=bool)
                )
                present = KVCache(
                    key=key, value=value, mask=chunk_mask, length=jnp.asarray(S, jnp.int32)
                )

        # Pallas fused attention fast paths (TPU only): full training
        # forwards/backwards with causal + segment masking fused into a
        # single kernel, no (L, L) logits materialized in HBM. Global layers
        # ride the flash-attention kernel; local (sliding-window) layers ride
        # the splash-attention kernel with a block-banded `LocalMask`, whose
        # scheduler skips blocks entirely outside the window — so the default
        # alternating ["local", "global"] stack stays on fused kernels end to
        # end. Falls back to the einsum path whenever kernel
        # preconditions don't hold (KV cache, dep-graph static-kv, attention
        # dropout, attention-weight outputs, non-TPU backends).
        fused_ok = (
            layer_past is None
            and not static_kv_first
            and not use_cache
            and not output_attentions
            and (float(cfg.attention_dropout) == 0.0 or not self.has_rng("dropout"))
        )
        # Fused dep-graph rows: the NA walk's
        # (B·L, G+1) flattened graphs are far too small for MXU-shaped
        # attention — the batched dot_generals pay layout copies comparable
        # to their FLOPs. ops/band_attention.dep_graph_attention re-expresses
        # the whole walk (causal mask, fp32 softmax, attention dropout, PV)
        # as broadcast-multiply + lane reductions in the projections' native
        # (N, S, H, D) layout, which XLA keeps in one fusion scope per
        # direction on every backend. Cached decode stays on the einsum path
        # (exact-parity gated by test_cached_dep_graph_decode_matches_uncached).
        use_dep_fused = (
            self.is_dep_graph
            and bool(getattr(cfg, "dep_graph_fused_attention", True))
            and layer_past is None
            and not use_cache
            and not output_attentions
            and attention_mask is None
            and segment_ids is None
        )
        kernel_ok = (
            cfg.attention_implementation == "pallas_flash"
            and jax.default_backend() == "tpu"
            and fused_ok
            and S % 128 == 0
        )
        use_pallas = kernel_ok and self.attention_type == "global"
        if (
            cfg.attention_implementation == "pallas_flash"
            and fused_ok
            and not kernel_ok
            and not use_dep_fused
        ):
            # Not silent: the config asked for the kernels and this trace
            # cannot take them (chip_smoke.py asserts the opposite on the chip).
            import warnings

            warnings.warn(
                "attention_implementation='pallas_flash' is taking the einsum/band "
                f"path: backend={jax.default_backend()!r}, S={S} (the flash/splash "
                "kernels need a TPU backend and S % 128 == 0)",
                stacklevel=2,
            )
        # Narrow-window local layers skip the kernels entirely: the chunked
        # band einsum (ops/band_attention.py) touches only a (C, 2C) logits
        # plane per window-sized chunk and measured ~35-45% faster fwd+bwd
        # than the splash kernel's best block shape at production width,
        # window <= 128 (before PR 22). It is backend-independent (pure
        # einsums), so it activates under the fused gate on CPU too; splash
        # remains the local path for wide windows, where its block-skipping
        # scheduler amortizes.
        use_band = (
            fused_ok
            and cfg.attention_implementation == "pallas_flash"
            and self.attention_type == "local"
            and self.window_size is not None
            and 1 <= self.window_size <= 128
            and S % self.window_size == 0
        )
        use_splash = (
            kernel_ok
            and not use_band
            and self.attention_type == "local"
            and self.window_size is not None
            and self.window_size >= 1
        )
        # Sequence-parallel ring attention: active when the training driver
        # wraps its step in `parallel.ring_context(mesh)` and the config asks
        # for it. Queries stay resident; kv blocks rotate over the `context`
        # mesh axis (parallel/ring_attention.py). Falls back to einsum with
        # no active context, so ring-configured checkpoints run anywhere.
        ring_ctx = None
        if cfg.attention_implementation == "ring" and fused_ok and not use_dep_fused:
            from ..parallel.context import current_ring_context

            ring_ctx = current_ring_context()
            if ring_ctx is not None and S % ring_ctx.mesh.shape[ring_ctx.axis_name] != 0:
                ring_ctx = None

        # All fused paths share one packed-segment convention: padding rides
        # as its own segment id (-1), so padded queries attend only among
        # padded keys (finite outputs, discarded by the event-mask zeroing
        # between layers).
        seg = None
        if not use_dep_fused and (ring_ctx is not None or use_pallas or use_splash or use_band):
            base_seg = (
                segment_ids if segment_ids is not None else jnp.zeros((B, S), dtype=jnp.int32)
            )
            pad_mask = attention_mask if attention_mask is not None else jnp.ones((B, S), bool)
            seg = jnp.where(pad_mask, base_seg.astype(jnp.int32), -1)
            if not use_pallas:
                # The ring, splash and band paths' contract is heads-first
                # (B, H, S, D); fused paths exclude the cache branches, so
                # this is the only transpose. The flash op reads the
                # projections' (B, S, H, D) as it is.
                query, key, value = heads_first(query), heads_first(key), heads_first(value)

        if use_dep_fused:
            from ..ops.band_attention import dep_graph_attention

            window = self.window_size if self.attention_type == "local" else None
            # Attention dropout rides as a precomputed keep-mask so the
            # Pallas kernel and the fused-XLA fallback apply the IDENTICAL
            # mask (ops/pallas_dep_graph.py module docs) — the r08 parity
            # contract extends to training-mode dropout. Semantics match
            # nn.Dropout: keep -> p / keep_prob, drop -> 0.
            rate = float(cfg.attention_dropout)
            dropout_mask = None
            if rate > 0.0 and self.has_rng("dropout"):
                dropout_mask = jax.random.bernoulli(
                    self.make_rng("dropout"),
                    1.0 - rate,
                    (query.shape[0], query.shape[1], key.shape[1], num_heads),
                )
            # query/key/value are still (N, S, H, D) — the matmuls' natural
            # layout; the fused op contracts in place, so the dep-graph walk
            # performs no transposes at all.
            attn_output = dep_graph_attention(
                query,
                key,
                value,
                q_offset=1 if static_kv_first else 0,
                window=window,
                dropout_mask=dropout_mask,
                dropout_rate=rate,
                # auto: the Pallas kernel on TPU, fused-XLA elsewhere;
                # config/$ESGPT_PALLAS_IMPL override (ops/impl_select.py).
                impl=getattr(cfg, "dep_graph_attention_impl", None),
            )
            outputs = {"present_key_value": None, "_heads_first_out": False}
        elif ring_ctx is not None:
            from ..parallel.ring_attention import ring_attention

            window = self.window_size if self.attention_type == "local" else None
            attn_output = ring_attention(
                query,
                key,
                value,
                seg,
                mesh=ring_ctx.mesh,
                axis_name=ring_ctx.axis_name,
                data_axis=ring_ctx.data_axis,
                head_axis=ring_ctx.head_axis,
                window_size=window,
            )
            outputs = {"present_key_value": None, "_heads_first_out": True}
        elif use_pallas:
            from ..ops.pallas_flash import flash_attention
            from ..parallel.context import per_batch_shard

            # GPT-Neo lineage: logits are NOT scaled by 1/sqrt(head_dim).
            # bf16 q/k/v ride the MXU directly (the kernels keep logits and
            # softmax statistics in fp32); fp32 mode keeps fp32 inputs. Block
            # and chunk sizes follow the static shapes
            # (`ops.pallas_flash.flash_block_sizes`), what is visited the
            # segment ids; the bounds are computed per batch shard.
            kernel_dt = dt if dt == jnp.bfloat16 else jnp.float32
            attn_output = per_batch_shard(
                lambda q, k, v, s: flash_attention(q, k, v, s, sm_scale=1.0),
                query.astype(kernel_dt),
                key.astype(kernel_dt),
                value.astype(kernel_dt),
                seg,
            ).astype(value.dtype)
            outputs = {"present_key_value": None, "_heads_first_out": False}
        elif use_band:
            from ..ops.band_attention import band_local_attention

            # chunk_size is left at its default C=window — the settled
            # production choice: fatter chunks win layer microbenches but
            # lost the interleaved step-level A/B (before PR 22).
            attn_output = band_local_attention(query, key, value, seg, self.window_size)
            outputs = {"present_key_value": None, "_heads_first_out": True}
        elif use_splash:
            from jax.experimental.pallas.ops.tpu.splash_attention import (
                splash_attention_kernel as splash_kernel,
            )
            from jax.experimental.pallas.ops.tpu.splash_attention import (
                splash_attention_mask as splash_mask,
            )

            # Reference local rule (transformer.py:109-118): k <= q and
            # k > q - window, i.e. LocalMask left span = window - 1, right 0
            # (right=0 makes the mask causal).
            mask = splash_mask.MultiHeadMask(
                [
                    splash_mask.LocalMask((S, S), (self.window_size - 1, 0), 0)
                    for _ in range(num_heads)
                ]
            )
            kernel = splash_kernel.make_splash_mha(mask, head_shards=1, q_seq_shards=1)

            # Splash applies no logit scaling — matching the unscaled GPT-Neo
            # lineage — and accumulates softmax statistics in fp32.
            kernel_dt = dt if dt == jnp.bfloat16 else jnp.float32
            from ..parallel.context import per_batch_shard

            attn_output = per_batch_shard(
                jax.vmap(
                    lambda q, k, v, s: kernel(
                        q, k, v, segment_ids=splash_kernel.SegmentIds(q=s, kv=s)
                    )
                ),
                query.astype(kernel_dt),
                key.astype(kernel_dt),
                value.astype(kernel_dt),
                seg,
            ).astype(value.dtype)
            outputs = {"present_key_value": None, "_heads_first_out": True}
        else:
            window = self.window_size if self.attention_type == "local" else None
            causal = make_causal_mask(q_positions, k_positions, window)  # (Q, K)

            # Layout: cached paths carry (B, H, S, D); the uncached fallback
            # stays (B, S, H, D) and contracts heads in place — no relayout.
            bhsd = layer_past is not None or use_cache
            # fp32 logits for numerical parity with the reference. Under bf16
            # the multiply stays on the MXU in bf16 with fp32 accumulation
            # (preferred_element_type) instead of upcasting the operands.
            attn_weights = jnp.einsum(
                "bhqd,bhkd->bhqk" if bhsd else "bqhd,bkhd->bhqk",
                query,
                key,
                preferred_element_type=jnp.float32,
            )
            # Scalar-cursor caches give a shared (Q, K) causal plane; per-row
            # cursors (vector-length caches) a (B, Q, K) one.
            mask = causal[None, None] if causal.ndim == 2 else causal[:, None]
            if valid_k is not None:
                mask = mask & (
                    valid_k[None, None, None, :]
                    if valid_k.ndim == 1
                    else valid_k[:, None, None, :]
                )
            if segment_ids is not None:
                if layer_past is not None or static_kv_first:
                    raise ValueError(
                        "Packed (segment_ids) batches support neither KV caching nor "
                        "dep-graph static_kv_first attention."
                    )
                # Packed rows: queries attend only within their own segment.
                mask = mask & (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
            attn_weights = jnp.where(mask, attn_weights, jnp.finfo(jnp.float32).min)

            if attention_mask is not None:
                # (B, K) boolean padding mask -> additive, matching expand_mask
                # (transformer.py:28-45).
                additive = jnp.where(
                    attention_mask[:, None, None, :], 0.0, jnp.finfo(jnp.float32).min
                )
                attn_weights = attn_weights + additive

            # Clamp so stacked masks cannot overflow to -inf: a fully-masked row
            # then softmaxes to uniform (finite) rather than NaN.
            attn_weights = jnp.maximum(attn_weights, jnp.finfo(jnp.float32).min)
            attn_weights = jax.nn.softmax(attn_weights, axis=-1).astype(value.dtype)
            attn_dropout = nn.Dropout(rate=float(cfg.attention_dropout), name="attn_dropout")
            attn_weights = attn_dropout(attn_weights, deterministic=not self.has_rng("dropout"))

            if bhsd:
                attn_output = jnp.einsum("bhqk,bhkd->bhqd", attn_weights, value)
            else:
                # (B, q, H, D) out: merges heads with a plain reshape below.
                attn_output = jnp.einsum("bhqk,bkhd->bqhd", attn_weights, value)
            outputs = {"present_key_value": present, "_heads_first_out": bhsd}
            if output_attentions:
                outputs["attn_weights"] = attn_weights

        # Shared tail: merge heads, project, residual dropout. Fused-kernel
        # and cached outputs are heads-first and need the swap; the uncached
        # einsum output is already (B, q, H, D).
        # Every path's attention output is checkpoint-named so the
        # "save_attention" remat policy (`remat_block_cls`) can pin exactly
        # this tensor: under selective remat the backward then reuses the
        # flash/splash/band custom-call results instead of re-executing them
        # (the memory-efficient-attention + remat interplay of Rabe & Staats,
        # arXiv 2112.05682). A no-op identity under every other policy.
        attn_output = checkpoint_name(attn_output, ATTENTION_CHECKPOINT_NAME)
        if outputs.pop("_heads_first_out"):
            attn_output = attn_output.swapaxes(-3, -2)
        attn_output = attn_output.reshape(B, q_len, embed_dim)
        with scope("attn_proj"):
            attn_output = out_proj(attn_output)
        resid_dropout = nn.Dropout(rate=float(cfg.resid_dropout), name="resid_dropout")
        attn_output = resid_dropout(attn_output, deterministic=not self.has_rng("dropout"))
        return attn_output, outputs


class InnerAttention(nn.Module):
    """LayerNorm + attention-type dispatch (reference ``transformer.py:285``)."""

    config: StructuredTransformerConfig
    layer_id: int = 0
    is_seq: bool = True

    @nn.compact
    def __call__(self, hidden_states, **kwargs):
        cfg = self.config
        layers = cfg.seq_attention_layers if self.is_seq else cfg.dep_graph_attention_layers
        attention_type = layers[self.layer_id]
        if attention_type == "local":
            window_size = cfg.seq_window_size if self.is_seq else cfg.dep_graph_window_size
        else:
            window_size = None
        if attention_type not in ("global", "local"):
            raise ValueError(
                "Only attn layer types 'global' and 'local' exist, but got `config.attention_layers`: "
                f"{layers}. Select attn layer types from ['global', 'local'] only."
            )
        with scope("norm"):
            normed = nn.LayerNorm(
                epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype, name="layer_norm"
            )(hidden_states)
        # All that lies between the projections (which name themselves
        # inside): masks, segment ids, the kernel or the einsum, relayouts.
        kind = {"global": "attn_global", "local": "attn_local"}[attention_type] if self.is_seq else "dep_graph"
        with scope(kind):
            return InnerSelfAttention(
                cfg,
                attention_type=attention_type,
                window_size=window_size,
                is_dep_graph=not self.is_seq,
                name="attention",
            )(normed, **kwargs)


class InnerMLP(nn.Module):
    """Feed-forward block (reference ``transformer.py:361``)."""

    config: StructuredTransformerConfig

    @nn.compact
    @scoped("mlp")
    def __call__(self, hidden_states):
        cfg = self.config
        inner_dim = cfg.intermediate_size if cfg.intermediate_size is not None else 4 * cfg.hidden_size
        dense_init = nn.initializers.normal(stddev=cfg.init_std)
        dt = cfg.compute_dtype
        h = nn.Dense(inner_dim, kernel_init=dense_init, dtype=dt, name="c_fc")(hidden_states)
        h = ACT2FN[cfg.activation_function](h)
        h = nn.Dense(cfg.hidden_size, kernel_init=dense_init, dtype=dt, name="c_proj")(h)
        return nn.Dropout(rate=float(cfg.resid_dropout))(h, deterministic=not self.has_rng("dropout"))


class InnerBlock(nn.Module):
    """Pre-LN attention + MLP residual block (reference ``transformer.py:394``)."""

    config: StructuredTransformerConfig
    layer_id: int = 0
    is_seq: bool = True

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        layer_past=None,
        use_cache=False,
        output_attentions=False,
        static_kv_first: bool = False,
        segment_ids=None,
    ):
        residual = hidden_states if not static_kv_first else hidden_states[:, 1:, :]

        attn_output, outputs = InnerAttention(self.config, self.layer_id, self.is_seq, name="attn")(
            hidden_states,
            attention_mask=attention_mask,
            layer_past=layer_past,
            use_cache=use_cache,
            output_attentions=output_attentions,
            static_kv_first=static_kv_first,
            segment_ids=segment_ids,
        )
        hidden_states = attn_output + residual

        residual = hidden_states
        with scope("norm"):
            normed = nn.LayerNorm(
                epsilon=self.config.layer_norm_epsilon,
                dtype=self.config.compute_dtype,
                name="layer_norm",
            )(hidden_states)
        feed_forward = InnerMLP(self.config, name="mlp")(normed)
        hidden_states = residual + feed_forward

        if not use_cache:
            outputs.pop("present_key_value", None)
        return hidden_states, outputs


class ConditionallyIndependentPointProcessInputLayer(nn.Module):
    """Data embedding + temporal encoding for CI models (``transformer.py:622``)."""

    config: StructuredTransformerConfig

    @nn.compact
    @scoped("embed")
    def __call__(self, batch: EventStreamBatch) -> Array:
        cfg = self.config
        data_embed = DataEmbeddingLayer(
            n_total_embeddings=max(cfg.vocab_size, 1),
            out_dim=cfg.hidden_size,
            categorical_embedding_dim=cfg.categorical_embedding_dim,
            numerical_embedding_dim=cfg.numerical_embedding_dim,
            static_embedding_mode=cfg.static_embedding_mode,
            split_by_measurement_indices=None,
            do_normalize_by_measurement_index=cfg.do_normalize_by_measurement_index,
            static_weight=cfg.static_embedding_weight,
            dynamic_weight=cfg.dynamic_embedding_weight,
            categorical_weight=cfg.categorical_embedding_weight,
            numerical_weight=cfg.numerical_embedding_weight,
            compute_dtype=cfg.compute_dtype,
            name="data_embedding_layer",
        )(batch)
        t = batch.time if batch.time is not None else time_from_deltas(batch)
        time_embed = TemporalPositionEncoding(embedding_dim=cfg.hidden_size, name="time_embedding_layer")(t)
        # Sinusoids are computed in fp32 (large cumulative-minute inputs);
        # the sum drops to the compute dtype only afterwards.
        embed = (data_embed + time_embed).astype(cfg.compute_dtype)

        if batch.event_mask is not None:
            embed = jnp.where(batch.event_mask[..., None], embed, 0.0)

        return nn.Dropout(rate=float(cfg.input_dropout))(embed, deterministic=not self.has_rng("dropout"))


_NO_REMAT = object()


def _remat_policy(config: StructuredTransformerConfig, use_flag: bool = False):
    """Resolves ``config.gradient_checkpointing`` into a jax.checkpoint
    policy, ``None`` for whole-block remat, or the `_NO_REMAT` sentinel."""
    mode = getattr(config, "gradient_checkpointing", "none")
    if use_flag and mode == "none":
        mode = "block"
    if mode == "none":
        return _NO_REMAT
    return {
        "block": None,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "save_attention": jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            jax.checkpoint_policies.save_only_these_names(ATTENTION_CHECKPOINT_NAME),
        ),
    }[mode]


def remat_block_cls(config: StructuredTransformerConfig, use_flag: bool = False, block_cls=None):
    """`InnerBlock` (or ``block_cls``, a block of the same call signature),
    wrapped per the configured rematerialization policy.

    ``config.gradient_checkpointing`` selects the policy
    (r06 MFU round): ``"none"`` (config default — at toy shapes every policy only
    adds recompute), ``"block"``
    (whole-block ``nn.remat``, minimum memory), ``"dots"`` /
    ``"dots_no_batch"`` (``jax.checkpoint`` selective policies saving matmul
    outputs — the memory/FLOPs middle ground for configs whose activations
    overflow HBM), and ``"save_attention"`` (``dots_no_batch`` composed with
    ``save_only_these_names`` on the checkpoint-named attention outputs —
    the backward replays only elementwise work and never re-executes the
    flash/splash/band attention custom-calls, the dominant recompute term
    ``dots_no_batch`` pays at production width). The legacy
    ``use_gradient_checkpointing`` bool maps to ``"block"``.
    """
    policy = _remat_policy(config, use_flag)
    block_cls = InnerBlock if block_cls is None else block_cls
    if policy is _NO_REMAT:
        return block_cls
    # Args seen by the lifted transform: (module, hidden, attn_mask,
    # layer_past, use_cache, output_attentions, static_kv_first).
    return nn.remat(block_cls, static_argnums=(4, 5, 6), policy=policy)


# ------------------------------------------------------- scan-over-layers
def scan_period(config: StructuredTransformerConfig) -> tuple[int, int]:
    """``(period, n_groups)`` of the attention-type pattern under scan.

    ``nn.scan`` requires every scan step to trace the identical program, but
    the per-layer attention types (``seq_attention_layers``, and for NA
    models ``dep_graph_attention_layers``) may alternate — the default stack
    is ``["local", "global"]`` repeated. The scan body therefore unrolls one
    *pattern period* (the smallest ``p`` dividing ``num_hidden_layers`` such
    that every attention-type list is ``p``-periodic) and the scan runs over
    ``num_hidden_layers / p`` stacked parameter groups. Uniform stacks give
    ``p == 1`` (a true per-layer scan); an aperiodic hand-written list
    degenerates to ``p == L`` (one group — correct, but compiling every
    layer, i.e. no better than unrolled).
    """
    L = config.num_hidden_layers
    lists = [config.seq_attention_layers]
    if getattr(config, "dep_graph_attention_layers", None) is not None:
        lists.append(config.dep_graph_attention_layers)
    for p in range(1, L + 1):
        if L % p != 0:
            continue
        if all(lst[i] == lst[i % p] for lst in lists for i in range(L)):
            return p, L // p
    return L, 1


def _stack_trees(trees):
    """Stacks a list of like-structured pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def _unstack_tree(tree, n: int):
    """Splits a stacked pytree back into ``n`` per-layer pytrees."""
    return [jax.tree_util.tree_map(lambda x: x[g], tree) for g in range(n)]


class _CIScanBody(nn.Module):
    """One scan step of the CI encoder: a pattern period of `InnerBlock`s.

    ``layer_id`` within the body indexes the pattern position (0..period-1);
    periodicity (`scan_period`) guarantees ``seq_attention_layers[g*p + j]
    == seq_attention_layers[j]`` for every group ``g``, so the one traced
    body is exact for all of them. Per-layer KV caches ride the scan as
    stacked inputs (``xs``) and the updated caches return as stacked
    outputs, keeping the `KVCache`-tuple interface of the unrolled path at
    the module boundary.
    """

    config: StructuredTransformerConfig
    period: int
    use_cache: bool = False
    output_hidden_states: bool = False

    @nn.compact
    def __call__(self, hidden_states, xs, attention_mask, segment_ids, event_mask):
        presents = []
        hiddens = []
        for j in range(self.period):
            if self.output_hidden_states:
                hiddens.append(hidden_states)
            block = InnerBlock(self.config, layer_id=j, is_seq=True, name=f"b{j}")
            hidden_states, outputs = block(
                hidden_states,
                attention_mask,
                xs[j] if xs is not None else None,
                self.use_cache,
                False,
                False,
                segment_ids,
            )
            if event_mask is not None:
                hidden_states = jnp.where(event_mask[..., None], hidden_states, 0.0)
            if self.use_cache:
                presents.append(outputs.get("present_key_value"))
        ys = (
            tuple(presents) if self.use_cache else None,
            tuple(hiddens) if self.output_hidden_states else None,
        )
        return hidden_states, ys


class _NAScanBody(nn.Module):
    """One scan step of the NA encoder: a pattern period of
    `StructuredTransformerBlock`s, with the two-level cache plumbing (seq +
    dep-graph `KVCache`s per layer) threaded through the scan as stacked
    inputs/outputs. The cache-mode flags are static attributes — they are
    uniform across layers by the NA state machine's construction."""

    config: StructuredTransformerConfig
    period: int
    update_seq_cache: bool = False
    update_dep_graph_cache: bool = False
    prepend_graph_with_history_embeddings: bool = True
    update_last_graph_el_to_history_embedding: bool = True
    output_hidden_states: bool = False

    @nn.compact
    def __call__(self, hidden_states, xs, seq_attention_mask, event_mask, segment_ids):
        seq_xs, dep_xs = xs if xs is not None else (None, None)
        presents_seq, presents_dep, hiddens = [], [], []
        for j in range(self.period):
            if self.output_hidden_states:
                hiddens.append(hidden_states)
            block = StructuredTransformerBlock(self.config, layer_id=j, name=f"b{j}")
            hidden_states, extra = block(
                hidden_states,
                seq_attention_mask=seq_attention_mask,
                event_mask=event_mask,
                segment_ids=segment_ids,
                prepend_graph_with_history_embeddings=self.prepend_graph_with_history_embeddings,
                update_last_graph_el_to_history_embedding=self.update_last_graph_el_to_history_embedding,
                seq_module_kwargs=dict(
                    layer_past=seq_xs[j] if seq_xs is not None else None,
                    use_cache=self.update_seq_cache,
                    output_attentions=False,
                ),
                dep_graph_module_kwargs=dict(
                    layer_past=dep_xs[j] if dep_xs is not None else None,
                    use_cache=self.update_dep_graph_cache,
                    output_attentions=False,
                ),
            )
            if self.update_seq_cache:
                presents_seq.append(extra["seq_module"]["present_key_value"])
            if self.update_dep_graph_cache:
                presents_dep.append(extra["dep_graph_module"]["present_key_value"])
        ys = (
            tuple(presents_seq) if self.update_seq_cache else None,
            tuple(presents_dep) if self.update_dep_graph_cache else None,
            tuple(hiddens) if self.output_hidden_states else None,
        )
        return hidden_states, ys


def _scan_stack_cls(body_cls, config, use_flag: bool, n_groups: int):
    """``nn.scan`` over the (optionally remat-wrapped) scan body.

    Composes per-layer rematerialization with the scan exactly as the
    pjit/TPUv4 playbook prescribes: the remat policy (including r06's
    ``save_attention``) applies to ONE body, and the scan stacks it
    ``n_groups`` deep with ``variable_axes={"params": 0}`` — so HLO size and
    compile time are depth-independent. ``prevent_cse=False`` is safe (and
    measurably faster) under scan: the loop boundary already prevents the
    cross-iteration CSE that standalone remat must guard against.
    """
    policy = _remat_policy(config, use_flag)
    if policy is not _NO_REMAT:
        body_cls = nn.remat(body_cls, policy=policy, prevent_cse=False)
    return nn.scan(
        body_cls,
        variable_axes={"params": 0},
        split_rngs={"params": True, "dropout": True},
        in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast),
        out_axes=0,
        length=n_groups,
    )


def _group_layer_trees(per_layer, period: int, n_groups: int):
    """``[layer0, layer1, ...]`` → per-pattern-position stacked trees:
    ``tuple_j(stack_g(per_layer[g*period + j]))`` — the xs layout the scan
    bodies consume."""
    return tuple(
        _stack_trees([per_layer[g * period + j] for g in range(n_groups)])
        for j in range(period)
    )


def _ungroup_layer_trees(ys, period: int, n_groups: int) -> list:
    """Inverse of `_group_layer_trees` for stacked scan outputs."""
    per_position = [_unstack_tree(ys[j], n_groups) for j in range(period)]
    return [per_position[j][g] for g in range(n_groups) for j in range(period)]


_LAYER_KEY_RE = re.compile(r"^h(\d+)$")


def _is_layer_dict(node, num_layers: int) -> bool:
    from collections.abc import Mapping

    if not isinstance(node, Mapping):
        return False
    return all(f"h{i}" in node for i in range(num_layers))


def stack_layer_params(params, config: StructuredTransformerConfig):
    """Migrates an **unrolled** parameter tree to the **scanned** layout.

    Wherever a subtree holds the per-layer scopes ``h0..h{L-1}`` (the CI and
    NA encoders, and every model wrapping them), they are replaced by one
    ``h_scan`` scope whose pattern-position children ``b0..b{p-1}`` hold the
    layer parameters stacked ``(L/p, ...)`` along a new leading axis — the
    exact tree `scan_layers=True` initializes, so an unrolled checkpoint
    restores into a scanned model (and vice versa via
    `unstack_layer_params`). Pure relayout: values are bit-identical.
    """
    from collections.abc import Mapping

    L = config.num_hidden_layers
    p, G = scan_period(config)

    def walk(node):
        if not isinstance(node, Mapping):
            return node
        if _is_layer_dict(node, L):
            out = {
                k: walk(v) for k, v in node.items() if not _LAYER_KEY_RE.match(str(k))
            }
            out["h_scan"] = {
                f"b{j}": _stack_trees([node[f"h{g * p + j}"] for g in range(G)])
                for j in range(p)
            }
            return out
        return {k: walk(v) for k, v in node.items()}

    return walk(params)


def unstack_layer_params(params, config: StructuredTransformerConfig):
    """Migrates a **scanned** parameter tree back to the **unrolled** layout
    (`stack_layer_params`' inverse) — e.g. to serve a scan-trained
    checkpoint through a deployment that keeps the unrolled decode program.
    """
    from collections.abc import Mapping

    L = config.num_hidden_layers
    p, G = scan_period(config)

    def walk(node):
        if not isinstance(node, Mapping):
            return node
        if "h_scan" in node and isinstance(node["h_scan"], Mapping):
            out = {k: walk(v) for k, v in node.items() if k != "h_scan"}
            groups = node["h_scan"]
            for j in range(p):
                for g, tree in enumerate(_unstack_tree(groups[f"b{j}"], G)):
                    out[f"h{g * p + j}"] = tree
            return out
        return {k: walk(v) for k, v in node.items()}

    return walk(params)


def _streams_sum(hidden_states):
    """Hyper-connected residual streams (a tuple of planes) read out as one
    state, summed in float32; one stream's state as it is."""
    if not isinstance(hidden_states, tuple):
        return hidden_states
    return sum(h.astype(jnp.float32) for h in hidden_states).astype(hidden_states[0].dtype)


class ConditionallyIndependentPointProcessTransformer(nn.Module):
    """Stack of `InnerBlock`s over whole-event embeddings.

    Reference: ``transformer.py:675-848``. Rematerialization is applied per
    block per the config policy (`remat_block_cls`).
    """

    config: StructuredTransformerConfig
    use_gradient_checkpointing: bool = False

    @nn.compact
    def __call__(
        self,
        batch: EventStreamBatch | None = None,
        input_embeds: Array | None = None,
        past: tuple[KVCache, ...] | None = None,
        use_cache: bool = False,
        output_attentions: bool = False,
        output_hidden_states: bool = False,
    ) -> TransformerOutputWithPast:
        cfg = self.config
        if input_embeds is None:
            input_embeds = ConditionallyIndependentPointProcessInputLayer(cfg, name="input_layer")(batch)

        # Chunk-local padding mask; with a cache, each attention layer splices
        # these bits into its KVCache.mask to recover the full-buffer mask.
        attention_mask = batch.event_mask if batch is not None else None

        hidden_states = input_embeds
        presents = [] if use_cache else None
        all_attentions = [] if output_attentions else None
        all_hidden = [] if output_hidden_states else None

        kinds = cfg.uses_layer_kinds  # its blocks refuse a cache themselves
        if kinds and getattr(cfg, "scan_layers", False):
            raise NotImplementedError("scan_layers does not serve the kinds block yet")
        if getattr(cfg, "scan_layers", False):
            # Depth-independent compilation (r10): ONE pattern-period body is
            # traced and scanned over stacked (L/p, ...) parameters; per-layer
            # KV caches thread through as stacked scan inputs/outputs so the
            # cached decode paths keep the tuple-of-`KVCache` interface.
            if output_attentions:
                raise NotImplementedError(
                    "scan_layers=True does not support output_attentions; migrate "
                    "the checkpoint to the unrolled layout (unstack_layer_params) "
                    "for attention introspection."
                )
            p, n_groups = scan_period(cfg)
            xs = _group_layer_trees(list(past), p, n_groups) if past is not None else None
            event_mask = batch.event_mask if batch is not None else None
            stack = _scan_stack_cls(
                _CIScanBody, cfg, self.use_gradient_checkpointing, n_groups
            )(
                cfg,
                period=p,
                use_cache=use_cache,
                output_hidden_states=output_hidden_states,
                name="h_scan",
            )
            hidden_states, (present_ys, hidden_ys) = stack(
                hidden_states,
                xs,
                attention_mask,
                batch.segment_ids if batch is not None else None,
                event_mask,
            )
            if presents is not None:
                presents = _ungroup_layer_trees(present_ys, p, n_groups)
            if all_hidden is not None:
                all_hidden = _ungroup_layer_trees(hidden_ys, p, n_groups)
        else:
            kinds_block = None
            if kinds:
                from .blocks import KindsBlock as kinds_block
            block_cls = remat_block_cls(cfg, self.use_gradient_checkpointing, kinds_block)
            if cfg.hc_mult > 1:
                # Hyper-connected residual streams (`models/hyper_connections.py`): the embedding
                # replicated in, a tuple of n planes between the blocks, summed out before ln_f.
                hidden_states = (hidden_states,) * cfg.hc_mult

            for i in range(cfg.num_hidden_layers):
                if all_hidden is not None:
                    all_hidden.append(_streams_sum(hidden_states))  # a layer's state as `ln_f` would read it
                layer_past = past[i] if past is not None else None
                block = block_cls(cfg, layer_id=i, is_seq=True, name=f"h{i}")
                hidden_states, outputs = block(
                    hidden_states,
                    attention_mask,
                    layer_past,
                    use_cache,
                    output_attentions,
                    False,
                    batch.segment_ids if batch is not None else None,
                )
                # Reference parity: zero masked events' hidden states between
                # layers (``transformer.py:820-825``).
                if batch is not None and batch.event_mask is not None:
                    hidden_states = jax.tree_util.tree_map(
                        lambda h: jnp.where(batch.event_mask[..., None], h, 0.0), hidden_states
                    )
                if presents is not None:
                    presents.append(outputs.get("present_key_value"))
                if all_attentions is not None:
                    all_attentions.append(outputs.get("attn_weights"))

        if isinstance(hidden_states, tuple):
            with scope("hc_mix"):
                hidden_states = _streams_sum(hidden_states)
        with scope("norm"):
            if kinds:
                from .latent_attention import RMSNorm

                hidden_states = RMSNorm(cfg.layer_norm_epsilon, cfg.compute_dtype, name="ln_f")(hidden_states)
            else:
                hidden_states = nn.LayerNorm(
                    epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype, name="ln_f"
                )(hidden_states)
        if all_hidden is not None:
            all_hidden.append(hidden_states)

        return TransformerOutputWithPast(
            last_hidden_state=hidden_states,
            past_key_values=tuple(presents) if presents is not None else None,
            hidden_states=tuple(all_hidden) if all_hidden is not None else None,
            attentions=tuple(all_attentions) if all_attentions is not None else None,
        )


class StructuredTransformerBlock(nn.Module):
    """Seq + dep-graph structured block (reference ``transformer.py:464``).

    The sequence and dep-graph halves are full `InnerBlock`s or bare
    `InnerAttention`s per ``do_full_block_in_{seq,dep_graph}_attention``.
    """

    config: StructuredTransformerConfig
    layer_id: int = 0

    @nn.compact
    def __call__(self, *args, **kwargs):
        cfg = self.config
        if cfg.do_full_block_in_seq_attention:
            seq_module = lambda: InnerBlock(cfg, self.layer_id, is_seq=True, name="seq_block")
        else:
            seq_module = lambda: InnerAttention(cfg, self.layer_id, is_seq=True, name="seq_attn")
        if cfg.do_full_block_in_dep_graph_attention:
            dep_module = lambda: InnerBlock(cfg, self.layer_id, is_seq=False, name="dep_graph_block")
        else:
            dep_module = lambda: InnerAttention(cfg, self.layer_id, is_seq=False, name="dep_graph_attn")
        return StructuredAttention(
            seq_module=seq_module, dep_graph_module=dep_module, name="block"
        )(*args, **kwargs)


class NestedAttentionPointProcessInputLayer(nn.Module):
    """Dep-graph-split input embeddings for NA models (``transformer.py:851``).

    Time embeddings join graph slot 0; a cumsum over the graph axis makes the
    final element a whole-event summary.
    """

    config: StructuredTransformerConfig

    @nn.compact
    @scoped("embed")
    def __call__(
        self,
        batch: EventStreamBatch,
        dep_graph_el_generation_target: int | None = None,
        partial_content_levels: bool = False,
    ) -> Array:
        cfg = self.config
        split_by_measurement_indices = []
        for measurement_list in cfg.measurements_per_dep_graph_level:
            out_list = []
            for measurement in measurement_list:
                if isinstance(measurement, str):
                    out_list.append(cfg.measurements_idxmap[measurement])
                elif isinstance(measurement, (tuple, list)) and len(measurement) == 2:
                    out_list.append((cfg.measurements_idxmap[measurement[0]], measurement[1]))
                else:
                    raise ValueError(
                        f"Unexpected measurement {type(measurement)}: {measurement}\n"
                        f"{cfg.measurements_per_dep_graph_level}"
                    )
            split_by_measurement_indices.append(tuple(out_list))

        embed_layer = DataEmbeddingLayer(
            n_total_embeddings=max(cfg.vocab_size, 1),
            out_dim=cfg.hidden_size,
            categorical_embedding_dim=cfg.categorical_embedding_dim,
            numerical_embedding_dim=cfg.numerical_embedding_dim,
            static_embedding_mode=cfg.static_embedding_mode,
            split_by_measurement_indices=tuple(split_by_measurement_indices),
            do_normalize_by_measurement_index=cfg.do_normalize_by_measurement_index,
            static_weight=cfg.static_embedding_weight,
            dynamic_weight=cfg.dynamic_embedding_weight,
            categorical_weight=cfg.categorical_embedding_weight,
            numerical_weight=cfg.numerical_embedding_weight,
            compute_dtype=cfg.compute_dtype,
            name="data_embedding_layer",
        )

        t = batch.time if batch.time is not None else time_from_deltas(batch)
        time_embed = TemporalPositionEncoding(embedding_dim=cfg.hidden_size, name="time_embedding_layer")(t)

        def slots_from(b: EventStreamBatch) -> Array:
            # Time-add + cumsum in fp32 (error compounds over graph levels),
            # then drop to the compute dtype.
            e = embed_layer(b).astype(jnp.float32).at[:, :, 0, :].add(time_embed)
            return jnp.cumsum(e, axis=2).astype(cfg.compute_dtype)

        if partial_content_levels:
            # Generation-parity graph slots (speculative-decoding verify):
            # the cached per-level decode writes graph element ``l``'s
            # key/value when the event holds ONLY levels <= l — and in JOINT
            # embedding mode every slot's embedding sums ALL present tokens
            # (out-of-group tokens at weight 1), so a teacher-forced slot
            # computed from the finished event differs from what the walk
            # actually wrote. Rebuild slot ``l`` from the batch with tokens
            # of later levels masked away (they are plain zero-padding at
            # walk time, which is exactly what masking produces) — one
            # embedding pass per level, identical queries/keys to the
            # sequential walk. Slot G-1 naturally sees the whole event (the
            # whole-event/contextualization element is built post-walk).
            lvl_of = na_level_of_measurement(cfg)
            slots = []
            for level in range(len(cfg.measurements_per_dep_graph_level)):
                masked = mask_batch_to_levels(batch, lvl_of, level)
                slots.append(slots_from(masked)[:, :, level, :])
            embed = jnp.stack(slots, axis=2)
        else:
            embed = slots_from(batch)
        # embed: (B, L, G, H)

        if dep_graph_el_generation_target is not None:
            # Cached generation: only the (target-1)-th graph element is new.
            embed = embed[:, :, dep_graph_el_generation_target - 1][:, :, None, :]

        if batch.event_mask is not None:
            embed = jnp.where(batch.event_mask[:, :, None, None], embed, 0.0)

        return nn.Dropout(rate=float(cfg.input_dropout))(embed, deterministic=not self.has_rng("dropout"))


@struct.dataclass
class NAPast:
    """The two-level NA cache: per-layer seq caches + dep-graph caches."""

    seq_past: Optional[tuple] = None
    dep_graph_past: Optional[tuple] = None


def na_level_of_measurement(config: StructuredTransformerConfig) -> Array:
    """Static measurement-index -> dep-graph-level lookup table.

    Unlisted measurements (functors, padding index 0) map to level 0 —
    present from the event's first write. THE one level map for every
    partial-content consumer (the input layer's
    ``partial_content_levels``, the spec engine's correction-event strip,
    and the draft-prefill walk replay): they must agree bit-for-bit or the
    NA verify exactness contract breaks, hence one builder. Split-mode
    entries (the same measurement's categorical/numerical halves on
    different levels) would need element-granular levels — unsupported,
    loudly.
    """
    import numpy as np

    lvl = np.zeros(max(config.measurements_idxmap.values()) + 1, np.int32)
    for level, meas_list in enumerate(config.measurements_per_dep_graph_level):
        for m in meas_list:
            if isinstance(m, (tuple, list)):
                raise ValueError(
                    "split-mode (CATEGORICAL_ONLY/NUMERICAL_ONLY) dep-graph "
                    "levels are not supported by per-level content masking "
                    f"(speculative decoding) yet; got {m!r}"
                )
            lvl[config.measurements_idxmap[m]] = level
    return jnp.asarray(lvl)


def mask_batch_to_levels(
    batch: EventStreamBatch, level_of_meas: Array, level
) -> EventStreamBatch:
    """The batch with dynamic tokens of dep-graph levels > ``level`` masked
    away (index/measurement -> 0, value -> 0, value mask off) — exactly the
    zero-padding an in-progress event carries before those levels are
    written, which is what makes partial-content replays bit-identical to
    the sequential walk."""
    keep = level_of_meas[batch.dynamic_measurement_indices] <= level
    return batch.replace(
        dynamic_indices=jnp.where(keep, batch.dynamic_indices, 0),
        dynamic_measurement_indices=jnp.where(
            keep, batch.dynamic_measurement_indices, 0
        ),
        dynamic_values=jnp.where(keep, batch.dynamic_values, 0.0),
        dynamic_values_mask=batch.dynamic_values_mask & keep,
    )


class NestedAttentionPointProcessTransformer(nn.Module):
    """NA encoder: stack of `StructuredTransformerBlock`s with the three-way
    cache state machine (reference ``transformer.py:939-1233``).

    ``dep_graph_el_generation_target`` (static) selects the generation mode:
    ``None`` = full forward; ``0`` = contextualize the just-completed event
    and reset the dep-graph cache to the history embedding; ``>0`` = decode
    one new graph element against the dep-graph cache.
    """

    config: StructuredTransformerConfig
    use_gradient_checkpointing: bool = False

    @nn.compact
    def __call__(
        self,
        batch: EventStreamBatch | None = None,
        input_embeds: Array | None = None,
        past: NAPast | None = None,
        use_cache: bool = False,
        output_attentions: bool = False,
        output_hidden_states: bool = False,
        dep_graph_el_generation_target: int | None = None,
        last_event_index: Array | None = None,
        partial_content_levels: bool = False,
        history_head: tuple | None = None,
        return_contextualized: bool = False,
    ) -> TransformerOutputWithPast:
        cfg = self.config
        segment_ids = batch.segment_ids if batch is not None else None
        if (history_head is not None or return_contextualized) and getattr(
            cfg, "scan_layers", False
        ):
            raise NotImplementedError(
                "history_head / return_contextualized (the speculative-decoding "
                "verify plumbing) require the unrolled layer stack; migrate the "
                "checkpoint with unstack_layer_params"
            )
        if segment_ids is not None and (use_cache or past is not None):
            raise NotImplementedError(
                "Packed (segment_ids) batches do not support KV-cached NA decoding; "
                "train/eval forwards handle packing (segment-aware seq attention + "
                "history), generation requires padded batches."
            )
        if input_embeds is None:
            input_embeds = NestedAttentionPointProcessInputLayer(cfg, name="input_layer")(
                batch,
                dep_graph_el_generation_target=dep_graph_el_generation_target,
                partial_content_levels=partial_content_levels,
            )
            event_mask = batch.event_mask
        else:
            event_mask = None

        seq_attention_mask = event_mask
        hidden_states = input_embeds
        bsz, seq_len, dep_graph_len, hidden_size = hidden_states.shape

        # Static cache-mode flags (reference ``transformer.py:1043-1100``).
        update_seq_cache = False
        update_dep_graph_cache = False
        re_set_dep_graph_cache = False
        prepend_graph_with_history_embeddings = True
        update_last_graph_el_to_history_embedding = True
        if use_cache:
            if dep_graph_el_generation_target is None:
                if past is not None and past.dep_graph_past is not None:
                    raise ValueError(
                        "dep_graph_past should be None if gen target is None; got "
                        f"{past.dep_graph_past}"
                    )
                update_seq_cache = True
                update_dep_graph_cache = True
                re_set_dep_graph_cache = True
            elif dep_graph_el_generation_target == 0:
                update_seq_cache = True
                update_dep_graph_cache = True
                re_set_dep_graph_cache = True
                prepend_graph_with_history_embeddings = False
            elif dep_graph_el_generation_target > 0:
                update_dep_graph_cache = True
                if past is None or past.dep_graph_past is None:
                    raise ValueError(
                        "dep_graph_past should not be None if dep_graph_el_generation_target is "
                        f"{dep_graph_el_generation_target}."
                    )
                prepend_graph_with_history_embeddings = False
                update_last_graph_el_to_history_embedding = False
            else:
                raise ValueError(
                    "While use_cache=True, dep_graph generation target must be a non-negative int; "
                    f"got {dep_graph_el_generation_target}."
                )

        seq_past = past.seq_past if past is not None else None
        dep_graph_past = past.dep_graph_past if past is not None else None

        presents_seq = [] if use_cache else None
        presents_dep = [] if use_cache else None
        all_attentions = {"seq_attentions": [], "dep_graph_attentions": []} if output_attentions else None
        all_hidden = [] if output_hidden_states else None

        if getattr(cfg, "scan_layers", False):
            # The NA stack scans like the CI stack (one pattern-period body,
            # stacked params), with BOTH cache levels — the per-layer seq
            # caches and the per-event dep-graph caches — threaded through
            # the scan as stacked inputs/outputs. The cache-mode flags are
            # uniform across layers (the state machine above), so the body
            # is identical for every scan step.
            if output_attentions:
                raise NotImplementedError(
                    "scan_layers=True does not support output_attentions; migrate "
                    "the checkpoint to the unrolled layout (unstack_layer_params) "
                    "for attention introspection."
                )
            p, n_groups = scan_period(cfg)
            xs = None
            if seq_past is not None or dep_graph_past is not None:
                xs = (
                    _group_layer_trees(list(seq_past), p, n_groups)
                    if seq_past is not None
                    else None,
                    _group_layer_trees(list(dep_graph_past), p, n_groups)
                    if dep_graph_past is not None
                    else None,
                )
            stack = _scan_stack_cls(
                _NAScanBody, cfg, self.use_gradient_checkpointing, n_groups
            )(
                cfg,
                period=p,
                update_seq_cache=update_seq_cache,
                update_dep_graph_cache=update_dep_graph_cache,
                prepend_graph_with_history_embeddings=prepend_graph_with_history_embeddings,
                update_last_graph_el_to_history_embedding=update_last_graph_el_to_history_embedding,
                output_hidden_states=output_hidden_states,
                name="h_scan",
            )
            hidden_states, (seq_ys, dep_ys, hidden_ys) = stack(
                hidden_states, xs, seq_attention_mask, event_mask, segment_ids
            )
            if update_seq_cache:
                presents_seq = _ungroup_layer_trees(seq_ys, p, n_groups)
            if update_dep_graph_cache:
                presents_dep = _ungroup_layer_trees(dep_ys, p, n_groups)
            if all_hidden is not None:
                all_hidden = _ungroup_layer_trees(hidden_ys, p, n_groups)
        else:
            all_contextualized = [] if return_contextualized else None
            for i in range(cfg.num_hidden_layers):
                if all_hidden is not None:
                    all_hidden.append(hidden_states)
                block = StructuredTransformerBlock(cfg, layer_id=i, name=f"h{i}")
                hidden_states, extra = block(
                    hidden_states,
                    seq_attention_mask=seq_attention_mask,
                    event_mask=event_mask,
                    segment_ids=segment_ids,
                    prepend_graph_with_history_embeddings=prepend_graph_with_history_embeddings,
                    update_last_graph_el_to_history_embedding=update_last_graph_el_to_history_embedding,
                    history_head=history_head[i] if history_head is not None else None,
                    return_contextualized=return_contextualized,
                    seq_module_kwargs=dict(
                        layer_past=seq_past[i] if seq_past is not None else None,
                        use_cache=update_seq_cache,
                        output_attentions=output_attentions,
                    ),
                    dep_graph_module_kwargs=dict(
                        layer_past=dep_graph_past[i] if dep_graph_past is not None else None,
                        use_cache=update_dep_graph_cache,
                        output_attentions=output_attentions,
                    ),
                )
                if all_contextualized is not None:
                    all_contextualized.append(extra.get("contextualized"))

                if update_seq_cache:
                    presents_seq.append(extra["seq_module"]["present_key_value"])
                if update_dep_graph_cache:
                    presents_dep.append(extra["dep_graph_module"]["present_key_value"])
                if output_attentions:
                    if extra["seq_module"] is not None:
                        all_attentions["seq_attentions"].append(extra["seq_module"].get("attn_weights"))
                    all_attentions["dep_graph_attentions"].append(
                        extra["dep_graph_module"].get("attn_weights")
                    )

        with scope("norm"):
            hidden_states = nn.LayerNorm(
                epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype, name="ln_f"
            )(hidden_states)

        if all_hidden is not None:
            all_hidden.append(hidden_states)

        presents = None
        if use_cache:
            if not update_seq_cache:
                presents_seq = list(seq_past) if seq_past is not None else None
            if re_set_dep_graph_cache:
                # Reset the dep-graph cache to a single entry: the key/value of
                # the last event's contextualized (whole-event) embedding,
                # which seeds the next event's dep-graph decode
                # (``transformer.py:1194-1221``).
                # Sized from static config, NOT the current input's
                # dep_graph_len: at target=0 the input is trimmed to one graph
                # element, but the reset buffer must still hold the history
                # slot plus every level decoded before the next reset
                # (targets 1..G-1 and the target=0 append).
                max_dep_len = len(cfg.measurements_per_dep_graph_level) + 1
                new_dep = []
                for kv in presents_dep:
                    # kv buffers: (B*seq_len, H, cached_len, hd); the last
                    # written position of the last event holds the
                    # contextualized embedding's kv.
                    n_heads = kv.key.shape[1]
                    hd = kv.key.shape[3]
                    last_pos = kv.length - 1

                    def last_el(x):
                        x_last = jax.lax.dynamic_index_in_dim(x, last_pos, axis=2, keepdims=False)
                        # (B*seq_len, H, hd) -> last event -> (B, H, hd).
                        # ``last_event_index`` overrides the static "last
                        # position" pick for bucket-padded prompts (serving
                        # engine prefill): the seed must be the last REAL
                        # event per row, not the padded tail position.
                        x_last = x_last.reshape(bsz, seq_len, n_heads, hd)
                        if last_event_index is None:
                            x_last = x_last[:, -1]
                        else:
                            from ..ops.tensor_ops import take_event

                            x_last = take_event(x_last, last_event_index)
                        buf = jnp.zeros((bsz, n_heads, max_dep_len, hd), dtype=x.dtype)
                        return buf.at[:, :, 0, :].set(x_last)

                    mask = jnp.zeros((bsz, max_dep_len), dtype=bool).at[:, 0].set(True)
                    new_dep.append(
                        KVCache(
                            key=last_el(kv.key),
                            value=last_el(kv.value),
                            mask=mask,
                            length=jnp.asarray(1, jnp.int32),
                        )
                    )
                presents_dep = new_dep
            presents = NAPast(
                seq_past=tuple(presents_seq) if presents_seq is not None else None,
                dep_graph_past=tuple(presents_dep) if presents_dep is not None else None,
            )

        return TransformerOutputWithPast(
            last_hidden_state=hidden_states,
            past_key_values=presents,
            hidden_states=tuple(all_hidden) if all_hidden is not None else None,
            attentions=all_attentions if all_attentions is not None else None,
            contextualized=(
                tuple(all_contextualized) if return_contextualized else None
            ),
        )
