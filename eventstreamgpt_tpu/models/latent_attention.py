"""Latent attention (MLA): queries, keys and values through low-rank latents.

The mixer of the kinds block (`models/blocks.py`), as the DeepSeek-V2 lineage
writes it (GLM-4.7-Flash's ``glm4_moe_lite``), with no bias anywhere::

    c_q = RMSNorm(x W_qa)                     q = c_q W_qb     heads of nope + rope
    [c_kv ; k_r] = x W_kva                    [k_nope ; v] = RMSNorm(c_kv) W_kvb
    q_r, k_r rotated (RoPE over the rope dims, rotate-half pairing), k_r shared by the heads
    softmax([q_nope ; q_r] [k_nope ; k_r]^T / sqrt(nope + rope)) v, causal, inside a segment
    out = concat(heads) W_o

A position is the event's index inside its subject: it restarts at every
segment of a packed row. The core is the ``pallas_flash`` kernel the classic
global layers use wherever some group of heads is whole 128-lane tiles at
either width (GLM-4.7-Flash's 192 + 64 and 256; Xing4.0's 128 + 64 beside 128, two
heads of 192 being three tiles), and the einsum elsewhere. ``rope_scaling``
of type ``yarn`` blends RoPE's frequencies and scales the logits
(`ops/rope.py`, `softmax_scale`). There is no decode cache
yet: a latent paged cache and the absorbed decode path are ROADMAP R2/R3.

**How q, k and v are assembled.** Two formulations of the lines above, one
parameter tree (``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``,
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``):

* *In the flash op's layout* (`ops/pallas_rope_join.py`), where a kernel is
  taken (`ops.impl_select.resolve_impl`: a TPU backend, or
  ``$ESGPT_PALLAS_IMPL=pallas_interpret`` anywhere) and the head widths allow
  it (`ops.pallas_rope_join.rope_join_applies`: some group of heads is whole
  128-lane tiles at either width, as the flash op asks; an even ``rope``; every
  head's rope span, lanes ``(h (nope + rope) + nope) mod 128`` onward, inside
  one tile, at any offset of it, and that tile no other head's; the value's
  width is free). Both published
  splits are such: GLM-4.7-Flash's 192 + 64 beside 256 (the span is the second
  half of every head's second tile) and Xing4.0's 128 + 64 beside 128 (the
  first half of a tile for the even heads, the second half of the next for
  the odd). ``kv_b_proj``'s kernel is sliced per head into a value kernel
  and a key kernel padded with ``rope`` zero columns a head
  (`split_kv_kernel`: 4.6M and 2.1M elements at the published widths), so
  ``value`` leaves its product as ``[B, S, H * v]`` and the key's nope part
  as ``[B, S, H * d]`` row-major, as ``q_b_proj`` leaves the query; one
  in-place Pallas pass then rotates the query's rope lanes and writes the
  rotated shared key part into every head's. Nothing between the products and
  the flash kernels, forward or backward, is sliced, concatenated or re-laid
  by XLA (which otherwise lays all of it out events-minor: `PERF.md` section
  6, PR 31 and PR 35).
* *With XLA's slices and concatenations* (`rotate`) everywhere else: the CPU,
  head widths such as the tests' 8 + 4 or a rope span that crosses a tile
  boundary (96 + 64). Asked for ``pallas_flash`` where a kernel is taken and
  the widths refuse, the layer warns, as `_core` does.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import segment_starts
from ..ops.rope import rope_cos_sin, yarn_mscale
from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .transformer import ATTENTION_CHECKPOINT_NAME


class RMSNorm(nn.Module):
    """``w * x / sqrt(mean(x^2) + eps)``, computed in float32."""

    epsilon: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (scale * y).astype(self.dtype)


def bias_free_dense(cfg, features: int, name: str) -> nn.Dense:
    """The kinds block's one kind of product: no bias, normal with the
    configuration's ``init_std``, in its compute dtype. Called inside a
    module's ``@nn.compact`` method, where the layer becomes its child."""
    return nn.Dense(
        features, use_bias=False, kernel_init=nn.initializers.normal(stddev=cfg.init_std),
        dtype=cfg.compute_dtype, name=name,
    )


def segment_positions(segment_ids, batch_size: int, seq_len: int):
    """(B, S) int32: each event's index inside its segment (inside the row
    where rows are not packed)."""
    idx = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), (batch_size, seq_len))
    if segment_ids is None:
        return idx
    first = jax.lax.cummax(jnp.where(segment_starts(segment_ids), idx, 0), axis=1)
    return idx - first


def softmax_scale(cfg) -> float:
    """Latent attention's logit scale: ``(nope + rope)^-1/2``, times YaRN's
    ``m(mscale_all_dim)^2`` under a ``rope_scaling`` that names one."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    scaling = getattr(cfg, "rope_scaling", None)
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rotate(x, positions, theta: float, scaling: dict | None = None):
    """RoPE over the last axis of ``x`` (B, S, ..., d), rotate-half pairing:
    dimension ``i`` pairs with ``i + d/2``; angles `ops.rope.rope_cos_sin`'s.
    Float32 inside."""
    d = x.shape[-1]
    cos, sin = (
        t.reshape(t.shape[:2] + (1,) * (x.ndim - 3) + t.shape[-1:]) for t in rope_cos_sin(positions, d, theta, scaling)
    )  # (B, S, ..., d/2)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., : d // 2], x32[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def split_kv_kernel(kernel, heads: int, nope: int, rope: int):
    """``kv_b_proj``'s kernel ``[r, heads * (nope + v)]`` as a key kernel
    ``[r, heads * (nope + rope)]``, a head's nope columns followed by ``rope``
    zero columns (the lanes `ops.pallas_rope_join.rope_join` fills), and a
    value kernel ``[r, heads * v]``. Sliced where the weights are, so the
    products' outputs are whole heads and nothing slices the activations."""
    per_head = kernel.reshape(kernel.shape[0], heads, -1)
    key = jnp.pad(per_head[..., :nope], ((0, 0), (0, 0), (0, rope)))
    return _whole_gradient(key.reshape(kernel.shape[0], -1)), per_head[..., nope:].reshape(kernel.shape[0], -1)


@jax.custom_vjp
def _whole_gradient(w):
    """``w``; its cotangent behind an optimization barrier. The key kernel's
    gradient is then a plain ``[r, heads * d]`` product like the value
    kernel's: without it XLA folds the per-head slice into the product and
    transposes the product's big operand, ``dkey``, for it."""
    return w


_whole_gradient.defvjp(lambda w: (w, None), lambda _, g: (jax.lax.optimization_barrier(g),))


class _Kernel(nn.Module):
    """A bias-free ``nn.Dense``'s parameter, leaf for leaf, for a caller that
    slices it before the product."""

    shape: tuple[int, int]
    init_std: float

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(stddev=self.init_std), self.shape, jnp.float32)


class LatentAttention(nn.Module):
    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, segment_ids=None):
        cfg = self.config
        dt = cfg.compute_dtype
        H, dn, dr, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        B, S = x.shape[:2]

        dense = functools.partial(bias_free_dense, cfg)
        impl = self._assembly_impl()
        with scope("attn_latent"):
            c_q = RMSNorm(cfg.layer_norm_epsilon, dt, name="q_a_layernorm")(dense(cfg.q_lora_rank, "q_a_proj")(x))
            q = dense(H * (dn + dr), "q_b_proj")(c_q)
            kv_a = dense(cfg.kv_lora_rank + dr, "kv_a_proj_with_mqa")(x)
            c_kv = RMSNorm(cfg.layer_norm_epsilon, dt, name="kv_a_layernorm")(kv_a[..., : cfg.kv_lora_rank])
            positions = segment_positions(segment_ids, B, S)
            if impl == "xla":
                q = q.reshape(B, S, H, dn + dr)
                kv = dense(H * (dn + dv), "kv_b_proj")(c_kv).reshape(B, S, H, dn + dv)
                q_r = rotate(q[..., dn:], positions, cfg.rope_theta, cfg.rope_scaling)
                k_r = rotate(kv_a[..., cfg.kv_lora_rank :], positions, cfg.rope_theta, cfg.rope_scaling)
                query = jnp.concatenate([q[..., :dn], q_r], axis=-1)
                key = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, S, H, dr))], axis=-1
                )
                value = kv[..., dn:]
            else:
                # q, k and v in the flash op's layout, [B, S, H * d] row-major: the key/value and
                # nope/rope splits made on the weights, RoPE and the join in one pass in place.
                from ..ops.pallas_rope_join import rope_join
                from ..parallel.context import per_batch_shard

                kernel = _Kernel((cfg.kv_lora_rank, H * (dn + dv)), cfg.init_std, name="kv_b_proj")()
                w_key, w_value = split_kv_kernel(kernel.astype(dt), H, dn, dr)
                value = jnp.dot(c_kv, w_value)
                query, key = per_batch_shard(
                    lambda q, k, r, p: rope_join(
                        q, k, r, p, heads=H, rope=dr, theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                        interpret=impl == "pallas_interpret",
                    ),
                    q, jnp.dot(c_kv, w_key), kv_a[..., cfg.kv_lora_rank :], positions,
                )
                query, key = query.reshape(B, S, H, dn + dr), key.reshape(B, S, H, dn + dr)
                value = value.reshape(B, S, H, dv)

        with scope("attn_global"):
            out = self._core(query, key, value, attention_mask, segment_ids)
            out = checkpoint_name(out, ATTENTION_CHECKPOINT_NAME)
        with scope("attn_proj"):
            return dense(cfg.hidden_size, "o_proj")(out.reshape(B, S, H * dv))

    def _assembly_impl(self) -> str:
        """``"pallas"`` / ``"pallas_interpret"``: q, k and v are assembled in
        the flash op's layout (`ops/pallas_rope_join.py`); ``"xla"``: slices,
        `rotate` and concatenations. Chosen as every kernel of ``ops/`` is
        (`ops.impl_select.resolve_impl`: the kernel on a TPU), where the head
        widths allow it."""
        from ..ops.impl_select import resolve_impl
        from ..ops.pallas_rope_join import rope_join_applies

        cfg = self.config
        impl = resolve_impl(None, "latent attention's assembly")
        if impl == "xla" or rope_join_applies(
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_attention_heads
        ):
            return impl
        if cfg.attention_implementation == "pallas_flash":
            import warnings

            warnings.warn(
                "latent attention is assembling q, k and v with XLA's slices and concatenations: head widths "
                f"nope {cfg.qk_nope_head_dim}, rope {cfg.qk_rope_head_dim}, value {cfg.v_head_dim} on "
                f"{cfg.num_attention_heads} heads (the in-place pass needs a group of heads that is whole 128-lane "
                "tiles at either width, an even rope, and every head's rope lanes inside one tile of their own)",
                stacklevel=2,
            )
        return "xla"

    def _core(self, query, key, value, attention_mask, segment_ids):
        return causal_core(
            self.config, query, key, value, attention_mask, segment_ids, "latent attention", softmax_scale(self.config)
        )


def causal_core(cfg, query, key, value, attention_mask, segment_ids, who: str, scale: float | None = None):
    """Causal, segment-masked softmax attention of queries and keys (B, S, H,
    d) over values (B, S, H, dv), scaled by ``scale`` (``d ** -0.5`` where not
    given): the repo's flash op where the configuration asks for it and a
    kernel is taken (a TPU, or the interpreter where
    ``$ESGPT_PALLAS_IMPL=pallas_interpret``), the einsum elsewhere. The op
    takes a key width beside a value width where some number of heads side by
    side is whole 128-lane tiles at both (`ops.pallas_flash.lane_tile_groups`:
    256 / 256 and 128 / 128 a head, 192 / 128 two heads, as it is: on the chip
    the kernels at 192 are 4% faster than at keys padded to 256, PERF.md
    section 6, PR 34)."""
    from ..ops.impl_select import resolve_impl
    from ..ops.pallas_flash import lane_tile_groups

    B, S, H, d = query.shape
    scale = d**-0.5 if scale is None else scale
    want_kernel = cfg.attention_implementation == "pallas_flash"
    impl = resolve_impl(None, "flash attention") if want_kernel else "xla"
    if impl != "xla" and S % 128 == 0 and lane_tile_groups(H, d, value.shape[-1]):
        from ..ops.pallas_flash import flash_attention
        from ..parallel.context import per_batch_shard

        # Padding rides as its own segment, as in the classic layers; the
        # op reads (B, S, H, d) as the projections leave it.
        seg = segment_ids if segment_ids is not None else jnp.zeros((B, S), jnp.int32)
        if attention_mask is not None:
            seg = jnp.where(attention_mask, seg.astype(jnp.int32), -1)
        return per_batch_shard(
            lambda q, k, v, s: flash_attention(q, k, v, s, sm_scale=scale, interpret=impl == "pallas_interpret"),
            query, key, value, seg,
        ).astype(value.dtype)
    if want_kernel:
        import warnings

        warnings.warn(
            f"attention_implementation='pallas_flash' is taking the einsum path in {who}: "
            f"backend={jax.default_backend()!r}, S={S}, head widths {d} and {value.shape[-1]} "
            "(the flash kernel needs a TPU, S % 128 == 0 and a group of heads that is whole 128-lane tiles at either width)",
            stacklevel=3,
        )
    logits = jnp.einsum("bqhd,bkhd->bhqk", query, key, preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    mask = (pos[None, :] <= pos[:, None])[None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(value.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, value)
