"""A plain reference of the kinds block for the tests: latent attention, the
gated short convolution, grouped-query attention with QK-norm and RoPE, the
gated and the routed feed-forward, the stack. Float32 ``jax.numpy`` over the
program's parameter tree, every expert of the router's width allowed (the
benchmark's references, `benchmark/reference/glm47flash_ep8.py` and
`benchmark/reference/lfm2_8b_a1b_ep4.py`, are separate copies that compute one
chip's share only). The convolution is a sum over its taps, each masked by the
event's index inside its segment."""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, positions, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2) / d)
    ang = positions[..., None] * inv
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def positions(segment_ids, B, L):
    idx = jnp.broadcast_to(jnp.arange(L), (B, L))
    if segment_ids is None:
        return idx
    start = jnp.concatenate([jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], 1)
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=1)


def latent_attention(x, p, cfg, mask=None, segment_ids=None):
    B, L, _ = x.shape
    H, dn, dr, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.layer_norm_epsilon
    c_q = rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(B, L, H, dn + dr)
    kv_a = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c_kv = rms_norm(kv_a[..., : cfg.kv_lora_rank], p["kv_a_layernorm"]["scale"], eps)
    kv = (c_kv @ p["kv_b_proj"]["kernel"]).reshape(B, L, H, dn + dv)
    pos = positions(segment_ids, B, L)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)], -1)
    k_r = rope(kv_a[..., cfg.kv_lora_rank :], pos, cfg.rope_theta)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (B, L, H, dr))], -1)
    ok = (jnp.arange(L)[None, :] <= jnp.arange(L)[:, None])[None]
    if segment_ids is not None:
        ok = ok & (segment_ids[:, :, None] == segment_ids[:, None, :])
    if mask is not None:
        ok = ok & mask[:, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dn + dr)
    probs = jax.nn.softmax(jnp.where(ok[:, None], logits, -1e30), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(B, L, H * dv)
    return out @ p["o_proj"]["kernel"]


def short_conv(x, p, cfg, mask=None, segment_ids=None):
    """``((C * sum_j w_j v_{t-j}) W_out`` of ``v = B * x~``, ``[B | C | x~] = x W_in``,
    a tap before the segment's first event and a padding slot's ``v`` zero."""
    B, L, h = x.shape
    b, c, xt = jnp.split(x @ p["in_proj"]["kernel"], 3, axis=-1)
    v = b * xt
    if mask is not None:
        v = jnp.where(mask[..., None], v, 0.0)
    seg = jnp.zeros((B, L), jnp.int32) if segment_ids is None else segment_ids
    if mask is not None:
        seg = jnp.where(mask, seg, -1)
    since = positions(seg, B, L)
    taps = p["conv_kernel"]
    conv = jnp.zeros_like(v)
    for j in range(taps.shape[0]):
        earlier = jnp.concatenate([jnp.zeros_like(v[:, :j]), v[:, : L - j]], axis=1)
        conv = conv + taps[-1 - j] * jnp.where((since >= j)[..., None], earlier, 0.0)
    return (c * conv) @ p["out_proj"]["kernel"]


def grouped_query_attention(x, p, cfg, mask=None, segment_ids=None):
    """Query heads over fewer key/value heads; per-head RMSNorm of q and k and
    RoPE on the whole head where the configuration asks for them."""
    B, L, _ = x.shape
    H, d = cfg.num_attention_heads, cfg.head_dim
    kv = cfg.num_key_value_heads or H
    q = (x @ p["q_proj"]["kernel"]).reshape(B, L, H, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(B, L, kv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, L, kv, d)
    if cfg.mha_qk_norm:
        q = rms_norm(q, p["q_layernorm"]["scale"], cfg.layer_norm_epsilon)
        k = rms_norm(k, p["k_layernorm"]["scale"], cfg.layer_norm_epsilon)
    if cfg.mha_rope:
        pos = positions(segment_ids, B, L)
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    k, v = jnp.repeat(k, H // kv, axis=2), jnp.repeat(v, H // kv, axis=2)
    ok = (jnp.arange(L)[None, :] <= jnp.arange(L)[:, None])[None]
    if segment_ids is not None:
        ok = ok & (segment_ids[:, :, None] == segment_ids[:, None, :])
    if mask is not None:
        ok = ok & mask[:, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
    probs = jax.nn.softmax(jnp.where(ok[:, None], logits, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, L, H * d) @ p["o_proj"]["kernel"]


MIXERS = {"latent": ("self_attn", latent_attention), "conv": ("conv", short_conv), "mha": ("self_attn", grouped_query_attention)}


def swiglu(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def routed_feed_forward(x, p, cfg):
    """``(routed, shared)``: the weighted outputs of the held experts a row
    chose (the configuration's range of the router's indices), and the shared
    expert's."""
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jax.lax.top_k(scores + p["e_score_correction_bias"], cfg.num_experts_per_tok)[1]
    top = jnp.take_along_axis(scores, chosen, -1)
    if cfg.norm_topk_prob:
        top = top / (top.sum(-1, keepdims=True) + cfg.moe_topk_norm_eps)
    top = cfg.routed_scaling_factor * top
    first = cfg.moe_expert_offset
    out = jnp.zeros_like(x)
    for i in range(cfg.n_routed_experts):
        w = jnp.where(chosen == first + i, top, 0.0).sum(-1)
        expert = {n: {"kernel": p[f"experts_{n}"][i]} for n in ("gate_proj", "up_proj", "down_proj")}
        out = out + w[..., None] * swiglu(x, expert)
    return out, (swiglu(x, p["shared_experts"]) if cfg.n_shared_experts else jnp.zeros_like(x))


def block(x, p, cfg, layer_id, mask=None, segment_ids=None):
    eps = cfg.layer_norm_epsilon
    name, mixer = MIXERS[cfg.mixer_layers[layer_id]]
    x = x + mixer(rms_norm(x, p["input_layernorm"]["scale"], eps), p[name], cfg, mask, segment_ids)
    normed = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if cfg.ffn_layers[layer_id] == "routed":
        routed, shared = routed_feed_forward(normed, p["mlp"], cfg)
        return x + routed + shared
    return x + swiglu(normed, p["mlp"])
