"""The kinds block (`models/blocks.py`): latent attention and the routed
feed-forward against a plain reference in float32 on seeded random weights,
the share of one chip against the uncut layer, no dropped row, RoPE's restart
at a packed segment, the routing counters, and what raises."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.models.blocks import KindsBlock
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.latent_attention import LatentAttention, split_kv_kernel
from eventstreamgpt_tpu.models.moe import RoutedFeedForward, held_experts_output

from .. import _programs as programs
from . import layer_kinds_reference as ref

KINDS = dict(
    hidden_size=32, num_attention_heads=4, num_hidden_layers=3, intermediate_size=48,
    seq_attention_types=["global"], mixer_types="latent", ffn_types=[[["swiglu"], 1], [["routed"], 46]],
    norm_type="rms_norm", activation_function="silu",
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12, rope_theta=1e6,
    moe_intermediate_size=24, moe_router_width=16, n_routed_experts=16, moe_expert_offset=0,
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=1.8,
    attention_dropout=0.0, input_dropout=0.0, resid_dropout=0.0, init_std=0.5,
)


def kinds_config(**kwargs):
    return StructuredTransformerConfig(**{**KINDS, **kwargs})


def inputs(B=2, L=12, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, L, KINDS["hidden_size"]))
    segment_ids = jnp.asarray(np.repeat([[0] * 5 + [1] * 4 + [2] * 3, [0] * 7 + [1] * 5], 1, axis=0)[:B])
    mask = jnp.ones((B, L), bool).at[1, L - 2 :].set(False)
    return x, mask, segment_ids


@pytest.mark.parametrize("packed", [False, True], ids=["padded", "packed"])
def test_latent_attention_agrees_with_the_plain_reference(packed):
    cfg = kinds_config()
    x, mask, segment_ids = inputs()
    segment_ids = segment_ids if packed else None
    module = LatentAttention(cfg)
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, segment_ids)
    got = programs.apply(module, params, x, mask, segment_ids)
    want = programs.call(lambda p: ref.latent_attention(x, p, cfg, mask, segment_ids), params["params"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# Head widths the in-place assembly takes (`ops/pallas_rope_join.py`), the two published splits: GLM-4.7-Flash's, a
# head of two lane tiles whose rope lanes end it, and Xing4.0's, two heads of three tiles whose rope lanes start at lane
# 0 of a tile (the even heads) and at lane 64 of the next (the odd), beside a narrower value, with and without YaRN;
# and a split no model publishes, whose spans lie at lanes 16-63 and 80-127 by head parity, with lanes between them.
ALIGNED = {
    "192 + 64 / 256": dict(num_attention_heads=2, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256),
    "128 + 64 / 128": dict(num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    "144 + 48 / 64": dict(num_attention_heads=2, qk_nope_head_dim=144, qk_rope_head_dim=48, v_head_dim=64),
    "128 + 64 / 128, four heads, yarn": dict(
        num_attention_heads=4, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    ),
}


def latent_layer(monkeypatch, impl, precision, packed, widths):
    """One latent-attention layer at lane-aligned widths under ``impl``: its
    parameters, output, the ``(query, key, value)`` its core was handed, and
    the gradients of ``x`` and of the parameters."""
    cfg = kinds_config(**ALIGNED[widths], init_std=0.2, precision=precision)
    x, mask, segment_ids = inputs()
    x, segment_ids = x.astype(cfg.compute_dtype), segment_ids if packed else None
    seen = []
    core = LatentAttention._core
    module = LatentAttention(cfg)
    with monkeypatch.context() as patch:
        patch.setenv("ESGPT_PALLAS_IMPL", impl)
        patch.setattr(LatentAttention, "_core", lambda self, *a: seen.append(a[:3]) or core(self, *a))
        params = module.init(jax.random.PRNGKey(1), x, mask, segment_ids)
        out, qkv = module.apply(params, x, mask, segment_ids), seen[-1]
        weigh = jax.random.normal(jax.random.PRNGKey(2), out.shape)
        loss = lambda p, x: jnp.sum(module.apply(p, x, mask, segment_ids).astype(jnp.float32) * weigh)  # noqa: E731
        grads = jax.grad(loss, argnums=(0, 1))(params, x)
    return params, out, qkv, grads


@pytest.mark.parametrize("packed", [False, True], ids=["padded", "packed"])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("widths", list(ALIGNED))
def test_assembled_latent_attention_agrees_with_the_present_formulation(monkeypatch, widths, precision, packed):
    """q, k and v assembled in the flash op's layout (the key/value split on
    the weights, RoPE and the join as one Pallas pass, interpreted here)
    against the slices, `rotate` and concatenations: the same parameter tree
    from the same key; ``query``, ``key`` and ``value`` bit for bit; the
    output and every gradient (the shared ``k_r``'s through
    ``kv_a_proj_with_mqa`` among them) within this file's tolerances in
    float32, within two ulps in bfloat16."""
    p_new, out_new, qkv_new, grads_new = latent_layer(monkeypatch, "pallas_interpret", precision, packed, widths)
    p_old, out_old, qkv_old, grads_old = latent_layer(monkeypatch, "xla", precision, packed, widths)
    assert jax.tree_util.tree_structure(p_new) == jax.tree_util.tree_structure(p_old)
    assert len(jax.tree_util.tree_leaves(p_new)) == 7
    for a, b in zip(jax.tree_util.tree_leaves(p_new), jax.tree_util.tree_leaves(p_old)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    ulp = float(jnp.finfo(qkv_new[0].dtype).eps)
    w = ALIGNED[widths]
    key_width = w["qk_nope_head_dim"] + w["qk_rope_head_dim"]
    for name, a, b in zip(("query", "key", "value"), qkv_new, qkv_old):
        assert a.shape == b.shape == (2, 12, w["num_attention_heads"], w["v_head_dim"] if name == "value" else key_width), name
        assert a.dtype == b.dtype, name
        if precision == "bf16":  # one rounding of the same float32 arithmetic: the forward, and so the routing, is the parent's
            np.testing.assert_array_equal(f32(a), f32(b), err_msg=name)
        else:  # the CPU contracts `a cos - b sin` as written and `a cos + b (-sin)` into different fused multiply-adds
            np.testing.assert_allclose(f32(a), f32(b), rtol=ulp, atol=1e-6, err_msg=name)
    # float32: this file's tolerances. bfloat16: the output to an ulp (it reads equal), a gradient to two
    # (the pass sums dkey's rope lanes over the heads in float32, the present formulation rounds the sum).
    out_tol, grad_tol = (2e-5, 1e-3) if precision == "fp32" else (ulp, 2 * ulp)
    np.testing.assert_allclose(f32(out_new), f32(out_old), rtol=out_tol, atol=out_tol)
    flat_new, flat_old = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (grads_new, grads_old))
    assert len(flat_new) == 8  # the seven leaves and x
    for (path, g), (_, w) in zip(flat_new, flat_old):
        assert g.shape == w.shape and g.dtype == w.dtype
        scale = float(np.abs(f32(w)).max())
        assert scale > 0, path
        np.testing.assert_allclose(f32(g), f32(w), rtol=grad_tol, atol=grad_tol * scale, err_msg=str(path))


@pytest.mark.parametrize(
    "nope, rope, value, heads, applies",
    [
        (192, 64, 256, 20, True),  # GLM-4.7-Flash: the span ends every head's second tile
        (128, 64, 128, 32, True),  # Xing4.0: lanes 0-63 of a tile, lanes 64-127 of the next
        (96, 32, 64, 20, True),  # one tile a head, a value of half a tile: two heads a group
        (8, 4, 12, 4, False),  # this file's widths: no group of heads is whole tiles
        (128, 64, 128, 3, False),  # three heads of 192 are not
        (193, 63, 256, 20, False),  # an odd rope has no halves
        (96, 64, 128, 20, False),  # four heads are five tiles, and the first head's span crosses lane 128
        (144, 48, 64, 2, True),  # spans at lanes 16-63 and 80-127, by head parity
        (16, 48, 64, 4, False),  # the same spans, but two heads' in one tile: a grid step rewrites one head's
    ],
)
def test_which_head_widths_the_in_place_assembly_takes(nope, rope, value, heads, applies):
    from eventstreamgpt_tpu.ops.pallas_rope_join import rope_join_applies

    assert rope_join_applies(nope, rope, value, heads) is applies


def test_the_split_kernels_gradient_lands_in_the_one_leaf():
    """`split_kv_kernel`: the value kernel is a head's value columns, the key
    kernel its nope columns with zeros where `rope_join` writes; every element
    of the leaf takes its gradient from exactly one place and what the padding
    columns are handed goes nowhere."""
    heads, nope, rope, v = 3, 5, 3, 8
    leaf = jax.random.normal(jax.random.PRNGKey(0), (4, heads * (nope + v)))
    (key, value), vjp = jax.vjp(lambda w: split_kv_kernel(w, heads, nope, rope), leaf)
    per_head = np.asarray(leaf).reshape(4, heads, nope + v)
    key = np.asarray(key).reshape(4, heads, nope + rope)
    np.testing.assert_array_equal(key[..., :nope], per_head[..., :nope])
    np.testing.assert_array_equal(key[..., nope:], 0.0)
    np.testing.assert_array_equal(np.asarray(value).reshape(4, heads, v), per_head[..., nope:])
    d_key = np.full((4, heads, nope + rope), 2.0, np.float32)
    d_key[..., nope:] = np.nan  # the padding's cotangent is dropped, not multiplied by zero
    (d_leaf,) = vjp((jnp.asarray(d_key.reshape(4, -1)), jnp.full((4, heads * v), 3.0)))
    assert d_leaf.shape == leaf.shape
    want = np.concatenate([np.full((4, heads, nope), 2.0), np.full((4, heads, v), 3.0)], axis=-1)
    np.testing.assert_array_equal(np.asarray(d_leaf).reshape(4, heads, nope + v), want)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_routed_feed_forward_agrees_with_the_plain_reference(impl, monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", impl)
    cfg = kinds_config(n_routed_experts=6, moe_expert_offset=3)
    x, mask, _ = inputs(B=2, L=64)
    module = RoutedFeedForward(cfg)
    params = programs.init(module, jax.random.PRNGKey(2), x)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (16,))  # a selection bias that chooses otherwise
    params = {"params": {**params["params"], "e_score_correction_bias": bias}}
    got = programs.apply(module, params, x)
    routed, shared = programs.call(lambda p: ref.routed_feed_forward(x, p, cfg), params["params"])
    np.testing.assert_allclose(got, routed + shared, rtol=2e-5, atol=2e-5)
    grads = programs.call(jax.grad(lambda p: jnp.sum(module.apply(p, x) ** 2)), params)["params"]
    want = programs.call(jax.grad(lambda p: jnp.sum(sum(ref.routed_feed_forward(x, p, cfg)) ** 2)), params["params"])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize(
    "router",
    [dict(moe_router_width=16), dict(moe_router_width=64, routed_scaling_factor=2.0)],
    ids=["glm47flash_ep8: 2 of 16", "xing40_a4b_ep8: 8 of 64, scale 2"],
)
def test_the_eight_shares_add_up_to_the_uncut_layer(router):
    """One chip's share is a range of the router's experts. The routed parts
    of all the shares, with the shared expert counted once, are the layer that
    holds every expert."""
    width = router["moe_router_width"]
    whole = kinds_config(n_routed_experts=width, **router)
    x, _, _ = inputs(B=2, L=32, seed=3)
    module = RoutedFeedForward(whole)
    params = programs.init(module, jax.random.PRNGKey(4), x)
    uncut = programs.apply(module, params, x)
    p = params["params"]

    def shares(p):
        shared = ref.swiglu(x, p["shared_experts"])
        total = shared
        for first in range(0, width, width // 8):
            share = kinds_config(n_routed_experts=width // 8, moe_expert_offset=first, **router)
            held = {
                **{k: v for k, v in p.items() if not k.startswith("experts_")},
                **{k: v[first : first + width // 8] for k, v in p.items() if k.startswith("experts_")},
            }
            total = total + RoutedFeedForward(share).apply({"params": held}, x) - shared
        return total

    np.testing.assert_allclose(programs.call(shares, p), uncut, rtol=2e-5, atol=2e-5)
    routed, ref_shared = programs.call(lambda p: ref.routed_feed_forward(x, p, whole), p)
    np.testing.assert_allclose(uncut, routed + ref_shared, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_no_row_is_dropped_when_every_row_chooses_the_same_experts(impl):
    """The worst load: every row's every choice is held here. All four chunks
    of the buffer run and every pair is computed."""
    n, k, h, inner, held = 128, 4, 16, 8, 4
    rows = jax.random.normal(jax.random.PRNGKey(5), (n, h))
    w = [jax.random.normal(jax.random.PRNGKey(6 + i), s) * 0.3 for i, s in enumerate([(held, h, inner)] * 2 + [(held, inner, h)])]
    chosen = jnp.broadcast_to(jnp.arange(k), (n, k))
    weights = jnp.full((n, k), 0.25)
    got, counters = held_experts_output(rows, chosen, weights, *w, offset=0, impl=impl)
    want = sum(0.25 * ((jax.nn.silu(rows @ w[0][e]) * (rows @ w[1][e])) @ w[2][e]) for e in range(k))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert counters.tolist() == [[n * k, n]]


def test_rope_restarts_at_a_packed_segment():
    """A packed row gives what its histories give when run apart."""
    cfg = kinds_config()
    x, _, segment_ids = inputs(B=1)
    block = KindsBlock(cfg, layer_id=1)
    params = programs.init(block, jax.random.PRNGKey(7), x, None, None, False, False, False, segment_ids)
    packed, _ = programs.apply(block, params, x, None, None, False, False, False, segment_ids)
    for lo, hi in ((0, 5), (5, 9), (9, 12)):
        apart, _ = programs.apply(block, params, x[:, lo:hi], None, None, False, False, False, None)
        np.testing.assert_allclose(packed[:, lo:hi], apart, rtol=2e-5, atol=2e-5)
    unpacked, _ = programs.apply(block, params, x, None, None, False, False, False, None)
    assert float(jnp.abs(unpacked[:, 5:] - packed[:, 5:]).max()) > 1e-3


@pytest.mark.parametrize("layer_id", [0, 1], ids=["swiglu", "routed"])
def test_block_agrees_with_the_plain_reference(layer_id):
    cfg = kinds_config(n_routed_experts=8, moe_expert_offset=8)
    x, mask, segment_ids = inputs()
    block = KindsBlock(cfg, layer_id=layer_id)
    params = programs.init(block, jax.random.PRNGKey(8), x, mask, None, False, False, False, segment_ids)
    got, _ = programs.apply(block, params, x, mask, None, False, False, False, segment_ids)
    want = programs.call(lambda p: ref.block(x, p, cfg, layer_id, mask, segment_ids), params["params"])
    real = np.asarray(mask)  # a row that holds no event is routed nowhere; the encoder zeroes it
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=3e-5, atol=3e-5)


def test_counters_equal_a_count_made_on_the_host():
    cfg = kinds_config(n_routed_experts=4, moe_expert_offset=2)
    x, mask, _ = inputs(B=2, L=32, seed=9)
    module = RoutedFeedForward(cfg)
    params = programs.init(module, jax.random.PRNGKey(10), x)
    assert set(params) == {"params"}  # `init` gives parameters alone
    _, sown = programs.apply(module, params, x, mask, mutable=["routing"])
    (counters,) = sown["routing"]["counters"]
    scores = jax.nn.sigmoid(x.reshape(-1, 32) @ params["params"]["router"])
    chosen = np.asarray(jax.lax.top_k(scores, 4)[1])[np.asarray(mask).reshape(-1)]
    loads = [(chosen == e).sum() for e in range(2, 6)]
    assert counters.tolist() == [sum(loads), max(loads)]


def test_the_selection_bias_chooses_and_does_not_weigh():
    """A selection bias that favours the held experts changes which experts a
    row is given and not the scores they are weighted by; it is zero at
    initialisation and gets no gradient."""
    cfg = kinds_config(n_routed_experts=4, moe_expert_offset=2, init_std=0.1)
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 64, 32))
    module = RoutedFeedForward(cfg)
    params = programs.init(module, jax.random.PRNGKey(13), x)
    assert float(jnp.abs(params["params"]["e_score_correction_bias"]).max()) == 0.0
    bias = jnp.zeros(16).at[2:6].set(10.0)  # every row's four choices are the four held experts
    biased = {"params": {**params["params"], "e_score_correction_bias": bias}}
    out, sown = programs.apply(module, biased, x, mutable=["routing"])
    assert sown["routing"]["counters"][0].tolist() == [4 * 256, 256]

    def by_score(p):
        rows = x.reshape(-1, 32)
        scores = jax.nn.sigmoid(rows @ p["router"])[:, 2:6]
        weights = cfg.routed_scaling_factor * scores / scores.sum(-1, keepdims=True)  # by score alone
        swiglu = lambda r, g, u, d: (jax.nn.silu(r @ g) * (r @ u)) @ d  # noqa: E731
        want = swiglu(rows, *(p["shared_experts"][n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")))
        for i in range(4):
            want = want + weights[:, i : i + 1] * swiglu(
                rows, p["experts_gate_proj"][i], p["experts_up_proj"][i], p["experts_down_proj"][i]
            )
        return want

    np.testing.assert_allclose(out.reshape(-1, 32), programs.call(by_score, params["params"]), rtol=1e-5, atol=1e-6)
    grads = programs.call(jax.grad(lambda q: jnp.sum(module.apply(q, x) ** 2)), biased)["params"]
    assert float(jnp.abs(grads["e_score_correction_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["router"]).max()) > 0.0


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="layer kinds"):
        kinds_config(norm_type="layer_norm")
    with pytest.raises(ValueError, match="layer kinds"):
        kinds_config(ffn_types="mlp")
    with pytest.raises(ValueError, match="latent attention needs"):
        kinds_config(q_lora_rank=None)
    with pytest.raises(ValueError, match="are not among the router's"):
        kinds_config(n_routed_experts=8, moe_expert_offset=9)
    with pytest.raises(ValueError, match="conditionally-independent"):
        kinds_config(
            structured_event_processing_mode="nested_attention", measurements_per_dep_graph_level=[[], ["a"]]
        )
    cfg = kinds_config()
    assert cfg.head_dim == 12 and cfg.uses_layer_kinds and cfg.ffn_layers == ["swiglu", "routed", "routed"]
    assert StructuredTransformerConfig.from_dict(cfg.to_dict()) == cfg
    assert not StructuredTransformerConfig(hidden_size=16, head_dim=4).uses_layer_kinds


# ------------------------------------------------------------------- YaRN, 192 / 128
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1}


def _yarn_by_the_letter(d, theta, s):
    """DeepSeek-V3's public modelling code, line for line, in float64."""
    import math

    def correction_dim(rotations):
        return (d * math.log(s["original_max_position_embeddings"] / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), d - 1)
    freq_extra = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    freq_inter = 1.0 / (s["factor"] * theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask, (low, high)


def test_yarns_frequencies_are_the_published_codes():
    from eventstreamgpt_tpu.ops.rope import rope_inv_freq, rope_table_scale, yarn_mscale

    want, (low, high) = _yarn_by_the_letter(64, 10000.0, YARN)
    assert (low, high) == (10, 23)  # of the 32 pairs: ten turn fast enough to stay, nine are interpolated whole
    got = np.asarray(rope_inv_freq(64, 10000.0, YARN), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = np.asarray(rope_inv_freq(64, 10000.0), np.float64)
    np.testing.assert_array_equal(got[:10], plain[:10])
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    # no stretch: the unscaled frequencies, the unscaled tables, the unscaled softmax
    flat = dict(YARN, factor=1)
    np.testing.assert_allclose(np.asarray(rope_inv_freq(64, 10000.0, flat)), plain, rtol=1e-7)
    assert rope_table_scale(flat) == 1.0 and rope_table_scale(None) == 1.0 and rope_table_scale(YARN) == 1.0
    assert rope_table_scale(dict(YARN, mscale_all_dim=0)) == yarn_mscale(64, 1) == 0.1 * np.log(64) + 1


def test_rope_scaling_null_computes_what_it_did_and_yarn_scales_the_softmax():
    from eventstreamgpt_tpu.models.latent_attention import rotate, softmax_scale
    from eventstreamgpt_tpu.ops.pallas_rope_join import rope_tables

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 64))
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    np.testing.assert_array_equal(rotate(x, positions, 1e4), rotate(x, positions, 1e4, None))
    assert np.abs(np.asarray(rotate(x, positions, 1e4, YARN) - rotate(x, positions, 1e4))).max() > 0.1
    # the in-place pass's tables turn by the same angles as `rotate`
    cos, sin = rope_tables(positions, 64, 1e4, YARN)
    turned = rotate(jnp.ones((2, 12, 64)), positions, 1e4, YARN)
    np.testing.assert_allclose(cos[..., 64:96] - sin[..., 96:], turned[..., :32], rtol=1e-6, atol=1e-6)
    assert softmax_scale(kinds_config()) == 12**-0.5
    cfg = kinds_config(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_scaling=YARN)
    assert softmax_scale(cfg) == pytest.approx(192**-0.5 * (0.1 * np.log(64) + 1) ** 2, rel=1e-12)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_latent_attention_at_192_and_128_runs_the_flash_op_and_agrees_with_the_einsum(monkeypatch, precision):
    """Xing4.0's widths, nope 128 + rope 64 beside a value of 128, on packed
    rows of 128 events with YaRN: under ``pallas_flash`` the core is the flash
    op (interpreted here; two heads of 192 are three lane tiles) and under ``einsum``
    the plain softmax; the same parameters give the same output and gradients."""
    import warnings

    from eventstreamgpt_tpu.ops import pallas_flash

    widths = dict(num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, init_std=0.2,
                  rope_scaling=YARN, precision=precision)
    rng = np.random.default_rng(0)
    B, S = 2, 128
    x = jnp.asarray(rng.normal(size=(B, S, KINDS["hidden_size"])), jnp.float32)
    segment_ids = jnp.asarray(np.sort(rng.integers(0, 4, (B, S)), axis=1), jnp.int32)
    mask = jnp.ones((B, S), bool).at[1, S - 9 :].set(False)
    weigh = jnp.asarray(rng.normal(size=(B, S, KINDS["hidden_size"])), jnp.float32) * mask[..., None]  # what a padded query reads out differs
    seen = []
    real = pallas_flash.flash_attention
    monkeypatch.setattr(pallas_flash, "flash_attention", lambda q, k, v, *a, **kw: seen.append((q.shape, v.shape)) or real(q, k, v, *a, **kw))

    def run(implementation, impl):
        cfg = kinds_config(**widths, attention_implementation=implementation)
        module = LatentAttention(cfg)
        xc = x.astype(cfg.compute_dtype)
        with monkeypatch.context() as patch:
            patch.setenv("ESGPT_PALLAS_IMPL", impl)
            params = programs.init(module, jax.random.PRNGKey(1), xc, mask, segment_ids)
            loss = lambda p, x_: jnp.sum(module.apply(p, x_, mask, segment_ids).astype(jnp.float32) * weigh)  # noqa: E731
            return programs.apply(module, params, xc, mask, segment_ids), programs.call(jax.grad(loss, argnums=(0, 1)), params, xc)

    with warnings.catch_warnings():  # the widths engage the in-place assembly: nothing falls back, nothing warns
        warnings.simplefilter("error")
        out_flash, grads_flash = run("pallas_flash", "pallas_interpret")
    assert seen and set(seen) == {((B, S, 2, 192), (B, S, 2, 128))}
    seen.clear()
    out_plain, grads_plain = run("einsum", "xla")
    assert not seen
    tol = 2e-5 if precision == "fp32" else 3e-2
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    real_rows = np.asarray(mask)
    np.testing.assert_allclose(f32(out_flash)[real_rows], f32(out_plain)[real_rows], rtol=tol, atol=tol * float(np.abs(f32(out_plain)).max()))
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads_flash)[0], jax.tree_util.tree_leaves(grads_plain)):
        scale = float(np.abs(f32(w)).max())
        np.testing.assert_allclose(f32(g), f32(w), rtol=tol, atol=tol * scale, err_msg=str(path))
