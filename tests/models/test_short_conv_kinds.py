"""The kinds block's gated short convolution (`models/short_conv.py`) and what
LFM2's block adds around it: the convolution against a sum over its taps,
packed and padded, forward and gradient; grouped-query attention with QK-norm
and RoPE; a five-layer stack of LFM2's pattern; the four shares of a 32-wide,
top-4 router against the uncut layer; the dispatch's chunk with headroom
against the one-chunk walk; and what the configuration refuses. The reference
is `tests/models/layer_kinds_reference.py`, float32 on seeded random weights."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.models.blocks import GroupedQueryAttention, KindsBlock
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.moe import RoutedFeedForward, dispatch_chunk, held_experts_output
from eventstreamgpt_tpu.models.short_conv import ShortConvMixer

from .. import _programs as programs
from . import layer_kinds_reference as ref

CONFIGS = Path(__file__).resolve().parents[2] / "benchmark" / "configs"
LFM2 = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8, num_hidden_layers=5,
    intermediate_size=48, seq_attention_types=["global"],
    mixer_types=["conv", "mha", "conv", "conv", "conv"], ffn_types=["swiglu", "routed", "routed", "routed", "routed"],
    norm_type="rms_norm", conv_L_cache=3, mha_qk_norm=True, mha_rope=True, rope_theta=1e6,
    moe_intermediate_size=16, moe_router_width=32, n_routed_experts=32, moe_expert_offset=0, n_shared_experts=0,
    num_experts_per_tok=4, routed_scaling_factor=1.0, moe_topk_norm_eps=1e-6,
    attention_dropout=0.0, input_dropout=0.0, resid_dropout=0.0, init_std=0.3,
)


def lfm2_config(**kwargs):
    return StructuredTransformerConfig(**{**LFM2, **kwargs})


def packed(seed=0, padded_tail=3):
    """Two rows of 24 events: three segments in the first (5, 8 and 11 events: a
    tap reaches across each boundary), two in the second, which ends in padding."""
    seg = jnp.asarray([[0] * 5 + [1] * 8 + [2] * 11, [0] * 13 + [1] * 11], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(seed), seg.shape + (LFM2["hidden_size"],))
    mask = jnp.ones(seg.shape, bool).at[1, seg.shape[1] - padded_tail :].set(False)
    return x, mask, seg


def jittered(params, seed=1):
    """The same tree with every norm scale away from 1, so that each one's gradient is read."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.3 * jax.random.normal(next(keys), a.shape) if "scale" in jax.tree_util.keystr(path) else a,
        params,
    )


def assert_grads_agree(got, want, rtol=1e-4):
    flat, flat_want = jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)
    assert len(flat) == len(flat_want)
    for (path, g), w in zip(flat, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0 or "e_score_correction_bias" in jax.tree_util.keystr(path), path  # it chooses, and gets none
        np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol * scale, err_msg=str(path))


@pytest.mark.parametrize("segments", [True, False], ids=["packed", "one_segment_a_row"])
def test_short_conv_agrees_with_a_sum_over_its_taps(segments):
    cfg = lfm2_config()
    x, mask, seg = packed()
    seg = seg if segments else None
    module = ShortConvMixer(cfg)
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, seg)["params"]
    assert jax.tree_util.tree_map(jnp.shape, params) == {
        "in_proj": {"kernel": (32, 96)}, "conv_kernel": (3, 32), "out_proj": {"kernel": (32, 32)},
    }
    assert float(jnp.abs(params["conv_kernel"]).max()) <= 0.5
    got = programs.apply(module, {"params": params}, x, mask, seg)
    want = programs.call(lambda p: ref.short_conv(x, p, cfg, mask, seg), params)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    grads = programs.call(jax.grad(lambda p, x: jnp.sum(module.apply({"params": p}, x, mask, seg) * weigh), argnums=(0, 1)), params, x)
    wants = programs.call(jax.grad(lambda p, x: jnp.sum(ref.short_conv(x, p, cfg, mask, seg) * weigh), argnums=(0, 1)), params, x)
    assert_grads_agree(grads, wants)


def test_no_tap_crosses_a_segment():
    """Each segment of a packed row gives what it gives alone; the same row
    unpacked differs right after each boundary, where a tap would reach back."""
    cfg = lfm2_config()
    x, _, seg = packed()
    module = ShortConvMixer(cfg)
    params = programs.init(module, jax.random.PRNGKey(1), x, None, seg)
    together = programs.apply(module, params, x, None, seg)
    for row in range(2):
        ids = np.asarray(seg[row]).tolist()
        for s in sorted(set(ids)):
            lo, hi = ids.index(s), len(ids) - ids[::-1].index(s)
            alone = programs.apply(module, params, x[row : row + 1, lo:hi], None, None)
            np.testing.assert_allclose(together[row : row + 1, lo:hi], alone, rtol=2e-5, atol=2e-5)
    unpacked = programs.apply(module, params, x, None, None)
    for start in (5, 13):  # the first two events of a segment read taps of the one before when unpacked
        assert float(jnp.abs(unpacked[0, start : start + 2] - together[0, start : start + 2]).min()) > 1e-4
        np.testing.assert_allclose(unpacked[0, start + 2 : start + 4], together[0, start + 2 : start + 4], rtol=2e-5, atol=2e-5)


def test_padding_slots_are_zeroed():
    """A padding slot's gated input is zero: its output is zero, and what the
    slots hold moves nothing at a real event."""
    cfg = lfm2_config()
    x, mask, seg = packed(padded_tail=5)
    module = ShortConvMixer(cfg)
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, seg)
    run = jax.jit(lambda x: module.apply(params, x, mask, seg))
    got = run(x)
    assert float(jnp.abs(got[1, -5:]).max()) == 0.0
    noise = jnp.where(mask[..., None], x, 100.0 * jax.random.normal(jax.random.PRNGKey(9), x.shape))
    np.testing.assert_array_equal(np.asarray(run(noise))[np.asarray(mask)], np.asarray(got)[np.asarray(mask)])


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_grouped_query_attention_with_qk_norm_and_rope(impl, monkeypatch):
    """Eight query heads of 64 over two key/value heads, rows of two
    128-event chunks with three segments and a padded tail: the einsum and
    the interpreted flash op against the reference, forward and gradient;
    RoPE restarts at every segment."""
    cfg = lfm2_config(num_attention_heads=8, head_dim=64, attention_implementation="pallas_flash", init_std=0.1)
    seg = jnp.asarray(np.repeat([[0] * 100 + [1] * 60 + [2] * 96], 2, axis=0), jnp.int32)
    mask = jnp.ones(seg.shape, bool).at[1, 240:].set(False)
    x = jax.random.normal(jax.random.PRNGKey(0), seg.shape + (32,))
    module = GroupedQueryAttention(cfg)
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", impl)
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, seg)
    assert jax.tree_util.tree_map(jnp.shape, params["params"]) == {
        "q_proj": {"kernel": (32, 512)}, "k_proj": {"kernel": (32, 128)}, "v_proj": {"kernel": (32, 128)},
        "q_layernorm": {"scale": (64,)}, "k_layernorm": {"scale": (64,)}, "o_proj": {"kernel": (512, 32)},
    }
    params = {"params": jittered(params["params"])}
    real = np.asarray(mask)

    def run(p):
        if impl == "xla":
            with pytest.warns(UserWarning, match="taking the einsum path in grouped-query attention"):
                return module.apply(p, x, mask, seg)
        return module.apply(p, x, mask, seg)

    want = jax.jit(lambda p: ref.grouped_query_attention(x, p, cfg, mask, seg))(params["params"])
    out = jax.jit(run)(params)
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(run(p) ** 2 * mask[..., None])))(params)["params"]
    wants = jax.jit(jax.grad(lambda p: jnp.sum(ref.grouped_query_attention(x, p, cfg, mask, seg) ** 2 * mask[..., None])))(params["params"])
    assert_grads_agree(grads, wants, rtol=1e-3)
    # a segment's attention is what it gives alone: its positions restart at 0
    alone = programs.apply(module, params, x[:1, 100:160], None, None) if impl == "xla" else None
    if alone is not None:
        np.testing.assert_allclose(out[:1, 100:160], alone, rtol=2e-5, atol=2e-5)


def test_both_options_off_leave_the_attention_as_it_was():
    """`nemotron_h`'s attention: no norm leaves and no rotation, the same numbers."""
    cfg = lfm2_config(mha_qk_norm=False, mha_rope=False)
    x, mask, seg = packed()
    module = GroupedQueryAttention(cfg)
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, seg)
    assert set(params["params"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    with_both = GroupedQueryAttention(lfm2_config())
    assert set(jax.eval_shape(with_both.init, jax.random.PRNGKey(1), x, mask, seg)["params"]) == set(params["params"]) | {"q_layernorm", "k_layernorm"}
    real = np.asarray(mask)
    got = programs.apply(module, params, x, mask, seg)
    want = programs.call(lambda p: ref.grouped_query_attention(x, p, cfg, mask, seg), params["params"])
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("first", [0, 24])
def test_a_five_layer_lfm2_stack_agrees_with_the_plain_reference(first):
    """``conv + dense``, ``attention + routed``, three ``conv + routed``, eight
    experts held of 32: the stack's output, and the gradient of a loss on it in
    every leaf and the input."""
    cfg = lfm2_config(n_routed_experts=8, moe_expert_offset=first)
    x, mask, seg = packed()
    blocks = [KindsBlock(cfg, layer_id=i) for i in range(5)]
    inits = programs.call(
        lambda x: [b.init(jax.random.PRNGKey(10 + i), x, mask, None, False, False, False, seg) for i, b in enumerate(blocks)], x
    )
    params = [jittered(p["params"], seed=20 + i) for i, p in enumerate(inits)]
    assert [sorted(p) for p in params] == [
        ["conv", "input_layernorm", "mlp", "post_attention_layernorm"],
        ["input_layernorm", "mlp", "post_attention_layernorm", "self_attn"],
    ] + [["conv", "input_layernorm", "mlp", "post_attention_layernorm"]] * 3
    real = mask[..., None]

    def stack(ps, x):
        for b, p in zip(blocks, ps):
            x = jnp.where(real, b.apply({"params": p}, x, mask, None, False, False, False, seg)[0], 0.0)
        return x

    def ref_stack(ps, x):
        for i, p in enumerate(ps):
            x = jnp.where(real, ref.block(x, p, cfg, i, mask, seg), 0.0)
        return x

    np.testing.assert_allclose(jax.jit(stack)(params, x), jax.jit(ref_stack)(params, x), rtol=1e-4, atol=1e-4)
    weigh = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    grad = lambda f: jax.jit(jax.grad(lambda ps, x: jnp.sum(f(ps, x) * weigh), argnums=(0, 1)))  # noqa: E731
    assert_grads_agree(grad(stack)(params, x), grad(ref_stack)(params, x), 1e-3)


def test_the_four_shares_of_a_32_wide_top_4_router_add_up_to_the_uncut_layer():
    """Four chips of eight experts each: the routed parts of the shares (no
    shared expert; the weights normalised over all four chosen plus 1e-6 in
    every share alike) are the layer that holds all 32."""
    whole = lfm2_config(n_routed_experts=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    module = RoutedFeedForward(whole)
    params = programs.init(module, jax.random.PRNGKey(4), x)
    uncut = programs.apply(module, params, x)
    p = params["params"]

    def shares(p):
        total = jnp.zeros_like(uncut)
        for first in range(0, 32, 8):
            share = lfm2_config(n_routed_experts=8, moe_expert_offset=first)
            held = {k: (v[first : first + 8] if k.startswith("experts_") else v) for k, v in p.items()}
            total = total + RoutedFeedForward(share).apply({"params": held}, x)
        return total

    np.testing.assert_allclose(programs.call(shares, p), uncut, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(uncut, programs.call(lambda p: ref.routed_feed_forward(x, p, whole)[0], p), rtol=2e-5, atol=2e-5)


def test_the_normalising_epsilon_is_lfm2s_alone():
    """With ``moe_topk_norm_eps`` the chosen weights sum to ``s / (s + eps)``; at its default of 0, to 1."""
    from eventstreamgpt_tpu.models.moe import route

    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (64, 32)) - 6.0)  # small scores: eps shows
    bias = jnp.zeros(32)
    _, w0 = route(scores, bias, 4, 1.0, True)
    _, w1 = route(scores, bias, 4, 1.0, True, 1e-6)
    total = jnp.sort(scores, axis=-1)[:, -4:].sum(-1)
    np.testing.assert_allclose(w0.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), total / (total + 1e-6), rtol=1e-6)
    assert float(jnp.max(w0.sum(-1) - w1.sum(-1))) > 1e-6


# ------------------------------------------------------------------ the dispatch
def _dispatch_case(n_rows, held_share, seed):
    """Rows whose four choices of 32 send ``held_share`` of the even load's pairs to experts 0-7."""
    rng = np.random.default_rng(seed)
    top_k, width, held = 4, 32, 8
    want = int(round(held_share * n_rows * top_k * held / width))
    chosen = np.stack([rng.choice(np.arange(held, width), top_k, replace=False) for _ in range(n_rows)])
    slots = rng.choice(n_rows * top_k, size=want, replace=False)
    rows, cols = np.divmod(slots, top_k)
    for r in np.unique(rows):  # distinct held experts within a row
        picked = cols[rows == r]
        chosen[r, picked] = rng.choice(held, len(picked), replace=False)
    assert int((chosen < held).sum()) == want
    return jnp.asarray(chosen), want


@pytest.mark.parametrize("held_share", [0.8, 1.2, 1.6], ids=["under", "over", "past_the_headroom"])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_chunk_with_headroom_gives_the_one_chunk_walks_output(held_share, impl):
    """At 1,024 rows, top-4 of 32 with 8 held, the even load is 1,024 pairs,
    one chunk of rows exactly. The walk's chunk is 1,280 rounded up to the
    row tile, 1,536 pairs: one chunk up to 1.5 times the even load, where the
    chunk of rows walks one (under) or two (over); past it both walk two.
    Outputs, gradients and counters agree, and with the plain sum."""
    n_rows, h, inner = 1024, 16, 8
    assert dispatch_chunk(n_rows, 4, 8, 32) == 1536 and dispatch_chunk(128, 4, 8, 32) == 512  # at most the buffer
    rows = jax.random.normal(jax.random.PRNGKey(5), (n_rows, h))
    stacks = [jax.random.normal(jax.random.PRNGKey(6 + i), s) * 0.3 for i, s in enumerate([(8, h, inner), (8, h, inner), (8, inner, h)])]
    chosen, pairs = _dispatch_case(n_rows, held_share, seed=int(held_share * 10))
    weights = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9), chosen.shape))
    walk = {
        width: jax.jit(lambda r, w: held_experts_output(r, chosen, w, *stacks, offset=0, impl=impl, router_width=width))
        for width in (None, 32)
    }
    one_chunk_walk, counters = walk[None](rows, weights)
    with_headroom, counters_too = walk[32](rows, weights)
    np.testing.assert_allclose(with_headroom, one_chunk_walk, rtol=2e-5, atol=2e-5)
    assert counters.tolist() == counters_too.tolist() and counters.tolist()[0][0] == pairs
    want = jax.jit(lambda rows, weights: sum(
        jnp.where((chosen == e)[..., None], weights[..., None], 0.0).sum(1)
        * ((jax.nn.silu(rows @ stacks[0][e]) * (rows @ stacks[1][e])) @ stacks[2][e])
        for e in range(8)
    ))(rows, weights)
    np.testing.assert_allclose(with_headroom, want, rtol=2e-5, atol=2e-5)
    grads = [jax.jit(jax.grad(lambda r, w: jnp.sum(f(r, w)[0] ** 2), argnums=(0, 1)))(rows, weights) for f in walk.values()]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def test_the_chunk_at_the_cells_shapes():
    """LFM2's cell (16,384 rows, top-4, 8 held of 32: the even load is one
    chunk of rows exactly) walks chunks of 20,480 pairs; the three other
    routed configurations, at their cells' rows, keep one chunk of rows."""
    cells = {"glm47flash_ep8": 16384, "nemotron_twotower_ep16": 16384, "xing40_a4b_ep8": 8192, "lfm2_8b_a1b_ep4": 16384}
    got = {}
    for name, n_rows in cells.items():
        c = json.loads((CONFIGS / f"{name}.json").read_text())["config"]
        got[name] = dispatch_chunk(n_rows, c["num_experts_per_tok"], c["n_routed_experts"], c["moe_router_width"])
    assert got == {"glm47flash_ep8": 16384, "nemotron_twotower_ep16": 16384, "xing40_a4b_ep8": 8192, "lfm2_8b_a1b_ep4": 20480}


# -------------------------------------------------------- configuration, decode
def test_what_the_configuration_refuses_and_the_missing_decode_state():
    from eventstreamgpt_tpu.models.transformer import NO_DECODE_STATE

    with pytest.raises(ValueError, match="conv_bias true is not built"):
        lfm2_config(conv_bias=True)
    with pytest.raises(ValueError, match="mixer 'conv' needs conv_L_cache"):
        lfm2_config(conv_L_cache=0)
    with pytest.raises(ValueError, match="mha_qk_norm and mha_rope"):
        StructuredTransformerConfig(hidden_size=16, head_dim=4, mha_rope=True)
    cfg = lfm2_config()
    assert cfg.mixer_layers == ["conv", "mha", "conv", "conv", "conv"]
    assert StructuredTransformerConfig.from_dict(cfg.to_dict()) == cfg
    assert "gated short convolution" in NO_DECODE_STATE and "last L - 1" in NO_DECODE_STATE
    x, mask, seg = packed()
    block = KindsBlock(cfg, layer_id=0)
    params = programs.init(block, jax.random.PRNGKey(0), x, mask, None, False, False, False, seg)
    with pytest.raises(NotImplementedError, match="gated short convolution"):
        programs.apply(block, params, x, mask, None, True, False, False, seg)
