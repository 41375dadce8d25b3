"""The kinds block's single-part layers (`models/blocks.py`): the Mamba-2 mixer
against the recurrence itself, packed and unpacked, forward and gradient; a
packed segment's outputs independent of what is packed before it; the ungated
relu^2 experts against the plain reference, the sixteen shares against the
uncut layer, no dropped row; grouped-query attention through the interpreted
flash op; the block of each kind; and what the configuration refuses. The
reference is the benchmark's (`benchmark/reference/nemotron_twotower_ep16.py`),
float32 on seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_twotower_ep16 as ref
from eventstreamgpt_tpu.models.blocks import GroupedQueryAttention, KindsBlock
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.moe import RoutedFeedForward, held_experts_output
from eventstreamgpt_tpu.models.state_space import Mamba2Mixer, _gate_norm, causal_conv, segment_ordinal
from eventstreamgpt_tpu.ops.ssd_scan import ssd_scan

from .. import _programs as programs

PATTERN = "MEMEM*EME"
KINDS = {"M": ("ssm", "none"), "E": ("none", "routed"), "*": ("mha", "none")}
HYBRID = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_hidden_layers=9,
    intermediate_size=48, seq_attention_types=["global"],
    mixer_types=[KINDS[c][0] for c in PATTERN], ffn_types=[KINDS[c][1] for c in PATTERN],
    norm_type="rms_norm", activation_function="silu",
    mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=16, mamba_conv_kernel=4, mamba_chunk_size=8,
    moe_expert_form="relu2", moe_intermediate_size=24, moe_shared_expert_intermediate_size=40, moe_router_width=16,
    n_routed_experts=16, moe_expert_offset=0, n_shared_experts=1, num_experts_per_tok=6, routed_scaling_factor=2.5,
    attention_dropout=0.0, input_dropout=0.0, resid_dropout=0.0, init_std=0.5,
)


def hybrid_config(**kwargs):
    return StructuredTransformerConfig(**{**HYBRID, **kwargs})


def sizes(cfg) -> dict:
    """The reference's sizes of a configuration object."""
    return {
        "hidden_size": cfg.hidden_size, "pattern": PATTERN[: cfg.num_hidden_layers], "published_layers": 52,
        "num_attention_heads": cfg.num_attention_heads, "num_key_value_heads": cfg.num_key_value_heads,
        "head_dim": cfg.head_dim, "rms_norm_eps": cfg.layer_norm_epsilon, "init_std": cfg.init_std,
        **{k: getattr(cfg, k) for k in (
            "mamba_num_heads", "mamba_head_dim", "mamba_n_groups", "ssm_state_size", "mamba_conv_kernel",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size", "moe_router_width", "n_routed_experts",
            "moe_expert_offset", "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        )},
    }


# Rows of 24 events at a chunk of 8: a segment start inside a chunk (5, 13), on a chunk's edge (8, 16), a
# segment that spans three chunks, single-event segments, and a padded tail.
LAYOUTS = {
    "inside": [[0] * 5 + [1] * 8 + [2] * 11, [0] * 13 + [1] * 11],
    "edge": [[0] * 8 + [1] * 8 + [2] * 8, [0] * 16 + [1] * 8],
    "mixed": [[0] * 1 + [1] * 1 + [2] * 6 + [3] * 16, [0] * 3 + [1] * 20 + [2] * 1],
}


def inputs(layout="inside", seed=0, padded_tail=3):
    seg = jnp.asarray(LAYOUTS[layout], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(seed), seg.shape + (HYBRID["hidden_size"],))
    mask = jnp.ones(seg.shape, bool).at[1, seg.shape[1] - padded_tail :].set(False)
    return x, mask, seg


def reference_mixer(x, p, cfg, mask, seg):
    first, since = ref._segments({"event_mask": mask, "segment_ids": seg})
    return ref.mamba_mixer(x, p, sizes(cfg), mask, first, since, None)


def mamba_params(module, x, mask, seg, seed=1):
    """Seeded parameters with every leaf away from its initial constant, so that each one's gradient is read."""
    params = programs.init(module, jax.random.PRNGKey(seed), x, mask, seg)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))
    jitter = lambda a, s: a + s * jax.random.normal(next(keys), a.shape)  # noqa: E731
    return {
        **params, "conv_bias": jitter(params["conv_bias"], 0.3), "D": jitter(params["D"], 0.3),
        "norm_scale": jitter(params["norm_scale"], 0.3), "A_log": jitter(params["A_log"], 0.2),
        "dt_bias": params["dt_bias"] + 4.0,  # steps of about 4, so that a state decays inside a row
    }


@pytest.mark.parametrize("layout", [None, "inside", "edge", "mixed"])
def test_mamba_mixer_agrees_with_the_recurrence(layout):
    cfg = hybrid_config()
    x, mask, seg = inputs(layout or "inside")
    seg = seg if layout else None
    module = Mamba2Mixer(cfg)
    params = mamba_params(module, x, mask, seg)
    got = programs.apply(module, {"params": params}, x, mask, seg)
    want = programs.call(lambda x, p: reference_mixer(x, p, cfg, mask, seg), x, params)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)

    weigh = jax.random.normal(jax.random.PRNGKey(2), got.shape) * mask[..., None]
    grads = programs.call(jax.grad(lambda p, x: jnp.sum(module.apply({"params": p}, x, mask, seg) * weigh), argnums=(0, 1)), params, x)
    wants = programs.call(jax.grad(lambda p, x: jnp.sum(reference_mixer(x, p, cfg, mask, seg) * weigh), argnums=(0, 1)), params, x)
    flat, flat_want = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (grads, wants))
    assert len(flat) == 9  # the eight leaves and x
    for (path, g), (_, w) in zip(flat, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4 * scale, err_msg=str(path))


@pytest.mark.parametrize("layout", ["inside", "edge", "mixed"])
def test_a_packed_segment_gives_what_it_gives_alone(layout):
    """Scan and convolution both: a segment's outputs do not depend on what is
    packed before it, whether it starts inside a chunk or on a chunk's edge."""
    cfg = hybrid_config()
    x, _, seg = inputs(layout)
    module = Mamba2Mixer(cfg)
    params = {"params": mamba_params(module, x, None, seg)}
    packed = programs.apply(module, params, x, None, seg)
    for row, ids in enumerate(LAYOUTS[layout]):
        for s in sorted(set(ids)):
            lo, hi = ids.index(s), len(ids) - ids[::-1].index(s)
            alone = programs.apply(module, params, x[row : row + 1, lo:hi], None, None)
            np.testing.assert_allclose(packed[row : row + 1, lo:hi], alone, rtol=2e-5, atol=2e-5)
    unpacked = programs.apply(module, params, x, None, None)
    last = LAYOUTS[layout][0].index(max(LAYOUTS[layout][0]))
    assert float(jnp.abs(unpacked[0, last:] - packed[0, last:]).max()) > 1e-3


def test_the_convolution_reads_zero_before_its_segments_first_event():
    x = jnp.arange(1.0, 13.0).reshape(1, 12, 1)
    kernel, bias = jnp.asarray([[1000.0], [100.0], [10.0], [1.0]]), jnp.asarray([0.5])
    ordinal = segment_ordinal(jnp.asarray([[0] * 5 + [1] * 2 + [2] * 5]), None, 1, 12)
    got = np.asarray(causal_conv(x, kernel, bias, ordinal))[0, :, 0]
    assert got[:5].tolist() == [1.5, 12.5, 123.5, 1234.5, 2345.5]
    assert got[5:7].tolist() == [6.5, 67.5]
    assert got[7:9].tolist() == [8.5, 89.5]


def test_the_scan_pads_a_row_to_whole_chunks_and_walks_rows_in_blocks(monkeypatch):
    """A row that is no whole number of chunks, and a budget for `L` that a single row fills: the same numbers."""
    from eventstreamgpt_tpu.ops import ssd_scan as module

    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, P, G, N = 4, 21, 4, 8, 2, 16
    x = jax.random.normal(keys[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, S, H)) + 1.0)
    a = -jnp.exp(jax.random.normal(keys[2], (H,)))
    bmat, cmat = jax.random.normal(keys[3], (B, S, G, N)), jax.random.normal(keys[4], (B, S, G, N))
    seg = jnp.asarray(np.repeat([[0] * 4 + [1] * 9 + [2] * 8], B, axis=0))
    first = jnp.concatenate([jnp.ones((B, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    want = programs.call(ref.recurrence, x, dt, a, bmat, cmat, first)
    ordinal = jnp.cumsum(first, axis=1, dtype=jnp.int32)
    scan = lambda chunk: programs.call(ssd_scan, x, dt, a, bmat, cmat, ordinal, chunk=chunk)  # noqa: E731
    np.testing.assert_allclose(scan(8), want, rtol=2e-5, atol=2e-5)
    monkeypatch.setattr(module, "_L_BYTES", 1)  # a block is one row
    np.testing.assert_allclose(scan(8), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(scan(128), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "rows, width",
    [(32, 128), (16, 256), (12, 128), (16, 24)],
    ids=["tiled", "tiled_two_lane_tiles", "rows_not_whole_tiles", "narrow_group"],
)
def test_the_grouped_norm_over_the_plane_as_it_is_tiled_is_the_grouped_norm(rows, width):
    """`_gate_norm` takes a group's mean over ``[row tiles, 8, groups, lane tiles,
    128]`` where a group is whole lane tiles and the rows whole sublane tiles
    (the first two cases), over ``[..., groups, width]`` elsewhere: the same
    numbers and gradients as the plain formula."""
    groups, eps = 3, 1e-5
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    y, z, w = (jax.random.normal(k, (2, rows // 2, groups * width)) for k in keys[:3])
    scale = 1.0 + 0.3 * jax.random.normal(keys[3], (groups * width,))

    def plain(y, z, scale):
        gated = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (groups, width))
        gated = gated / jnp.sqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
        return scale * gated.reshape(y.shape)

    np.testing.assert_allclose(
        programs.call(_gate_norm, y, z, scale, groups, eps), programs.call(plain, y, z, scale), rtol=2e-6, atol=2e-6
    )
    got = programs.call(jax.grad(lambda *v: jnp.sum(_gate_norm(*v, groups, eps) * w), argnums=(0, 1, 2)), y, z, scale)
    want = programs.call(jax.grad(lambda *v: jnp.sum(plain(*v) * w), argnums=(0, 1, 2)), y, z, scale)
    for g, v in zip(got, want):
        np.testing.assert_allclose(g, v, rtol=2e-5, atol=2e-5)


def routed_reference(x, p, cfg):
    return ref.routed_feed_forward(x, p, sizes(cfg), None)[0]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_relu2_routed_layer_agrees_with_the_plain_reference(impl, monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", impl)
    cfg = hybrid_config(n_routed_experts=6, moe_expert_offset=3)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
    module = RoutedFeedForward(cfg)
    params = programs.init(module, jax.random.PRNGKey(2), x)
    assert set(params["params"]) == {"router", "e_score_correction_bias", "experts_up_proj", "experts_down_proj", "shared_experts"}
    assert set(params["params"]["shared_experts"]) == {"up_proj", "down_proj"}
    assert params["params"]["shared_experts"]["up_proj"]["kernel"].shape == (32, 40)
    got = programs.apply(module, params, x)
    np.testing.assert_allclose(got, programs.call(lambda p: routed_reference(x, p, cfg), params["params"]), rtol=2e-5, atol=2e-5)
    grads = programs.call(jax.grad(lambda p: jnp.sum(module.apply(p, x) ** 2)), params)["params"]
    want = programs.call(jax.grad(lambda p: jnp.sum(routed_reference(x, p, cfg) ** 2)), params["params"])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3 * float(jnp.abs(w).max()), err_msg=str(path))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One chip's share is one expert of the router's sixteen here. The routed
    parts of all the shares, with the shared expert counted once, are the
    layer that holds every expert."""
    whole = hybrid_config(n_routed_experts=16)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    module = RoutedFeedForward(whole)
    params = programs.init(module, jax.random.PRNGKey(4), x)
    uncut = programs.apply(module, params, x)
    p = params["params"]

    def shares(p):
        shared = ref.relu2(x, p["shared_experts"], None)
        total = shared
        for first in range(16):
            share = hybrid_config(n_routed_experts=1, moe_expert_offset=first)
            held = {k: (v[first : first + 1] if k.startswith("experts_") else v) for k, v in p.items()}
            total = total + RoutedFeedForward(share).apply({"params": held}, x) - shared
        return total

    np.testing.assert_allclose(programs.call(shares, p), uncut, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(uncut, programs.call(lambda p: routed_reference(x, p, whole), p), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_no_row_is_dropped_when_every_row_chooses_the_same_relu2_experts(impl):
    n, k, h, inner, held = 128, 4, 16, 8, 4
    rows = jax.random.normal(jax.random.PRNGKey(5), (n, h))
    w = [jax.random.normal(jax.random.PRNGKey(6 + i), s) * 0.3 for i, s in enumerate([(held, h, inner), (held, inner, h)])]
    chosen = jnp.broadcast_to(jnp.arange(k), (n, k))
    weights = jnp.full((n, k), 0.25)
    got, counters = programs.call(held_experts_output, rows, chosen, weights, *w, offset=0, impl=impl)
    want = programs.call(lambda rows, w: sum(0.25 * (jnp.square(jax.nn.relu(rows @ w[0][e])) @ w[1][e]) for e in range(k)), rows, w)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert counters.tolist() == [[n * k, n]]


def test_grouped_query_attention_agrees_through_the_interpreted_flash_op(monkeypatch):
    """Four query heads of 128 over two key/value heads, rows of two 128-event
    chunks with three segments and a padded tail: the flash op (interpreted)
    and the einsum against the reference's masked softmax."""
    cfg = hybrid_config(head_dim=128, attention_implementation="pallas_flash", init_std=0.2)
    seg = jnp.asarray(np.repeat([[0] * 100 + [1] * 60 + [2] * 96], 2, axis=0), jnp.int32)
    mask = jnp.ones(seg.shape, bool).at[1, 240:].set(False)
    x = jax.random.normal(jax.random.PRNGKey(0), seg.shape + (32,))
    module = GroupedQueryAttention(cfg)
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
    params = programs.init(module, jax.random.PRNGKey(1), x, mask, seg)
    assert params["params"]["k_proj"]["kernel"].shape == (32, 2 * 128)
    batch = {"event_mask": mask, "segment_ids": seg}
    reference = lambda p: ref.grouped_query_attention(x, p, sizes(cfg), ref.allowed_keys(batch), None)  # noqa: E731
    want = programs.call(reference, params["params"])
    real = np.asarray(mask)
    flash = programs.apply(module, params, x, mask, seg)
    np.testing.assert_allclose(np.asarray(flash)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)
    g_flash = programs.call(jax.grad(lambda p: jnp.sum(module.apply(p, x, mask, seg) ** 2 * mask[..., None])), params)
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "xla")
    with pytest.warns(UserWarning, match="taking the einsum path in grouped-query attention"):
        einsum = programs.apply(module, params, x, mask, seg)
    np.testing.assert_allclose(np.asarray(einsum)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)
    g_want = programs.call(jax.grad(lambda p: jnp.sum(reference(p) ** 2 * mask[..., None])), params["params"])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(g_flash["params"])[0], jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize("layer_id", [0, 1, 5], ids=["ssm", "routed", "attention"])
def test_each_single_part_block_agrees_with_the_plain_reference(layer_id):
    cfg = hybrid_config(n_routed_experts=8, moe_expert_offset=8)
    x, mask, seg = inputs("inside")
    block = KindsBlock(cfg, layer_id=layer_id)
    params = programs.init(block, jax.random.PRNGKey(8), x, mask, None, False, False, False, seg)["params"]
    part = {"M": "mixer", "E": "mlp", "*": "self_attn"}[PATTERN[layer_id]]
    assert set(params) == {"input_layernorm", part}  # one norm a layer, one part
    got, _ = programs.apply(block, {"params": params}, x, mask, None, False, False, False, seg)
    model, batch = sizes(cfg), {"event_mask": mask, "segment_ids": seg}

    def reference(params):
        normed = ref.rms_norm(x, params["input_layernorm"], cfg.layer_norm_epsilon)
        if part == "mixer":
            return x + ref.mamba_mixer(normed, params[part], model, mask, *ref._segments(batch), None)
        if part == "mlp":
            return x + ref.routed_feed_forward(normed, params[part], model, None)[0]
        return x + ref.grouped_query_attention(normed, params[part], model, ref.allowed_keys(batch), None)

    want = programs.call(reference, params)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=3e-5, atol=3e-5)


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="not a multiple of num_key_value_heads"):
        hybrid_config(num_key_value_heads=3)
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        hybrid_config(ffn_types="none")
    with pytest.raises(ValueError, match="mixer 'ssm' needs"):
        hybrid_config(ssm_state_size=None)
    with pytest.raises(ValueError, match="not a multiple of mamba_n_groups"):
        hybrid_config(mamba_n_groups=3)
    with pytest.raises(ValueError, match="moe_expert_form"):
        hybrid_config(moe_expert_form="gelu")
    with pytest.raises(ValueError, match="layer kinds"):
        hybrid_config(norm_type="layer_norm", head_dim=8)
    with pytest.raises(ValueError, match="as many key/value heads"):
        StructuredTransformerConfig(hidden_size=16, head_dim=4, num_key_value_heads=2)
    cfg = hybrid_config()
    assert cfg.uses_layer_kinds and cfg.head_dim * cfg.num_attention_heads != cfg.hidden_size
    assert "".join({"ssm": "M", "none": "", "mha": "*"}[m] or "E" for m in cfg.mixer_layers) == PATTERN
    assert StructuredTransformerConfig.from_dict(cfg.to_dict()) == cfg


def test_the_grouped_products_tiles_at_both_configurations_shapes():
    """`ops/grouped_matmul.py::_tiling` (rows, contraction, columns): what
    `glm47flash_ep8`'s expert shapes pick is what they picked before this
    configuration came; at 2,688 x 1,856 only the 128 tile divides 2,688 and
    no listed tile divides 1,856, which is taken whole."""
    from eventstreamgpt_tpu.ops.grouped_matmul import _tiling

    assert _tiling(16384, 2048, 1536) == (512, 1024, 768)  # glm47flash_ep8: gate and up
    assert _tiling(16384, 1536, 2048) == (512, 512, 512)  # ... down, and the two backward products
    assert _tiling(16384, 2688, 1856) == (512, 128, 1856)  # nemotron_twotower_ep16: up
    assert _tiling(16384, 1856, 2688) == (512, 1856, 128)  # ... down
