"""Hyper-connected residual streams (`models/hyper_connections.py`) in the
kinds block: the Sinkhorn map's sums, the streamed block against the plain one
at maps that make them equal, one stream as the parent's tree and numbers,
padding and packed segments, and what the configuration refuses."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.models.blocks import KindsBlock
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.hyper_connections import GAIN, RES_DIAGONAL, HyperConnection, post_mix, pre_mix, sinkhorn
from eventstreamgpt_tpu.models.transformer import ConditionallyIndependentPointProcessTransformer

from .test_layer_kinds import KINDS, inputs, kinds_config

N = 4


def streamed_config(**kwargs):
    return kinds_config(hc_mult=N, **kwargs)


@pytest.mark.parametrize("logits", ["seeded", "near_identity", "clamped_permutation", "clamped_flat"])
def test_the_res_map_is_doubly_stochastic_after_twenty_iterations(logits):
    """Rows and columns of ``H_res`` sum to 1 within 1e-4, at logits of order
    one and at logits far outside the clamp, where ``exp`` sees +-30 and
    nothing else. At the seed's logits (3.5 on the diagonal, 0.24 of noise) the
    rows do, being normalised last, and the columns are within 2e-3: a matrix
    this near the identity scales at 0.8 an iteration, and 20 iterations are 20
    (the reference runs the same 20; a 21st would move ``H_res`` by 2e-4)."""
    rng = np.random.default_rng(7)
    events = (3, 5)
    if logits == "seeded":
        z = rng.normal(size=(N, N) + events)
    elif logits == "near_identity":
        z = RES_DIAGONAL * np.eye(N)[:, :, None, None] + 0.24 * rng.normal(size=(N, N) + events)
    elif logits == "clamped_permutation":
        z = np.full((N, N) + events, -1000.0)
        for e in np.ndindex(events):
            z[(np.arange(N), rng.permutation(N)) + e] = 1000.0
    else:
        z = np.full((N, N) + events, 1000.0)
    m = np.asarray(sinkhorn(jnp.asarray(z, jnp.float32), iters=20, eps=1e-6, clamp=30.0))
    assert np.isfinite(m).all() and (m >= 0).all()
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=2e-3 if logits == "near_identity" else 1e-4)
    if logits == "clamped_permutation":
        assert ((m > 0.999) | (m < 1e-3)).all()  # the clamp lets exp reach e^30 against e^-30 and no further


def test_the_seeds_maps_are_near_a_plain_residual_and_move_with_phi():
    """At the module's own initialisation ``H_pre`` is near 1/n, ``H_post``
    near 1 and ``H_res`` near the identity with a few percent of a row's mass
    off the diagonal; ``Phi``'s product moves each of them by a tenth."""
    cfg = streamed_config(init_std=0.02)
    streams = tuple(jax.random.normal(jax.random.PRNGKey(i), (2, 12, 32 * 14)) for i in range(N))  # n C = 1,792
    module = HyperConnection(cfg)
    params = module.init(jax.random.PRNGKey(9), streams)["params"]
    assert {k: v.shape for k, v in params.items()} == {"phi": (N * 448, 24), "gain": (3,), "bias": (24,)}
    np.testing.assert_allclose(params["gain"], GAIN)
    np.testing.assert_allclose(jax.nn.sigmoid(params["bias"][:N]), 1 / N, rtol=1e-6)
    h_pre, h_post, h_res = module.apply({"params": params}, streams)
    assert h_pre.shape == (N, 2, 12) and h_post.shape == (N, 2, 12) and h_res.shape == (N, N, 2, 12)
    assert abs(float(h_pre.mean()) - 1 / N) < 0.02 and 0.002 < float(h_pre.std()) < 0.05
    assert abs(float(h_post.mean()) - 1.0) < 0.05 and 0.01 < float(h_post.std()) < 0.2
    off_diagonal = 1.0 - float(jnp.mean(jnp.trace(h_res)) / N)
    assert 0.03 < off_diagonal < 0.2
    assert abs(off_diagonal - 3 / (math.exp(RES_DIAGONAL) + 3)) < 0.02


def identity_maps(n: int) -> dict:
    """Maps that make a streamed block the plain block on every stream:
    ``H_pre`` = 1/n, ``H_post`` = 1, ``H_res`` = I whatever ``Phi`` says."""
    bias = jnp.concatenate([jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)), (60.0 * jnp.eye(n) - 30.0).reshape(-1)])
    return {"gain": jnp.zeros((3,)), "bias": bias}


@pytest.mark.parametrize("layer", [0, 1], ids=["latent + swiglu", "latent + routed"])
def test_at_identity_maps_the_streamed_block_is_the_plain_block_on_every_stream(layer):
    plain_cfg, cfg = kinds_config(), streamed_config()
    x, mask, segment_ids = inputs()
    args = (mask, None, False, False, False, segment_ids)
    plain = KindsBlock(plain_cfg, layer_id=layer)
    plain_params = plain.init(jax.random.PRNGKey(1), x, *args)["params"]
    want, _ = plain.apply({"params": plain_params}, x, *args, mutable=["routing"])[0]
    block = KindsBlock(cfg, layer_id=layer)
    params = block.init(jax.random.PRNGKey(2), (x,) * N, *args)["params"]
    assert set(params) == set(plain_params) | {"mixer_hc", "ffn_hc"}
    params = {**plain_params, **{k: {"phi": params[k]["phi"], **identity_maps(N)} for k in ("mixer_hc", "ffn_hc")}}
    got, _ = block.apply({"params": params}, (x,) * N, *args, mutable=["routing"])[0]
    assert len(got) == N
    for stream in got:
        np.testing.assert_allclose(stream, want, rtol=1e-6, atol=1e-6 * float(jnp.abs(want).max()))


def test_the_mixes_are_the_equations():
    rng = np.random.default_rng(3)
    streams = tuple(jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32) for _ in range(N))
    y = jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32)
    h_pre, h_post = (jnp.asarray(rng.uniform(size=(N, 2, 5)), jnp.float32) for _ in range(2))
    h_res = jnp.asarray(rng.uniform(size=(N, N, 2, 5)), jnp.float32)
    x = np.stack(streams)  # [n, B, S, C]
    np.testing.assert_allclose(pre_mix(streams, h_pre), np.einsum("ibs,ibsc->bsc", h_pre, x), rtol=1e-5, atol=1e-6)
    want = np.einsum("ijbs,jbsc->ibsc", h_res, x) + np.asarray(h_post)[..., None] * np.asarray(y)[None]
    np.testing.assert_allclose(np.stack(post_mix(streams, y, h_post, h_res)), want, rtol=1e-5, atol=1e-6)


# What the parent commit's tree gives for this model on these inputs (float32, CPU), to the bit.
PARENT_LEAVES, PARENT_PARAMETERS = 47, 95816
PARENT_OUTPUTS = {(1, 7, 3): -0.2588144838809967, (0, 11, 30): 0.6008114218711853}
PARENT_WEIGHTED_SUM = 1021.5831298828125


@pytest.mark.parametrize("given", [{}, {"hc_mult": 1}], ids=["default", "hc_mult=1"])
def test_one_stream_is_the_parents_tree_and_the_parents_numbers(given):
    cfg = kinds_config(**given)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, KINDS["hidden_size"]))
    model = ConditionallyIndependentPointProcessTransformer(cfg)
    params = model.init(jax.random.PRNGKey(1), None, x)
    leaves = jax.tree_util.tree_flatten_with_path(params["params"])[0]
    assert len(leaves) == PARENT_LEAVES and sum(a.size for _, a in leaves) == PARENT_PARAMETERS
    assert not [path for path, _ in leaves if "_hc" in jax.tree_util.keystr(path)]
    out = model.apply(params, None, x, mutable=["routing"])[0].last_hidden_state
    for at, value in PARENT_OUTPUTS.items():
        assert float(out[at]) == value
    assert float(jnp.sum(out * jnp.arange(32.0))) == PARENT_WEIGHTED_SUM


def _encode(cfg, params, x, mask, segment_ids):
    from eventstreamgpt_tpu.data.types import EventStreamBatch

    batch = EventStreamBatch(event_mask=mask, segment_ids=segment_ids)
    model = ConditionallyIndependentPointProcessTransformer(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(5), batch, x)
    out = model.apply(params, batch, x, output_hidden_states=True, mutable=["routing"])[0]
    return params, out


def test_padding_stays_zero_and_a_segment_does_not_see_another():
    """Four streams through three layers on packed rows: a padding slot is zero
    after every layer (``output_hidden_states`` gives a layer's streams summed,
    one array a layer as for one stream), and what an event reads out does not
    move when the events of another segment of its row change."""
    cfg = streamed_config()
    x, mask, segment_ids = inputs()
    x = jnp.where(mask[..., None], x, 0.0)
    params, out = _encode(cfg, None, x, mask, segment_ids)
    assert len(out.hidden_states) == cfg.num_hidden_layers + 1
    np.testing.assert_allclose(np.asarray(out.hidden_states[0]), N * np.asarray(x), rtol=1e-6)  # replicated in
    for state in out.hidden_states:
        assert state.shape == x.shape
        np.testing.assert_array_equal(np.asarray(state)[~np.asarray(mask)], 0.0)
    assert out.last_hidden_state.shape == x.shape
    # row 0 holds segments 0 (events 0-4), 1 (5-8) and 2 (9-11): change segment 1
    moved = x.at[0, 5:9].add(jax.random.normal(jax.random.PRNGKey(8), (4, x.shape[-1])))
    _, other = _encode(cfg, params, moved, mask, segment_ids)
    a, b = np.asarray(out.last_hidden_state), np.asarray(other.last_hidden_state)
    np.testing.assert_allclose(b[0, :5], a[0, :5], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(b[0, 9:], a[0, 9:], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(b[1], a[1], rtol=1e-6, atol=1e-6)
    assert np.abs(b[0, 5:9] - a[0, 5:9]).max() > 1e-2


def test_the_streams_take_gradients_through_the_sinkhorn_loop():
    cfg = streamed_config()
    x, mask, segment_ids = inputs()
    params, _ = _encode(cfg, None, x, mask, segment_ids)

    def loss(p):
        model = ConditionallyIndependentPointProcessTransformer(cfg)
        from eventstreamgpt_tpu.data.types import EventStreamBatch

        out = model.apply(p, EventStreamBatch(event_mask=mask, segment_ids=segment_ids), x, mutable=["routing"])[0]
        return jnp.sum(out.last_hidden_state * jnp.arange(32.0))

    grads = jax.grad(loss)(params)["params"]
    for layer in ("h0", "h1", "h2"):
        for maps in ("mixer_hc", "ffn_hc"):
            for leaf in ("phi", "gain", "bias"):
                g = np.asarray(grads[layer][maps][leaf])
                assert np.isfinite(g).all() and np.abs(g).max() > 0, (layer, maps, leaf)


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="only the kinds block"):
        StructuredTransformerConfig(hidden_size=32, num_attention_heads=4, head_dim=8, hc_mult=4)
    with pytest.raises(ValueError, match="scan_layers"):
        streamed_config(scan_layers=True)
    with pytest.raises(ValueError, match="whole numbers"):
        kinds_config(hc_mult=0)
    with pytest.raises(ValueError, match="rope_scaling"):
        kinds_config(rope_scaling={"type": "linear", "factor": 2})
    cfg = StructuredTransformerConfig.from_dict(streamed_config(hc_sinkhorn_iters=7).to_dict())
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (N, 7, 1e-6, 30.0)
