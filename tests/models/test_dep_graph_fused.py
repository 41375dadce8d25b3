"""Parity gates for the fused NA dep-graph attention + head-stack levers.

ISSUE 4 (MFU round) contract: the fused dep-graph walk
(``ops/band_attention.dep_graph_attention``, routed by
``config.dep_graph_fused_attention``) and the narrow classification
projections (``config.head_narrow_projections``) are *pure formulation*
changes — numerics must match the unfused/full-plane paths on padded,
packed-segment, and cached-decode inputs, in fp32 and bf16.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from eventstreamgpt_tpu.models.model_output import VocabProjection
from eventstreamgpt_tpu.models.na_model import NAPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.transformer import (
    NAPast,
    NestedAttentionPointProcessTransformer,
    init_kv_caches,
    time_from_deltas,
)
from eventstreamgpt_tpu.ops.band_attention import dep_graph_attention

from .. import _programs as programs
from .test_na_model import G, make_batch, make_config


def einsum_reference(q, k, v, q_offset=0, window=None):
    """The unfused formulation (models/transformer.py einsum path), verbatim."""
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k, preferred_element_type=jnp.float32)
    q_pos = jnp.arange(q.shape[1]) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", probs, v)


class TestFusedOp:
    """Op-level: dep_graph_attention == masked-einsum attention."""

    def _qkv(self, dtype=jnp.float32, N=6, S=4, H=2, D=8, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(rng.normal(size=(N, S, H, D)).astype(np.float32)).astype(dtype)  # noqa: E731
        return mk(), mk(), mk()

    def test_matches_einsum_global(self):
        q, k, v = self._qkv()
        out = dep_graph_attention(q[:, 1:], k, v, q_offset=1)
        ref = einsum_reference(q[:, 1:], k, v, q_offset=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    def test_matches_einsum_no_offset(self):
        q, k, v = self._qkv(seed=1)
        out = dep_graph_attention(q, k, v, q_offset=0)
        ref = einsum_reference(q, k, v, q_offset=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    def test_matches_einsum_windowed(self):
        q, k, v = self._qkv(seed=2)
        out = dep_graph_attention(q[:, 1:], k, v, q_offset=1, window=2)
        ref = einsum_reference(q[:, 1:], k, v, q_offset=1, window=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    def test_matches_einsum_bf16(self):
        q, k, v = self._qkv(dtype=jnp.bfloat16, seed=3)
        out = dep_graph_attention(q, k, v).astype(jnp.float32)
        ref = einsum_reference(q, k, v).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_causality(self):
        """Perturbing key/value position j must not change outputs at q < j."""
        q, k, v = self._qkv(seed=4)
        out1 = dep_graph_attention(q[:, 1:], k, v, q_offset=1)
        k2 = k.at[:, -1].add(5.0)
        v2 = v.at[:, -1].add(5.0)
        out2 = dep_graph_attention(q[:, 1:], k2, v2, q_offset=1)
        # Query i (absolute position i+1) sees keys <= i+1; only the last
        # query attends the last key.
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-6, atol=1e-6
        )
        assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


def _fused_and_unfused(**kwargs):
    fused_cfg = make_config(**kwargs)
    unfused_cfg = make_config(dep_graph_fused_attention=False, **kwargs)
    return fused_cfg, unfused_cfg


class TestModelParity:
    """Model-level: fused and unfused paths share params and numerics."""

    def test_forward_parity_padded(self):
        fused_cfg, unfused_cfg = _fused_and_unfused()
        batch = make_batch(all_real=False)
        enc_f = NestedAttentionPointProcessTransformer(fused_cfg)
        enc_u = NestedAttentionPointProcessTransformer(unfused_cfg)
        params = programs.init(enc_f, jax.random.PRNGKey(0), batch)
        out_f = programs.apply(enc_f, params, batch)
        out_u = programs.apply(enc_u, params, batch)  # identical param tree
        np.testing.assert_allclose(
            np.asarray(out_f.last_hidden_state),
            np.asarray(out_u.last_hidden_state),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_loss_and_grads_parity(self):
        fused_cfg, unfused_cfg = _fused_and_unfused()
        batch = make_batch()
        model_f = NAPPTForGenerativeSequenceModeling(fused_cfg)
        model_u = NAPPTForGenerativeSequenceModeling(unfused_cfg)
        params = programs.init(model_f, jax.random.PRNGKey(0), batch)

        loss_f, grads_f = programs.call(jax.value_and_grad(lambda p: model_f.apply(p, batch).loss), params)
        loss_u, grads_u = programs.call(jax.value_and_grad(lambda p: model_u.apply(p, batch).loss), params)
        np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-6)
        for gf, gu in zip(jax.tree_util.tree_leaves(grads_f), jax.tree_util.tree_leaves(grads_u)):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gu), rtol=2e-4, atol=1e-6)

    def test_forward_parity_packed_segments(self):
        fused_cfg, unfused_cfg = _fused_and_unfused()
        batch = make_batch(B=2, L=6)
        seg = jnp.asarray([[0, 0, 0, 1, 1, 1], [0, 0, 1, 1, 1, 1]], dtype=jnp.int32)
        batch = batch.replace(segment_ids=seg)
        enc_f = NestedAttentionPointProcessTransformer(fused_cfg)
        enc_u = NestedAttentionPointProcessTransformer(unfused_cfg)
        params = programs.init(enc_f, jax.random.PRNGKey(0), batch)
        out_f = programs.apply(enc_f, params, batch)
        out_u = programs.apply(enc_u, params, batch)
        np.testing.assert_allclose(
            np.asarray(out_f.last_hidden_state),
            np.asarray(out_u.last_hidden_state),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_loss_parity_bf16(self):
        fused_cfg, unfused_cfg = _fused_and_unfused(precision="bf16")
        batch = make_batch()
        model_f = NAPPTForGenerativeSequenceModeling(fused_cfg)
        model_u = NAPPTForGenerativeSequenceModeling(unfused_cfg)
        params = programs.init(model_f, jax.random.PRNGKey(0), batch)
        loss_f = float(programs.loss(model_f, params, batch))
        loss_u = float(programs.loss(model_u, params, batch))
        assert abs(loss_f - loss_u) < 5e-2 * max(1.0, abs(loss_u))

    def test_cached_decode_matches_fused_uncached(self):
        """Cached decode rides the einsum path; the uncached forward rides
        the fused path (the production default). The three-phase decode must
        reproduce the fused forward — the cross-path half of the parity gate
        (the einsum-vs-einsum version lives in
        test_na_model.test_cached_dep_graph_decode_matches_uncached).
        """
        config = make_config()
        batch = make_batch()
        B, L = batch.event_mask.shape
        encoder = NestedAttentionPointProcessTransformer(config)
        params = programs.init(encoder, jax.random.PRNGKey(0), batch)
        full = programs.apply(encoder, params, batch)  # fused path

        prefix = batch.slice((slice(None), slice(0, L - 1)))
        out1 = programs.apply(
            encoder,
            params,
            prefix,
            past=NAPast(seq_past=init_kv_caches(config, B, max_len=L), dep_graph_past=None),
            use_cache=True,
        )
        past = out1.past_key_values
        t_full = time_from_deltas(batch)
        trimmed = batch.slice((slice(None), slice(L - 1, L))).replace(
            time=t_full[:, L - 1 : L]
        )
        for target in range(1, G):
            out_t = programs.apply(
                encoder, params, trimmed, past=past, use_cache=True,
                dep_graph_el_generation_target=target,
            )
            past = out_t.past_key_values
            np.testing.assert_allclose(
                np.asarray(out_t.last_hidden_state[:, 0, 0]),
                np.asarray(full.last_hidden_state[:, L - 1, target - 1]),
                rtol=1e-4,
                atol=1e-5,
                err_msg=f"target={target}",
            )
        out_0 = programs.apply(
            encoder, params, trimmed, past=past, use_cache=True, dep_graph_el_generation_target=0
        )
        np.testing.assert_allclose(
            np.asarray(out_0.last_hidden_state[:, 0, 0]),
            np.asarray(full.last_hidden_state[:, L - 1, G - 1]),
            rtol=1e-4,
            atol=1e-5,
        )


class TestNarrowHeadProjections:
    """`head_narrow_projections` is formulation-only: same params, same math."""

    def test_vocab_projection_is_dense_compatible(self):
        vp = VocabProjection(features=12, in_features=8, dtype=jnp.float32)
        dense = nn.Dense(12, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)).astype(np.float32))
        pv = vp.init(jax.random.PRNGKey(7), x)
        pd = dense.init(jax.random.PRNGKey(7), x)
        assert jax.tree_util.tree_structure(pv) == jax.tree_util.tree_structure(pd)
        for a, b in zip(jax.tree_util.tree_leaves(pv), jax.tree_util.tree_leaves(pd)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(vp.apply(pv, x)), np.asarray(dense.apply(pd, x))
        )

    def test_narrow_slice_matches_full_columns(self):
        vp = VocabProjection(features=12, in_features=8, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 8)).astype(np.float32))
        pv = vp.init(jax.random.PRNGKey(0), x)
        full = vp.apply(pv, x)
        narrow = vp.apply(pv, x, vocab_slice=(3, 9))
        np.testing.assert_allclose(
            np.asarray(narrow), np.asarray(full[:, 3:9]), rtol=1e-6, atol=1e-7
        )

    def test_na_model_narrow_matches_full(self):
        batch = make_batch()
        narrow_cfg = make_config()
        full_cfg = make_config(head_narrow_projections=False)
        model_n = NAPPTForGenerativeSequenceModeling(narrow_cfg)
        model_f = NAPPTForGenerativeSequenceModeling(full_cfg)
        params = programs.init(model_n, jax.random.PRNGKey(0), batch)
        out_n = programs.apply(model_n, params, batch)
        out_f = programs.apply(model_f, params, batch)
        np.testing.assert_allclose(float(out_n.loss), float(out_f.loss), rtol=1e-6)
        for m in out_n.losses.classification:
            np.testing.assert_allclose(
                float(out_n.losses.classification[m]),
                float(out_f.losses.classification[m]),
                rtol=1e-6,
                err_msg=m,
            )
