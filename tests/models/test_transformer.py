"""Tests for the CI encoder stack.

Mirrors ``tests/transformer/test_transformer.py`` in the reference: shape
preservation, event-mask sensitivity, time encoding, and the gold-standard
cache-equivalence invariant (iterative cached decoding must reproduce the
uncached forward — reference ``test_transformer.py:208``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from eventstreamgpt_tpu.data.types import EventStreamBatch
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.transformer import (
    ConditionallyIndependentPointProcessTransformer,
    TemporalPositionEncoding,
    init_kv_caches,
    make_causal_mask,
    time_from_deltas,
)


def small_config(**kwargs):
    defaults = dict(
        vocab_sizes_by_measurement={"event_type": 4, "lab": 8},
        vocab_offsets_by_measurement={"event_type": 1, "lab": 5},
        measurements_idxmap={"event_type": 1, "lab": 2},
        max_seq_len=10,
        hidden_size=16,
        head_dim=4,
        num_attention_heads=4,
        num_hidden_layers=2,
        seq_attention_types=["local", "global"],
        seq_window_size=3,
        intermediate_size=16,
    )
    defaults.update(kwargs)
    return StructuredTransformerConfig(**defaults)


def make_batch(B=2, L=6, M=3, seed=0):
    rng = np.random.default_rng(seed)
    event_mask = np.ones((B, L), dtype=bool)
    event_mask[1, L - 2 :] = False
    dynamic_indices = rng.integers(1, 12, size=(B, L, M))
    dynamic_indices[~event_mask] = 0
    return EventStreamBatch(
        event_mask=jnp.asarray(event_mask),
        time_delta=jnp.asarray(rng.uniform(0.5, 10.0, size=(B, L)).astype(np.float32)),
        static_indices=jnp.asarray(rng.integers(1, 12, size=(B, 2))),
        static_measurement_indices=jnp.asarray(np.ones((B, 2), dtype=np.int64)),
        dynamic_indices=jnp.asarray(dynamic_indices),
        dynamic_measurement_indices=jnp.asarray(np.where(dynamic_indices > 0, (dynamic_indices >= 5) + 1, 0)),
        dynamic_values=jnp.asarray(rng.normal(size=(B, L, M)).astype(np.float32)),
        dynamic_values_mask=jnp.asarray(rng.integers(0, 2, size=(B, L, M)).astype(bool)),
    )


class TestHelpers:
    def test_time_from_deltas(self):
        batch = EventStreamBatch(
            event_mask=jnp.asarray([[True, True, True], [True, True, False]]),
            time_delta=jnp.asarray([[1.0, 3.2, 0.0], [1.4, 0.0, 1.0]]),
        )
        np.testing.assert_allclose(
            np.asarray(time_from_deltas(batch)), [[0.0, 1.0, 4.2], [0.0, 1.4, 1.4]], rtol=1e-6
        )

    def test_make_causal_mask_global(self):
        m = make_causal_mask(jnp.arange(3), jnp.arange(3))
        expected = [[True, False, False], [True, True, False], [True, True, True]]
        np.testing.assert_array_equal(np.asarray(m), expected)

    def test_make_causal_mask_local(self):
        m = make_causal_mask(jnp.arange(4), jnp.arange(4), window_size=2)
        # Row i can see keys in (i-2, i].
        expected = [
            [True, False, False, False],
            [True, True, False, False],
            [False, True, True, False],
            [False, False, True, True],
        ]
        np.testing.assert_array_equal(np.asarray(m), expected)

    def test_temporal_position_encoding_matches_reference_formula(self):
        dim = 8
        layer = TemporalPositionEncoding(embedding_dim=dim)
        t = jnp.asarray([[0.0, 1.0, 2.5]])
        out = layer.apply({}, t)
        div = np.exp(np.arange(0, dim, 2) * (-np.log(10000.0) / dim))
        expected = np.zeros((1, 3, dim), dtype=np.float32)
        expected[0, :, 0::2] = np.sin(np.asarray(t)[0][:, None] * div)
        expected[0, :, 1::2] = np.cos(np.asarray(t)[0][:, None] * div)
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5, atol=1e-6)

    def test_temporal_position_encoding_odd_dim(self):
        layer = TemporalPositionEncoding(embedding_dim=7)
        out = layer.apply({}, jnp.ones((2, 4)))
        assert out.shape == (2, 4, 7)


class TestCIEncoder:
    def setup_method(self):
        self.config = small_config()
        self.batch = make_batch()
        self.model = ConditionallyIndependentPointProcessTransformer(self.config)
        self.params = self.model.init(jax.random.PRNGKey(0), self.batch)

    def test_output_shape(self):
        out = self.model.apply(self.params, self.batch)
        assert out.last_hidden_state.shape == (2, 6, 16)

    def test_masked_events_do_not_affect_earlier_outputs(self):
        """Causality: changing a later event must not change earlier outputs."""
        out1 = self.model.apply(self.params, self.batch)
        modified = self.batch.replace(
            dynamic_indices=self.batch.dynamic_indices.at[:, -1].set(3),
            dynamic_values=self.batch.dynamic_values.at[:, -1].set(9.9),
        )
        out2 = self.model.apply(self.params, modified)
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state[:, :-1]),
            np.asarray(out2.last_hidden_state[:, :-1]),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_padding_mask_sensitivity(self):
        """Real-event outputs must not depend on padded events' content."""
        out1 = self.model.apply(self.params, self.batch)
        modified = self.batch.replace(
            dynamic_indices=self.batch.dynamic_indices.at[1, -1].set(7),
            time_delta=self.batch.time_delta.at[1, -1].set(99.0),
        )
        out2 = self.model.apply(self.params, modified)
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state[1, :4]),
            np.asarray(out2.last_hidden_state[1, :4]),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_hidden_states_and_attentions_outputs(self):
        out = self.model.apply(
            self.params, self.batch, output_attentions=True, output_hidden_states=True
        )
        assert len(out.hidden_states) == 3  # embeddings + 2 layers (final normed)
        assert len(out.attentions) == 2
        assert out.attentions[0].shape == (2, 4, 6, 6)

    def test_cached_forward_matches_uncached(self):
        """Iterative cached decoding reproduces the full uncached forward.

        The reference's most important encoder invariant
        (``test_transformer.py:208``).
        """
        full = self.model.apply(self.params, self.batch)

        B, L = self.batch.event_mask.shape
        caches = init_kv_caches(self.config, B, max_len=L)
        t_full = time_from_deltas(self.batch)
        step_outputs = []
        for i in range(L):
            step_batch = self.batch.slice((slice(None), slice(i, i + 1))).replace(
                time=t_full[:, i : i + 1]
            )
            out = self.model.apply(self.params, step_batch, past=caches, use_cache=True)
            caches = out.past_key_values
            step_outputs.append(np.asarray(out.last_hidden_state[:, 0]))

        stacked = np.stack(step_outputs, axis=1)
        np.testing.assert_allclose(
            stacked, np.asarray(full.last_hidden_state), rtol=1e-4, atol=1e-5
        )

    def test_jit_and_grad(self):
        def loss_fn(params):
            out = self.model.apply(params, self.batch)
            return jnp.sum(out.last_hidden_state**2)

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(self.params)
        assert np.isfinite(float(loss))
        flat = jax.tree_util.tree_leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)

    def test_gradient_checkpointing_same_output(self):
        model_ckpt = ConditionallyIndependentPointProcessTransformer(
            self.config, use_gradient_checkpointing=True
        )
        out1 = self.model.apply(self.params, self.batch)
        out2 = model_ckpt.apply(self.params, self.batch)
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state), np.asarray(out2.last_hidden_state), rtol=1e-5
        )


class TestRematPolicies:
    """Every gradient_checkpointing policy computes identical loss + grads.

    Rematerialization only changes WHAT is recomputed in the backward, never
    the math; a measured step picks for speed, this pins correctness.
    """

    def test_policies_match_no_remat(self):
        batch = make_batch()
        ref_grads = None
        for policy in ("none", "block", "dots", "dots_no_batch", "save_attention"):
            config = small_config(gradient_checkpointing=policy)
            model = ConditionallyIndependentPointProcessTransformer(config)
            params = model.init(jax.random.PRNGKey(0), batch)

            def loss_fn(p):
                out = model.apply(p, batch)
                return (out.last_hidden_state.astype(jnp.float32) ** 2).sum()

            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
            if ref_grads is None:
                ref_loss, ref_grads = loss, grads
                continue
            np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
            for g, r in zip(
                jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)
            ):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-6
                )
