"""Tests for the nested-attention encoder + model.

Mirrors the reference's ``tests/transformer/test_structured_attention.py`` and
``test_nested_attention_model.py``: structured-attention data flow, dep-graph
causality, training-path losses, and the cached-vs-uncached equivalence of the
per-dep-graph-level decode pipeline (the reference's gold invariant,
``test_nested_attention_model.py:747``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from eventstreamgpt_tpu.data.types import EventStreamBatch
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.na_model import NAPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.transformer import (
    NAPast,
    NestedAttentionPointProcessTransformer,
    init_kv_caches,
    time_from_deltas,
)

from .. import _programs as programs

# Vocab layout: event_type [1, 4), multi_lab [4, 8), lab_vals [8, 12).
G = 3  # dep graph: [time-dependent (empty here), event_type, labs]


def make_config(**kwargs):
    defaults = dict(
        vocab_sizes_by_measurement={"event_type": 3, "multi_lab": 4, "lab_vals": 4},
        vocab_offsets_by_measurement={"event_type": 1, "multi_lab": 4, "lab_vals": 8},
        measurements_idxmap={"event_type": 1, "multi_lab": 2, "lab_vals": 3},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["multi_lab", "lab_vals"],
            "multivariate_regression": ["lab_vals"],
        },
        structured_event_processing_mode="nested_attention",
        measurements_per_dep_graph_level=[
            [],
            ["event_type"],
            ["multi_lab", "lab_vals"],
        ],
        max_seq_len=8,
        hidden_size=16,
        head_dim=4,
        num_attention_heads=4,
        num_hidden_layers=2,
        intermediate_size=16,
        seq_attention_types="global",
        dep_graph_attention_types="global",
        do_full_block_in_seq_attention=False,
        do_full_block_in_dep_graph_attention=True,
    )
    defaults.update(kwargs)
    return StructuredTransformerConfig(**defaults)


def make_batch(B=2, L=4, M=5, seed=0, all_real=True):
    rng = np.random.default_rng(seed)
    event_mask = np.ones((B, L), dtype=bool)
    if not all_real:
        event_mask[-1, L - 1 :] = False
    dyn_meas = np.zeros((B, L, M), dtype=np.int64)
    dyn_idx = np.zeros((B, L, M), dtype=np.int64)
    dyn_vals = np.zeros((B, L, M), dtype=np.float32)
    dyn_vmask = np.zeros((B, L, M), dtype=bool)
    for b in range(B):
        for l in range(L):
            if not event_mask[b, l]:
                continue
            dyn_meas[b, l, 0] = 1
            dyn_idx[b, l, 0] = rng.integers(1, 4)
            dyn_meas[b, l, 1] = 2
            dyn_idx[b, l, 1] = rng.integers(4, 8)
            dyn_meas[b, l, 2] = 3
            dyn_idx[b, l, 2] = rng.integers(8, 12)
            dyn_vals[b, l, 2] = rng.normal()
            dyn_vmask[b, l, 2] = True
    return EventStreamBatch(
        event_mask=jnp.asarray(event_mask),
        time_delta=jnp.asarray(rng.uniform(0.5, 10.0, size=(B, L)).astype(np.float32)),
        static_indices=jnp.asarray(rng.integers(1, 12, size=(B, 2))),
        static_measurement_indices=jnp.asarray(np.ones((B, 2), dtype=np.int64)),
        dynamic_indices=jnp.asarray(dyn_idx),
        dynamic_measurement_indices=jnp.asarray(dyn_meas),
        dynamic_values=jnp.asarray(dyn_vals),
        dynamic_values_mask=jnp.asarray(dyn_vmask),
    )


class TestNAEncoder:
    @pytest.fixture(autouse=True, scope="class")
    def built(self, request):
        cls = request.cls
        cls.config = make_config()
        cls.batch = make_batch()
        cls.encoder = NestedAttentionPointProcessTransformer(cls.config)
        cls.params = programs.init(cls.encoder, jax.random.PRNGKey(0), cls.batch)

    def test_output_shape(self):
        out = programs.apply(self.encoder, self.params, self.batch)
        assert out.last_hidden_state.shape == (2, 4, G, 16)

    def test_seq_causality(self):
        """Changing a later event must not change earlier events' outputs."""
        out1 = programs.apply(self.encoder, self.params, self.batch)
        modified = self.batch.replace(
            dynamic_indices=self.batch.dynamic_indices.at[:, -1, 0].set(2),
            time_delta=self.batch.time_delta.at[:, -1].set(42.0),
        )
        out2 = programs.apply(self.encoder, self.params, modified)
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state[:, :-1]),
            np.asarray(out2.last_hidden_state[:, :-1]),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_dep_graph_causality(self):
        """Level j's content must not leak into outputs at graph positions < j.

        Output position p attends [history, levels 0..p], so changing level-2
        data (labs, graph slot 2) may only affect output positions >= 2.
        """
        out1 = programs.apply(self.encoder, self.params, self.batch)
        # Labs live at data-element slot 2 (measurement 3, graph level 2).
        modified = self.batch.replace(
            dynamic_values=self.batch.dynamic_values.at[:, :, 2].set(7.7),
        )
        out2 = programs.apply(self.encoder, self.params, modified)
        # Graph output positions 0 and 1 (predicting levels 1 and 2) see only
        # levels 0..1 of the same event — position 1 sees level 1 only.
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state[:, 0, :2]),
            np.asarray(out2.last_hidden_state[:, 0, :2]),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_event_mask_zeroing(self):
        batch = make_batch(all_real=False)
        out = programs.apply(self.encoder, self.params, batch)
        np.testing.assert_allclose(np.asarray(out.last_hidden_state[-1, -1]), 0.0)

    def test_cached_dep_graph_decode_matches_uncached(self):
        """The three-phase cached decode reproduces the uncached forward
        across MULTIPLE consecutive events.

        Phase 1: full cached forward over events [0, L-2) (target=None).
        Then for each of the last two events: per-level decode (targets
        1..G-1) followed by target=0 on the completed event. Decoding two
        events exercises the post-reset dep-graph cache buffer — a reset
        buffer sized from the trimmed input instead of the static config
        overflows on the second event (silent dynamic_update_slice clamping).
        Each phase's outputs must match the corresponding slice of the
        uncached full forward.
        """
        B, L = self.batch.event_mask.shape
        n_decode = 2  # decode the last two events through the cached machine
        full = programs.apply(self.encoder, self.params, self.batch)

        prefix = self.batch.slice((slice(None), slice(0, L - n_decode)))
        out1 = programs.apply(
            self.encoder,
            self.params,
            prefix,
            past=NAPast(
                seq_past=init_kv_caches(self.config, B, max_len=L),
                dep_graph_past=None,
            ),
            use_cache=True,
        )
        past = out1.past_key_values
        np.testing.assert_allclose(
            np.asarray(out1.last_hidden_state),
            np.asarray(full.last_hidden_state[:, : L - n_decode]),
            rtol=1e-4,
            atol=1e-5,
        )

        t_full = time_from_deltas(self.batch)
        for ev in range(L - n_decode, L):
            trimmed = self.batch.slice((slice(None), slice(ev, ev + 1))).replace(
                time=t_full[:, ev : ev + 1]
            )

            for target in range(1, G):
                out_t = programs.apply(
                    self.encoder,
                    self.params,
                    trimmed,
                    past=past,
                    use_cache=True,
                    dep_graph_el_generation_target=target,
                )
                past = out_t.past_key_values
                np.testing.assert_allclose(
                    np.asarray(out_t.last_hidden_state[:, 0, 0]),
                    np.asarray(full.last_hidden_state[:, ev, target - 1]),
                    rtol=1e-4,
                    atol=1e-5,
                    err_msg=f"event={ev} target={target}",
                )

            out_0 = programs.apply(
                self.encoder,
                self.params,
                trimmed,
                past=past,
                use_cache=True,
                dep_graph_el_generation_target=0,
            )
            past = out_0.past_key_values
            np.testing.assert_allclose(
                np.asarray(out_0.last_hidden_state[:, 0, 0]),
                np.asarray(full.last_hidden_state[:, ev, G - 1]),
                rtol=1e-4,
                atol=1e-5,
                err_msg=f"event={ev} target=0",
            )


class TestNAModel:
    @pytest.fixture(autouse=True, scope="class")
    def built(self, request):
        cls = request.cls
        cls.config = make_config()
        cls.batch = make_batch()
        cls.model = NAPPTForGenerativeSequenceModeling(cls.config)
        cls.params = programs.init(cls.model, jax.random.PRNGKey(0), cls.batch)

    def test_forward_losses(self):
        out = jax.jit(self.model.apply)(self.params, self.batch)
        assert np.isfinite(float(out.loss))
        assert set(out.losses.classification) == {"event_type", "multi_lab", "lab_vals"}
        assert set(out.losses.regression) == {"lab_vals"}
        assert np.isfinite(float(out.losses.time_to_event))

    def test_trains(self):
        tx = optax.adamw(3e-3)
        params = self.params
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(lambda p: self.model.apply(p, self.batch).loss)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(20):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_generation_mode(self):
        out = programs.apply(self.model, self.params, self.batch, is_generation=True)
        assert out.loss is None
        assert out.preds.time_to_event is not None

    def test_ci_mode_config_rejected(self):
        ci_config = StructuredTransformerConfig(hidden_size=16, head_dim=4, num_attention_heads=4)
        with pytest.raises(ValueError):
            model = NAPPTForGenerativeSequenceModeling(ci_config)
            model.init(jax.random.PRNGKey(0), self.batch)
