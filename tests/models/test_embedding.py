"""Tests for the JAX DataEmbeddingLayer.

Mirrors the validation + math coverage of the reference's
``tests/data/test_data_embedding_layer.py`` (913 LoC): constructor errors,
joint vs split embedding math against hand-computed expectations, measurement
bucketing, and full forward shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.data.types import EventStreamBatch
from eventstreamgpt_tpu.models.embedding import (
    DataEmbeddingLayer,
    EmbeddingMode,
    MeasIndexGroupOptions,
    StaticEmbeddingMode,
)


def make_batch():
    """The reference doctest batch (``data_embedding_layer.py:628-650``)."""
    return EventStreamBatch(
        event_mask=jnp.asarray([[True, True, True], [True, True, False]]),
        static_indices=jnp.asarray([[1, 2, 3], [4, 5, 6]]),
        static_measurement_indices=jnp.asarray([[1, 1, 2], [2, 2, 3]]),
        dynamic_indices=jnp.asarray([[[7, 8], [11, 10], [8, 7]], [[8, 7], [8, 10], [0, 0]]]),
        dynamic_measurement_indices=jnp.asarray([[[4, 4], [5, 5], [4, 4]], [[4, 4], [4, 5], [0, 0]]]),
        dynamic_values=jnp.asarray([[[1.0, 2.0], [0.0, 0.0], [1.1, 2.1]], [[5.0, 6.0], [7.0, 0.0], [0.0, 0.0]]]),
        dynamic_values_mask=jnp.asarray(
            [
                [[True, True], [False, False], [True, True]],
                [[True, True], [True, False], [False, False]],
            ]
        ),
    )


def init_layer(layer, batch):
    params = layer.init(jax.random.PRNGKey(0), batch)
    return params


class TestConstruction:
    def test_joint_mode_selected(self):
        layer = DataEmbeddingLayer(
            n_total_embeddings=100, out_dim=10, static_embedding_mode=StaticEmbeddingMode.DROP
        )
        assert layer.embedding_mode == EmbeddingMode.JOINT

    def test_split_mode_selected(self):
        layer = DataEmbeddingLayer(
            n_total_embeddings=100,
            out_dim=10,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            categorical_embedding_dim=5,
            numerical_embedding_dim=5,
        )
        assert layer.embedding_mode == EmbeddingMode.SPLIT_CATEGORICAL_NUMERICAL

    @pytest.mark.parametrize(
        "kwargs,err",
        [
            (dict(n_total_embeddings=100, out_dim="10"), TypeError),
            (dict(n_total_embeddings=100, out_dim=-10), ValueError),
            (dict(n_total_embeddings="100", out_dim=10), TypeError),
            (dict(n_total_embeddings=-100, out_dim=10), ValueError),
            (
                dict(n_total_embeddings=100, out_dim=10, categorical_embedding_dim=5),
                ValueError,
            ),
            (
                dict(
                    n_total_embeddings=100,
                    out_dim=10,
                    categorical_embedding_dim=5,
                    numerical_embedding_dim=5,
                    split_by_measurement_indices=(4, (5, MeasIndexGroupOptions.CATEGORICAL_ONLY)),
                ),
                TypeError,
            ),
        ],
    )
    def test_constructor_errors(self, kwargs, err):
        kwargs.setdefault("static_embedding_mode", StaticEmbeddingMode.DROP)
        with pytest.raises(err):
            DataEmbeddingLayer(**kwargs)


class TestJointEmbedding:
    def test_joint_forward_math(self):
        """Joint mode: observed values weight embeddings; missing values act as 1."""
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12, out_dim=4, static_embedding_mode=StaticEmbeddingMode.DROP
        )
        params = init_layer(layer, batch)
        table = np.asarray(params["params"]["embed_table"])
        out = np.asarray(layer.apply(params, batch))

        assert out.shape == (2, 3, 4)
        # Event (0, 0): indices (7, 8), values (1, 2) both observed.
        expected_00 = table[7] * 1.0 + table[8] * 2.0
        np.testing.assert_allclose(out[0, 0], expected_00, rtol=1e-5)
        # Event (0, 1): indices (11, 10), no observed values -> weights 1.
        np.testing.assert_allclose(out[0, 1], table[11] + table[10], rtol=1e-5)
        # Event (1, 2): padding event (mask False) -> zeros.
        np.testing.assert_allclose(out[1, 2], 0.0)

    def test_padding_index_contributes_nothing(self):
        batch = make_batch()
        # Event (1, 1) has a real event with idx (8, 10); (1, 2) has (0, 0) idx.
        layer = DataEmbeddingLayer(
            n_total_embeddings=12, out_dim=4, static_embedding_mode=StaticEmbeddingMode.DROP
        )
        params = init_layer(layer, batch)
        # Force event_mask True for the padding event: output should still be 0
        # because all its indices are the padding index 0.
        batch2 = batch.replace(event_mask=jnp.asarray([[True, True, True], [True, True, True]]))
        out = np.asarray(layer.apply(params, batch2))
        np.testing.assert_allclose(out[1, 2], 0.0)


class TestSplitEmbedding:
    def test_split_forward_math(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            categorical_embedding_dim=3,
            numerical_embedding_dim=5,
            categorical_weight=1 / 4,
            numerical_weight=3 / 4,
        )
        params = init_layer(layer, batch)
        p = params["params"]
        cat_table = np.asarray(p["categorical_embed_table"])
        num_table = np.asarray(p["numerical_embed_table"])
        cat_kernel = np.asarray(p["cat_proj"]["kernel"])
        cat_bias = np.asarray(p["cat_proj"]["bias"])
        num_kernel = np.asarray(p["num_proj"]["kernel"])
        num_bias = np.asarray(p["num_proj"]["bias"])

        out = np.asarray(layer.apply(params, batch))
        assert out.shape == (2, 3, 4)

        # Event (1, 1): indices (8, 10), values (7, -) with only idx 8 observed.
        cat_embed = (cat_table[8] + cat_table[10]) @ cat_kernel + cat_bias
        num_embed = (num_table[8] * 7.0) @ num_kernel + num_bias
        expected = 0.25 * cat_embed + 0.75 * num_embed
        np.testing.assert_allclose(out[1, 1], expected, rtol=1e-4, atol=1e-5)


class TestBucketing:
    def test_split_by_measurement_indices_shapes_and_masks(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            categorical_embedding_dim=3,
            numerical_embedding_dim=5,
            split_by_measurement_indices=(
                ((4, MeasIndexGroupOptions.CATEGORICAL_ONLY),),
                (5, (4, MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL)),
            ),
        )
        params = init_layer(layer, batch)
        out = np.asarray(layer.apply(params, batch))
        assert out.shape == (2, 3, 2, 4)

        # Group 0 is categorical-only on measurement 4: for event (0, 0) whose
        # measurements are all 4, the numerical part must not contribute.
        p = params["params"]
        cat_table = np.asarray(p["categorical_embed_table"])
        cat_kernel = np.asarray(p["cat_proj"]["kernel"])
        cat_bias = np.asarray(p["cat_proj"]["bias"])
        num_bias = np.asarray(p["num_proj"]["bias"])
        cat_embed = (cat_table[7] + cat_table[8]) @ cat_kernel + cat_bias
        num_embed = num_bias  # no observed numerical values in group 0
        expected = 0.5 * cat_embed + 0.5 * num_embed
        np.testing.assert_allclose(out[0, 0, 0], expected, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("normalize", [False, True])
    def test_joint_grouped_matches_broadcast_formulation(self, normalize):
        """The one-gather grouped JOINT path equals the reference's G-fold
        broadcast formulation (embed the same tokens per group; a token
        weighs its value inside the group's numerical mask and 1 elsewhere
        — data_embedding_layer.py:575-588 + :380-388), including under
        measurement-index normalization."""
        from eventstreamgpt_tpu.ops import embedding_bag, measurement_index_normalization

        batch = make_batch()
        groups = (
            ((4, MeasIndexGroupOptions.CATEGORICAL_ONLY),),
            (5, (4, MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL)),
        )
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            split_by_measurement_indices=groups,
            do_normalize_by_measurement_index=normalize,
        )
        params = init_layer(layer, batch)
        out = np.asarray(layer.apply(params, batch))
        assert out.shape == (2, 3, 2, 4)

        # Reference formulation: broadcast every token to every group and run
        # the ungrouped bag with the group's numerical mask.
        _, num_mask = layer.bind(params)._split_batch_into_measurement_index_buckets(batch)
        table = np.asarray(params["params"]["embed_table"])
        shape = np.asarray(num_mask).shape  # (B, L, G, M)
        indices = jnp.broadcast_to(batch.dynamic_indices[:, :, None, :], shape)
        values = jnp.broadcast_to(batch.dynamic_values[:, :, None, :], shape)
        meas = jnp.broadcast_to(batch.dynamic_measurement_indices[:, :, None, :], shape)
        vmask = jnp.broadcast_to(batch.dynamic_values_mask[:, :, None, :], shape) & num_mask
        w = jnp.where(vmask, values, 1.0)
        if normalize:
            w = w * measurement_index_normalization(meas)
        expected = np.asarray(embedding_bag(jnp.asarray(table), indices, w))
        expected = expected * np.asarray(batch.event_mask)[:, :, None, None]
        np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)

    def test_empty_non_first_group_raises(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            split_by_measurement_indices=((4,), ()),
        )
        with pytest.raises(ValueError, match="Empty measurement index group"):
            init_layer(layer, batch)

    def test_empty_first_group_ok(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            categorical_embedding_dim=3,
            numerical_embedding_dim=5,
            split_by_measurement_indices=((), (4,), (5,)),
        )
        params = init_layer(layer, batch)
        out = np.asarray(layer.apply(params, batch))
        assert out.shape == (2, 3, 3, 4)
        # First group is empty: in split mode both bags get zero weights, so
        # only the projection biases survive (reference semantics — the bags
        # see no unmasked entries but the Linear biases still apply).
        p = params["params"]
        expected = 0.5 * np.asarray(p["cat_proj"]["bias"]) + 0.5 * np.asarray(p["num_proj"]["bias"])
        for b in range(2):
            for s in range(3):
                if bool(batch.event_mask[b, s]):
                    np.testing.assert_allclose(out[b, s, 0], expected, rtol=1e-4, atol=1e-6)
                else:
                    np.testing.assert_allclose(out[b, s, 0], 0.0)


class TestStaticModes:
    def test_sum_all(self):
        batch = make_batch()
        drop_layer = DataEmbeddingLayer(
            n_total_embeddings=12, out_dim=4, static_embedding_mode=StaticEmbeddingMode.DROP
        )
        sum_layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.SUM_ALL,
            static_weight=1 / 3,
            dynamic_weight=2 / 3,
        )
        params = init_layer(drop_layer, batch)
        dyn = np.asarray(drop_layer.apply(params, batch))
        out = np.asarray(sum_layer.apply(params, batch))
        table = np.asarray(params["params"]["embed_table"])
        static_0 = table[1] + table[2] + table[3]
        expected_00 = (2 / 3) * dyn[0, 0] + (1 / 3) * static_0
        np.testing.assert_allclose(out[0, 0], expected_00, rtol=1e-5)
        # Masked events stay zero even with static sum.
        np.testing.assert_allclose(out[1, 2], 0.0)

    def test_normalize_by_measurement_index(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.DROP,
            do_normalize_by_measurement_index=True,
        )
        params = init_layer(layer, batch)
        out = np.asarray(layer.apply(params, batch))
        table = np.asarray(params["params"]["embed_table"])
        # Event (0, 0): both elements measurement 4 -> each weight 1/2, then
        # scaled by observed values (1, 2).
        expected = table[7] * (0.5 * 1.0) + table[8] * (0.5 * 2.0)
        np.testing.assert_allclose(out[0, 0], expected, rtol=1e-5)

    def test_jit_compatible(self):
        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12, out_dim=4, static_embedding_mode=StaticEmbeddingMode.SUM_ALL
        )
        params = init_layer(layer, batch)
        jitted = jax.jit(lambda p, b: layer.apply(p, b))
        out1 = jitted(params, batch)
        out2 = layer.apply(params, batch)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


class TestPlanePath:
    """`ops.embedding_bag` reads its formulation from static shapes (wide
    tables at training sizes: one weighted-multihot plane and two matmuls;
    otherwise the gather). The layer must not be able to tell."""

    @pytest.mark.parametrize(
        "mode_kwargs",
        [{}, {"categorical_embedding_dim": 6, "numerical_embedding_dim": 5}],
        ids=["joint", "split"],
    )
    def test_layer_is_the_same_on_the_plane_as_on_the_gather(self, monkeypatch, mode_kwargs):
        from eventstreamgpt_tpu.ops import tensor_ops

        batch = make_batch()
        layer = DataEmbeddingLayer(
            n_total_embeddings=12,
            out_dim=4,
            static_embedding_mode=StaticEmbeddingMode.SUM_ALL,
            static_weight=1 / 3,
            dynamic_weight=2 / 3,
            do_normalize_by_measurement_index=True,
            **mode_kwargs,
        )
        params = init_layer(layer, batch)
        cot = jnp.asarray(np.random.default_rng(3).normal(size=(2, 3, 4)).astype(np.float32))

        plane_calls = []
        bag_2d = tensor_ops._bag_2d
        monkeypatch.setattr(
            tensor_ops, "_bag_2d", lambda *a: plane_calls.append(1) or bag_2d(*a)
        )

        def run(min_dim):
            monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", min_dim)
            del plane_calls[:]

            def apply(p):
                out = layer.apply(p, batch)
                return (out * cot).sum(), out

            (_, out), grads = jax.value_and_grad(apply, has_aux=True)(params)
            return out, grads, len(plane_calls)

        out_g, grads_g, calls_g = run(10**9)
        out_p, grads_p, calls_p = run(1)
        # Dynamic data once per table; static codes have no values, so one bag.
        assert calls_g == 0 and calls_p == (3 if mode_kwargs else 2)
        assert not np.asarray(out_p[1, 2]).any()  # the masked event stays zero
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_g), rtol=1e-6, atol=1e-6)
        flat_g = jax.tree_util.tree_leaves_with_path(grads_g)
        flat_p = jax.tree_util.tree_leaves(grads_p)
        assert len(flat_g) == len(flat_p) > 0
        for (path, g), p in zip(flat_g, flat_p):
            assert np.abs(np.asarray(g)).sum() > 0, path
            np.testing.assert_allclose(
                np.asarray(p), np.asarray(g), rtol=1e-6, atol=1e-6, err_msg=str(path)
            )

    @pytest.mark.parametrize("site", ["generate", "engine"])
    def test_programs_over_a_mesh_trace_the_plane_inside_kernel_mesh(self, monkeypatch, site):
        """GSPMD cannot partition the plane's Mosaic call: `generate(mesh=...)`
        and an engine with a mesh trace the model inside `kernel_mesh`, as the
        trainers do, and sample what the one-device program samples."""
        from eventstreamgpt_tpu.generation import generate
        from eventstreamgpt_tpu.ops import tensor_ops
        from eventstreamgpt_tpu.parallel import current_kernel_mesh
        from eventstreamgpt_tpu.training.sharding import make_mesh
        from tests.test_spec import build, engine_for, mixed_requests

        monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", 1)
        monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
        traced_under = []
        multihot = tensor_ops.weighted_multihot
        monkeypatch.setattr(
            tensor_ops,
            "weighted_multihot",
            lambda *a: traced_under.append(current_kernel_mesh()) or multihot(*a),
        )
        config, model, params, prompt, _ = build("ci")

        def run(mesh):
            del traced_under[:]
            if site == "generate":
                key = jax.random.PRNGKey(3)
                return [generate(model, params, prompt, config, key, max_new_events=2, mesh=mesh)]
            results = engine_for(model, params, config, prompt, mesh=mesh).run(
                mixed_requests(prompt)
            )
            return [r.batch for r in sorted(results, key=lambda r: r.request_id)]

        want = run(None)
        assert traced_under and all(m is None for m in traced_under)
        mesh = make_mesh(2, 1)
        got = run(mesh)
        assert traced_under and all(m is mesh for m in traced_under)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(
                np.asarray(g.dynamic_indices), np.asarray(w.dynamic_indices)
            )
            np.testing.assert_allclose(
                np.asarray(g.time_delta), np.asarray(w.time_delta), rtol=1e-5, atol=1e-6
            )
