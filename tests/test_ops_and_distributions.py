"""Parity tests for tensor ops and distributions against torch oracles.

The reference implementation delegates these semantics to
``torch``/``torch.distributions``/EmbeddingBag; testing against torch on CPU
pins the rebuild to the exact same numerics (SURVEY.md §4, §7 "hard parts").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from eventstreamgpt_tpu.distributions import (
    Bernoulli,
    Categorical,
    Exponential,
    LogNormalMixture,
    Normal,
)
from eventstreamgpt_tpu.ops import (
    embedding_bag,
    expand_indexed_regression,
    measurement_index_normalization,
    safe_masked_max,
    safe_weighted_avg,
    weighted_loss,
)

RNG = np.random.default_rng(0)


def assert_close(jax_val, torch_val, rtol=1e-3, atol=1e-4):
    np.testing.assert_allclose(np.asarray(jax_val), torch_val.detach().numpy(), rtol=rtol, atol=atol)


class TestTensorOps:
    def test_expand_indexed_regression(self):
        X = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        idx = jnp.asarray([[0, 1, 2], [1, 3, 0]])
        out = expand_indexed_regression(X, idx, 5)
        expected = torch.zeros(2, 5).scatter(
            -1, torch.tensor([[0, 1, 2], [1, 3, 0]]), torch.tensor([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        )
        assert_close(out, expected)

    def test_safe_masked_max_elementwise(self):
        X = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        mask = jnp.asarray([[True, True, False], [False, False, False]])
        np.testing.assert_allclose(np.asarray(safe_masked_max(X, mask)), [2.0, 0.0])

    def test_safe_masked_max_columnwise(self):
        X = jnp.asarray([[[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], [[7.0, 8.0, 9.0], [10.0, 11.0, 12.0]]])
        mask = jnp.asarray([[False, True, False], [True, False, True]])
        np.testing.assert_allclose(np.asarray(safe_masked_max(X, mask)), [[2.0, 5.0], [9.0, 12.0]])

    def test_safe_masked_max_bad_shape(self):
        X = jnp.ones((2, 2, 3))
        with pytest.raises(AssertionError):
            safe_masked_max(X, jnp.ones((2, 2), dtype=bool))

    def test_safe_weighted_avg(self):
        X = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        w = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        avg, denom = safe_weighted_avg(X, w)
        np.testing.assert_allclose(np.asarray(avg), [14 / 6, 77 / 15], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(denom), [6.0, 15.0])
        avg0, denom0 = safe_weighted_avg(X, jnp.asarray([[0.0, 0.0, 0.0], [1.0, 0.0, 0.0]]))
        np.testing.assert_allclose(np.asarray(avg0), [0.0, 4.0])

    def test_weighted_loss(self):
        lpe = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
        em = jnp.asarray([[1.0, 1.0, 1.0], [1.0, 0.0, 0.0]])
        np.testing.assert_allclose(np.asarray(weighted_loss(lpe, em)), 3.0)

    def test_embedding_bag_matches_torch(self):
        n_emb, dim = 20, 8
        table = RNG.normal(size=(n_emb, dim)).astype(np.float32)
        indices = RNG.integers(0, n_emb, size=(6, 5))
        indices[0, :2] = 0
        weights = RNG.normal(size=(6, 5)).astype(np.float32)

        t_bag = torch.nn.EmbeddingBag(n_emb, dim, mode="sum", padding_idx=0)
        with torch.no_grad():
            t_bag.weight.copy_(torch.from_numpy(table))
            t_bag.weight[0] = 0.0
        expected = t_bag(torch.from_numpy(indices), per_sample_weights=torch.from_numpy(weights))

        out = embedding_bag(jnp.asarray(table), jnp.asarray(indices), jnp.asarray(weights))
        assert_close(out, expected, rtol=1e-4, atol=1e-5)

    def test_embedding_bag_no_weights(self):
        table = jnp.asarray(RNG.normal(size=(10, 4)).astype(np.float32))
        indices = jnp.asarray([[1, 2, 0], [0, 0, 0]])
        out = embedding_bag(table, indices)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(table[1] + table[2]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), 0.0)

    def test_embedding_bag_matmul_backward_matches_autodiff(self, monkeypatch):
        """The custom multihot-matmul table gradient == XLA's scatter grad."""
        import jax

        from eventstreamgpt_tpu.ops import tensor_ops
        from eventstreamgpt_tpu.ops.tensor_ops import grouped_embedding_bag

        # The production gate only engages the plane path at wide dims;
        # force it on so the tiny test shape exercises the custom vjp.
        monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", 1)

        n_emb, dim, B, L, M, G = 30, 8, 2, 5, 6, 3
        table = jnp.asarray(RNG.normal(size=(n_emb, dim)).astype(np.float32))
        indices = jnp.asarray(RNG.integers(0, n_emb, size=(B, L, M)))
        weights = jnp.asarray(RNG.normal(size=(B, L, M)).astype(np.float32))
        gw = jnp.asarray(RNG.normal(size=(B, L, G, M)).astype(np.float32))

        def ref_bag(t, w):
            gathered = jnp.take(t, indices, axis=0)
            pm = (indices != 0).astype(t.dtype)
            return jnp.einsum("...md,...m->...d", gathered, w * pm)

        def ref_grouped(t, w):
            gathered = jnp.take(t, indices, axis=0)
            pm = (indices != 0).astype(t.dtype)
            return jnp.einsum("...md,...gm->...gd", gathered, w * pm[..., None, :])

        for fn, ref, w in (
            (lambda t, w: embedding_bag(t, indices, w), ref_bag, weights),
            (lambda t, w: grouped_embedding_bag(t, indices, w), ref_grouped, gw),
        ):
            gt, gw_out = jax.grad(lambda t, w: (fn(t, w) ** 2).sum(), argnums=(0, 1))(
                table, w
            )
            rt, rw = jax.grad(lambda t, w: (ref(t, w) ** 2).sum(), argnums=(0, 1))(table, w)
            np.testing.assert_allclose(np.asarray(gt), np.asarray(rt), rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(gw_out), np.asarray(rw), rtol=1e-4, atol=1e-5)

    def test_embedding_bag_backward_clips_out_of_range_like_scatter(self, monkeypatch):
        """Out-of-range indices: the forward gathers with ``mode="clip"`` (the
        edge row), so the matmul backward must credit that same edge row —
        exactly what XLA's scatter backward of the clipped gather does. An
        unclipped equality-match multihot would silently DROP the cotangent."""
        import jax

        from eventstreamgpt_tpu.ops import tensor_ops
        from eventstreamgpt_tpu.ops.tensor_ops import grouped_embedding_bag

        monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", 1)

        n_emb, dim, B, M, G = 12, 4, 3, 5, 2
        table = jnp.asarray(RNG.normal(size=(n_emb, dim)).astype(np.float32))
        indices = jnp.asarray(RNG.integers(1, n_emb, size=(B, M)))
        # Poison slots with indices past the table end (the slot-clipping
        # path can produce these when config caps slots below the data max).
        indices = indices.at[0, 0].set(n_emb).at[2, 3].set(n_emb + 7)
        weights = jnp.asarray(RNG.normal(size=(B, M)).astype(np.float32))
        gw = jnp.asarray(RNG.normal(size=(B, G, M)).astype(np.float32))

        def ref_bag(t, w):
            gathered = jnp.take(t, indices, axis=0, mode="clip")
            pm = (indices != 0).astype(t.dtype)
            return jnp.einsum("...md,...m->...d", gathered, w * pm)

        def ref_grouped(t, w):
            gathered = jnp.take(t, indices, axis=0, mode="clip")
            pm = (indices != 0).astype(t.dtype)
            return jnp.einsum("...md,...gm->...gd", gathered, w * pm[..., None, :])

        for fn, ref, w in (
            (lambda t, w: embedding_bag(t, indices, w), ref_bag, weights),
            (lambda t, w: grouped_embedding_bag(t, indices, w), ref_grouped, gw),
        ):
            gt = jax.grad(lambda t: (fn(t, w) ** 2).sum())(table)
            rt = jax.grad(lambda t: (ref(t, w) ** 2).sum())(table)
            # The edge row must actually receive credit for the clipped slots.
            assert np.abs(np.asarray(rt[-1])).sum() > 0
            np.testing.assert_allclose(np.asarray(gt), np.asarray(rt), rtol=1e-4, atol=1e-5)

    # name: (N, M, V, D, dtype, edit of the random indices)
    PLANE_CASES = {
        "repeated_index": (12, 6, 40, 8, np.float32, lambda i, v: i.__setitem__((0, slice(0, 3)), 7)),
        "padding_with_weight": (12, 6, 40, 8, np.float32, lambda i, v: i.__setitem__((slice(0, 4), 1), 0)),
        "out_of_range": (12, 6, 40, 8, np.float32, lambda i, v: i.__setitem__(([0, 5], [0, 3]), [v, v + 9])),
        "ragged_vocab": (20, 5, 200, 8, np.float32, lambda i, v: None),
        "ragged_rows": (300, 4, 130, 8, np.float32, lambda i, v: None),
        "bf16": (37, 6, 150, 16, "bfloat16", lambda i, v: i.__setitem__((1, slice(0, 2)), 9)),
    }

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("case", list(PLANE_CASES))
    def test_embedding_bag_plane_path_matches_gather(self, monkeypatch, case, impl):
        """Forward, ``d_table`` and ``d_w`` of the plane path (one
        weighted-multihot plane, two matmuls) == ``take`` + einsum in
        float32, in both formulations of the plane builder."""
        from eventstreamgpt_tpu.ops import tensor_ops

        monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", 1)
        monkeypatch.setenv("ESGPT_PALLAS_IMPL", impl)
        n, m, v, d, dtype, edit = self.PLANE_CASES[case]
        rng = np.random.default_rng(27)
        idx = rng.integers(1, v, size=(n, m))
        edit(idx, v)
        indices = jnp.asarray(idx)
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32)).astype(dtype)
        weights = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32)).astype(dtype)
        cot = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

        def ref(t, w):
            t, w = t.astype(jnp.float32), w.astype(jnp.float32)
            gathered = jnp.take(t, indices, axis=0, mode="clip")
            return jnp.einsum("nmd,nm->nd", gathered, w * (indices != 0))

        def run(fn):
            loss = lambda t, w: (fn(t, w).astype(jnp.float32) * cot).sum()  # noqa: E731
            return (fn(table, weights), *jax.grad(loss, argnums=(0, 1))(table, weights))

        got = run(lambda t, w: embedding_bag(t, indices, w))
        want = run(ref)
        if case == "out_of_range":
            assert np.abs(np.asarray(want[1][-1])).sum() > 0  # the edge row is credited
        for g, r in zip(got, want):
            assert g.dtype == table.dtype and g.shape == r.shape
            r = np.asarray(r, np.float32)
            if dtype == "bfloat16":  # within bf16's step of the float32 answer
                tol = dict(rtol=0, atol=2.0**-7 * np.abs(r).max())
            else:
                tol = dict(rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(g, np.float32), r, **tol)

    @staticmethod
    def _forward_eqns(fn, *args):
        from jax._src.core import jaxprs_in_params

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for sub in jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))

    def test_embedding_bag_path_is_read_from_static_shapes(self, monkeypatch):
        """Narrow tables (every tiny CPU model) keep the gather, to the bit;
        at a benchmark cell's shapes the forward is the plane kernel and two
        matmuls, and holds no gather with an ``(N, M, D)`` result."""
        monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
        sds = jax.ShapeDtypeStruct

        def names_and_shapes(v, d, n, m, dtype):
            eqns = self._forward_eqns(
                embedding_bag,
                sds((v, d), dtype), sds((n, m), jnp.int32), sds((n, m), dtype),
            )
            return (
                {e.primitive.name for e in eqns},
                {(e.primitive.name, tuple(o.aval.shape)) for e in eqns for o in e.outvars},
            )

        names, outs = names_and_shapes(50, 32, 64, 6, jnp.float32)
        assert "gather" in names and "pallas_call" not in names and "while" not in names
        assert ("gather", (64, 6, 32)) in outs

        n, m, v, d = 16384, 24, 4057, 1024
        names, outs = names_and_shapes(v, d, n, m, jnp.bfloat16)
        assert "pallas_call" in names
        assert ("pallas_call", (n, 4096)) in outs
        assert not [o for o in outs if o[0] == "gather" and o[1] == (n, m, d)]
        assert ("dot_general", (n, d)) in outs


    @pytest.mark.parametrize("b", [8, 3], ids=["rows_sharded", "rows_replicated"])
    def test_embedding_bag_plane_path_inside_kernel_mesh(self, monkeypatch, b):
        """GSPMD cannot partition a Mosaic call: under `kernel_mesh` the
        plane kernel runs once per batch shard (`per_batch_shard`), or on
        every device whole where the rows do not divide over the shards (a
        serving engine's replicated prefill group), and the answer is the
        unsharded one."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from eventstreamgpt_tpu.ops import tensor_ops
        from eventstreamgpt_tpu.parallel import kernel_mesh
        from eventstreamgpt_tpu.training.sharding import make_mesh

        monkeypatch.setattr(tensor_ops, "_BAG_PLANE_MIN_DIM", 1)
        monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
        length, m, v, d = 6, 5, 150, 8
        rng = np.random.default_rng(28)
        indices = jnp.asarray(rng.integers(0, v, size=(b, length, m)))
        weights = jnp.asarray(rng.normal(size=(b, length, m)).astype(np.float32))
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        cot = jnp.asarray(rng.normal(size=(b, length, d)).astype(np.float32))

        def step(t, i, w):
            loss = lambda t_: (embedding_bag(t_, i, w) * cot).sum()  # noqa: E731
            return embedding_bag(t, i, w), jax.grad(loss)(t)

        # A function object of its own: jit's trace cache is keyed on the
        # function, and the mesh context is read while tracing.
        want = jax.jit(lambda *a: step(*a))(table, indices, weights)
        mesh = make_mesh(4, 1, 2)
        whole = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P(("data", "fsdp"), None, None)) if b % 8 == 0 else whole
        with kernel_mesh(mesh):
            sharded = jax.jit(step, in_shardings=(whole, rows, rows))
            text = sharded.lower(table, indices, weights).as_text()
            got = sharded(table, indices, weights)
        assert "manual_computation" in text
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6, atol=1e-6)

    def test_measurement_index_normalization(self):
        mi = jnp.asarray([[1, 2, 5, 2, 2], [1, 3, 5, 3, 0]])
        out = measurement_index_normalization(mi)
        expected = [[1 / 3, 1 / 9, 1 / 3, 1 / 9, 1 / 9], [1 / 3, 1 / 6, 1 / 3, 1 / 6, 0.0]]
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)

    def test_take_event_matches_indexing(self):
        """take_event(x, i) == x[:, i] on random floats/ints/bools, traced index."""
        from eventstreamgpt_tpu.ops.tensor_ops import take_event

        x_f = jnp.asarray(RNG.normal(size=(3, 7, 5)).astype(np.float32))
        x_i = jnp.asarray(RNG.integers(-9, 9, size=(3, 7)).astype(np.int32))
        x_b = jnp.asarray(RNG.integers(0, 2, size=(3, 7, 2, 4)).astype(bool))
        for idx in (0, 3, 6):
            traced = jnp.asarray(idx)
            for x in (x_f, x_i, x_b):
                np.testing.assert_array_equal(np.asarray(take_event(x, traced)), np.asarray(x[:, idx]))
                # Python-int fast path too.
                np.testing.assert_array_equal(np.asarray(take_event(x, idx)), np.asarray(x[:, idx]))

    def test_take_event_preserves_nonfinite_at_selected_slot(self):
        from eventstreamgpt_tpu.ops.tensor_ops import take_event

        x = jnp.asarray([[1.0, np.nan, np.inf], [2.0, 5.0, -np.inf]])
        np.testing.assert_array_equal(np.asarray(take_event(x, jnp.asarray(1))), [np.nan, 5.0])
        np.testing.assert_array_equal(np.asarray(take_event(x, jnp.asarray(2))), [np.inf, -np.inf])
        # NaN at an UNSELECTED slot never leaks into the result.
        np.testing.assert_array_equal(np.asarray(take_event(x, jnp.asarray(0))), [1.0, 2.0])

    def test_gather_last_matches_take_along_axis(self):
        from eventstreamgpt_tpu.ops.tensor_ops import gather_last

        plane_f = jnp.asarray(RNG.normal(size=(2, 3, 11)).astype(np.float32))
        plane_b = jnp.asarray(RNG.integers(0, 2, size=(2, 3, 11)).astype(bool))
        idx = jnp.asarray(RNG.integers(0, 11, size=(2, 3, 4)).astype(np.int32))
        for plane in (plane_f, plane_b):
            np.testing.assert_array_equal(
                np.asarray(gather_last(plane, idx)),
                np.asarray(jnp.take_along_axis(plane, idx, axis=-1)),
            )
        # Repeated indices gather the same slot repeatedly (true gather, not sum).
        rep = jnp.asarray([[[5, 5, 5, 5]] * 3] * 2)
        np.testing.assert_array_equal(
            np.asarray(gather_last(plane_f, rep)),
            np.asarray(jnp.take_along_axis(plane_f, rep, axis=-1)),
        )

    def test_gather_last_preserves_nan(self):
        from eventstreamgpt_tpu.ops.tensor_ops import gather_last

        plane = jnp.asarray([[0.0, np.nan, 2.0]])
        out = gather_last(plane, jnp.asarray([[1, 2]]))
        np.testing.assert_array_equal(np.asarray(out), [[np.nan, 2.0]])
        # ...and a NaN at an unselected slot does not poison selected ones.
        out2 = gather_last(plane, jnp.asarray([[0, 2]]))
        np.testing.assert_array_equal(np.asarray(out2), [[0.0, 2.0]])


class TestDistributions:
    def test_categorical_log_prob(self):
        logits = RNG.normal(size=(4, 7)).astype(np.float32)
        values = RNG.integers(0, 7, size=(4,))
        ours = Categorical(logits=jnp.asarray(logits)).log_prob(jnp.asarray(values))
        theirs = torch.distributions.Categorical(logits=torch.from_numpy(logits)).log_prob(
            torch.from_numpy(values)
        )
        assert_close(ours, theirs, rtol=1e-4, atol=1e-4)

    def test_bernoulli_log_prob(self):
        logits = RNG.normal(size=(4, 7)).astype(np.float32)
        values = RNG.integers(0, 2, size=(4, 7)).astype(np.float32)
        ours = Bernoulli(logits=jnp.asarray(logits)).log_prob(jnp.asarray(values))
        theirs = torch.distributions.Bernoulli(logits=torch.from_numpy(logits)).log_prob(
            torch.from_numpy(values)
        )
        assert_close(ours, theirs)

    def test_normal_log_prob(self):
        loc = RNG.normal(size=(5,)).astype(np.float32)
        scale = RNG.uniform(0.5, 2.0, size=(5,)).astype(np.float32)
        values = RNG.normal(size=(5,)).astype(np.float32)
        ours = Normal(loc=jnp.asarray(loc), scale=jnp.asarray(scale)).log_prob(jnp.asarray(values))
        theirs = torch.distributions.Normal(torch.from_numpy(loc), torch.from_numpy(scale)).log_prob(
            torch.from_numpy(values)
        )
        assert_close(ours, theirs)

    def test_exponential_log_prob(self):
        rate = RNG.uniform(0.5, 3.0, size=(6,)).astype(np.float32)
        values = RNG.uniform(0.1, 5.0, size=(6,)).astype(np.float32)
        ours = Exponential(rate=jnp.asarray(rate)).log_prob(jnp.asarray(values))
        theirs = torch.distributions.Exponential(torch.from_numpy(rate)).log_prob(torch.from_numpy(values))
        assert_close(ours, theirs)

    def test_lognormal_mixture_log_prob_vs_torch_composition(self):
        """Checks against the torch composition pytorch_lognormal_mixture uses:
        TransformedDistribution(MixtureSameFamily(Cat, Normal), [Affine, Exp])."""
        K = 3
        locs = RNG.normal(size=(4, K)).astype(np.float32)
        log_scales = RNG.normal(size=(4, K)).astype(np.float32) * 0.3
        log_weights = RNG.normal(size=(4, K)).astype(np.float32)
        mean_log, std_log = 0.7, 1.3
        t = RNG.uniform(0.1, 10.0, size=(4,)).astype(np.float32)

        ours = LogNormalMixture(
            locs=jnp.asarray(locs),
            log_scales=jnp.asarray(log_scales),
            log_weights=jnp.asarray(log_weights),
            mean_log_inter_time=mean_log,
            std_log_inter_time=std_log,
        ).log_prob(jnp.asarray(t))

        gmm = torch.distributions.MixtureSameFamily(
            torch.distributions.Categorical(logits=torch.from_numpy(log_weights)),
            torch.distributions.Normal(
                torch.from_numpy(locs), torch.from_numpy(np.exp(log_scales))
            ),
        )
        theirs = torch.distributions.TransformedDistribution(
            gmm,
            [
                torch.distributions.transforms.AffineTransform(loc=mean_log, scale=std_log),
                torch.distributions.transforms.ExpTransform(),
            ],
        ).log_prob(torch.from_numpy(t))
        assert_close(ours, theirs, rtol=1e-4, atol=1e-5)

    def test_sampling_shapes_and_ranges(self):
        key = jax.random.PRNGKey(0)
        cat = Categorical(logits=jnp.zeros((3, 5)))
        s = cat.sample(key)
        assert s.shape == (3,) and (np.asarray(s) < 5).all()

        exp = Exponential(rate=jnp.ones((3,)))
        s = exp.sample(key)
        assert s.shape == (3,) and (np.asarray(s) > 0).all()

        lnm = LogNormalMixture(
            locs=jnp.zeros((3, 2)), log_scales=jnp.zeros((3, 2)), log_weights=jnp.zeros((3, 2))
        )
        s = lnm.sample(key, (7,))
        assert s.shape == (7, 3) and (np.asarray(s) > 0).all()

    def test_lognormal_mixture_sample_statistics(self):
        key = jax.random.PRNGKey(1)
        lnm = LogNormalMixture(
            locs=jnp.asarray([[0.0, 1.0]]),
            log_scales=jnp.asarray([[-1.0, -1.0]]),
            log_weights=jnp.asarray([[0.0, 0.0]]),
        )
        samples = lnm.sample(key, (20000,))
        np.testing.assert_allclose(np.asarray(samples.mean()), np.asarray(lnm.mean)[0], rtol=0.05)

    def test_distribution_slicing(self):
        """Slicing a distribution pytree replaces the reference's idx_distribution."""
        cat = Categorical(logits=jnp.asarray(RNG.normal(size=(4, 6, 5)).astype(np.float32)))
        sliced = cat[:, -1]
        assert sliced.logits.shape == (4, 5)
        np.testing.assert_allclose(np.asarray(sliced.logits), np.asarray(cat.logits[:, -1]))

        lnm = LogNormalMixture(
            locs=jnp.zeros((4, 6, 3)), log_scales=jnp.zeros((4, 6, 3)), log_weights=jnp.zeros((4, 6, 3)),
            mean_log_inter_time=0.5, std_log_inter_time=2.0,
        )
        sliced = lnm[:, 2:3]
        assert sliced.locs.shape == (4, 1, 3)
        assert sliced.std_log_inter_time == 2.0
