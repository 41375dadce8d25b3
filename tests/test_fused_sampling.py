"""`ops.fused_sampling.fused_categorical` — the engine's fused decode tail.

The load-bearing contract: with no filters the fused draw reproduces
``jax.random.categorical`` **bit-exactly** on every impl (same gumbel
call, same add, same first-max tie-break) — that is what lets the serving
engine default to the fused tail without breaking its bit-exact
``generate()`` parity pin. Filters are tie-inclusive and shared verbatim
across impls, so impl agreement under top-k/top-p is exact by
construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.ops.fused_sampling import fused_categorical, topk_topp_mask

pytestmark = pytest.mark.pallas

# CPU CI runs the kernel code in interpreter mode; the compiled kernel is
# compared with the XLA tail on the chip by chip_smoke.py.
KERNEL = "pallas_interpret"
IMPLS = ("xla", KERNEL)


def _logits(seed=0, rows=16, V=300, scale=3.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, V)).astype(np.float32)) * scale


class TestUnfilteredBitExactness:
    @pytest.mark.parametrize("impl", IMPLS)
    def test_matches_jax_random_categorical(self, impl):
        logits = _logits()
        key = jax.random.PRNGKey(7)
        ref = jax.random.categorical(key, logits, axis=-1)
        out = fused_categorical(logits, key, impl=impl)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_per_row_keys_under_vmap(self, impl):
        """The engine's pattern: vmapped draws with per-slot key chains."""
        logits = _logits(seed=1)
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
            jnp.arange(logits.shape[0])
        )
        ref = jax.vmap(lambda l, k: jax.random.categorical(k, l))(logits, keys)
        out = jax.vmap(lambda l, k: fused_categorical(l, k, impl=impl))(logits, keys)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_bf16_logits_match_multi_seed(self, impl):
        """bf16 is where upcast-then-add would silently diverge (near-tied
        tokens order differently than the reference's bf16 add): sweep
        seeds so a single lucky draw can't green-light the contract."""
        for seed in range(24):
            logits = _logits(seed=seed, rows=8).astype(jnp.bfloat16)
            key = jax.random.PRNGKey(100 + seed)
            ref = jax.random.categorical(key, logits, axis=-1)
            np.testing.assert_array_equal(
                np.asarray(ref),
                np.asarray(fused_categorical(logits, key, impl=impl)),
                err_msg=f"seed {seed}",
            )

    def test_inside_jitted_scan(self):
        """The decode-loop context: jit(scan(vmap(draw)))."""
        logits = _logits(seed=3, rows=4)
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(1), i))(
            jnp.arange(4)
        )
        ref = jax.vmap(lambda l, k: jax.random.categorical(k, l))(logits, keys)

        def step(c, _):
            out = jax.vmap(lambda l, k: fused_categorical(l, k, impl=KERNEL))(logits, keys)
            return c, out

        _, outs = jax.jit(lambda: jax.lax.scan(step, 0, None, length=2))()
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(outs[1]), np.asarray(ref))


class TestFilters:
    @pytest.mark.parametrize("top_k,top_p", [(5, None), (None, 0.9), (8, 0.5), (1, None)])
    def test_impls_agree(self, top_k, top_p):
        logits = _logits(seed=4)
        key = jax.random.PRNGKey(11)
        outs = [
            np.asarray(fused_categorical(logits, key, top_k=top_k, top_p=top_p, impl=i))
            for i in IMPLS
        ]
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_top_k_1_is_argmax(self):
        logits = _logits(seed=5)
        out = fused_categorical(logits, jax.random.PRNGKey(0), top_k=1, impl=KERNEL)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.argmax(logits, -1)))

    def test_samples_stay_inside_the_filter_set(self):
        logits = _logits(seed=6, rows=64)
        keep_k = np.asarray(topk_topp_mask(logits, top_k=5))
        keep_p = np.asarray(topk_topp_mask(logits, top_p=0.6))
        for i, key in enumerate(jax.random.split(jax.random.PRNGKey(2), 8)):
            sk = np.asarray(fused_categorical(logits, key, top_k=5, impl=KERNEL))
            sp = np.asarray(fused_categorical(logits, key, top_p=0.6, impl=KERNEL))
            rows = np.arange(logits.shape[0])
            assert keep_k[rows, sk].all(), f"top-k escape at draw {i}"
            assert keep_p[rows, sp].all(), f"top-p escape at draw {i}"

    def test_mask_is_tie_inclusive(self):
        logits = jnp.asarray([[1.0, 3.0, 3.0, 0.0, -1.0]])
        keep = np.asarray(topk_topp_mask(logits, top_k=1))[0]
        assert keep.tolist() == [False, True, True, False, False]

    def test_top_p_keeps_the_crossing_token(self):
        # probs ~ [0.5, 0.3, 0.2]: exclusive prefix at token 1 is 0.5 < 0.6,
        # so the nucleus at p=0.6 is {0, 1} even though 0.5+0.3 > 0.6.
        logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.2]]))
        keep = np.asarray(topk_topp_mask(logits, top_p=0.6))[0]
        assert keep.tolist() == [True, True, False]

    def test_bad_filter_values_rejected(self):
        logits = _logits(seed=7, rows=1)
        with pytest.raises(ValueError, match="top_k"):
            fused_categorical(logits, jax.random.PRNGKey(0), top_k=0)
        with pytest.raises(ValueError, match="top_p"):
            fused_categorical(logits, jax.random.PRNGKey(0), top_p=0.0)


class TestActiveMerge:
    def test_inactive_rows_freeze_to_fill(self):
        logits = _logits(seed=8)
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(4), i))(
            jnp.arange(logits.shape[0])
        )
        active = jnp.arange(logits.shape[0]) % 2 == 0
        ref = jax.vmap(lambda l, k: jax.random.categorical(k, l))(logits, keys)
        out = jax.vmap(
            lambda l, k, a: fused_categorical(l, k, active=a, fill=-1, impl=KERNEL)
        )(logits, keys, active)
        np.testing.assert_array_equal(
            np.asarray(out), np.where(np.asarray(active), np.asarray(ref), -1)
        )


class TestSamplePredictionsHook:
    def test_fused_tail_is_bit_exact_through_sample_predictions(self):
        """The engine's swap point: `sample_predictions` with the fused
        sampler must reproduce the reference multi-op tail bit-exactly."""
        import functools

        from eventstreamgpt_tpu.distributions import Bernoulli, Categorical
        from eventstreamgpt_tpu.generation.sampling import sample_predictions
        from eventstreamgpt_tpu.models.model_output import (
            GenerativeSequenceModelPredictions,
        )

        rng = np.random.default_rng(9)
        B, V = 6, 40
        preds = GenerativeSequenceModelPredictions(
            classification={
                "event_type": (None, Categorical(jnp.asarray(rng.normal(size=(B, V)).astype(np.float32)))),
                "obs_cls": (
                    Bernoulli(jnp.asarray(rng.normal(size=(B,)).astype(np.float32))),
                    Categorical(jnp.asarray(rng.normal(size=(B, V)).astype(np.float32))),
                ),
            }
        )
        em = jnp.ones((B,), bool)
        key = jax.random.PRNGKey(21)
        ref = sample_predictions(preds, em, key)
        for impl in IMPLS:
            sampler = functools.partial(fused_categorical, impl=impl)
            out = sample_predictions(preds, em, key, categorical_sampler=sampler)
            for name in ref.classification:
                np.testing.assert_array_equal(
                    np.asarray(ref.classification[name]),
                    np.asarray(out.classification[name]),
                    err_msg=f"{impl}:{name}",
                )
