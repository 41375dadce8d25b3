"""`ops.pallas_heads.vocab_gather` — the head-stack gather kernel.

The CPU suite pins (a) the XLA fallback used off-TPU, (b) kernel
correctness in Pallas interpreter mode (same kernel code, any backend),
and (c) the layer-level guarantee that the regression head's forward is
identical whichever path runs. Real-chip kernel-vs-XLA parity (forward
exact, gradient to one bf16 rounding) runs in ``chip_smoke.py``'s kernel
phase.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.ops.pallas_heads import vocab_gather

pytestmark = pytest.mark.pallas

@pytest.fixture
def cpu_backend():
    """The backend question is asked when a test runs, never at import:
    these cases pin the off-chip behaviour (tier-1 runs on the CPU). The
    on-chip kernel-vs-XLA comparisons live in ``chip_smoke.py``."""
    if jax.default_backend() == "tpu":
        pytest.skip("pins the non-TPU resolution")



def _case(seed, b=2, l=5, v=300, m=9, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(b, l, v)).astype(np.float32)).astype(dtype)
    ci = jnp.asarray(rng.integers(0, v, size=(b, l, m)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(b, l, m)).astype(np.float32))
    return z, ci, g


class TestInterpretParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_is_exact(self, dtype):
        z, ci, _ = _case(0, dtype=dtype)
        ref = jnp.take_along_axis(z, ci, axis=-1).astype(jnp.float32)
        out = vocab_gather(z, ci, impl="pallas_interpret")
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_forward_exact_at_aligned_vocab_width(self):
        z, ci, _ = _case(1, v=512, m=16, dtype=jnp.bfloat16)
        ref = jnp.take_along_axis(z, ci, axis=-1).astype(jnp.float32)
        out = vocab_gather(z, ci, impl="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_backward_matches_xla_scatter(self):
        z, ci, g = _case(2)
        gk = jax.grad(lambda zz: (vocab_gather(zz, ci, impl="pallas_interpret") * g).sum())(z)
        gx = jax.grad(lambda zz: (vocab_gather(zz, ci, impl="xla") * g).sum())(z)
        assert gk.dtype == z.dtype
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gx), rtol=1e-6)

    def test_backward_sums_duplicate_indices(self):
        z, ci, g = _case(3)
        ci = ci.at[..., 1].set(ci[..., 0])  # force duplicates per row
        gk = jax.grad(lambda zz: (vocab_gather(zz, ci, impl="pallas_interpret") * g).sum())(z)
        gx = jax.grad(lambda zz: (vocab_gather(zz, ci, impl="xla") * g).sum())(z)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gx), rtol=1e-6)

    def test_regression_layer_forward_identical_across_paths(self):
        """The head's concat-gather-split wiring: mean/std from the kernel
        path must match the per-parameter take_along_axis formulation."""
        from eventstreamgpt_tpu.models.generative_layers import (
            GaussianIndexedRegressionLayer,
            _elu_plus_one,
        )

        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.normal(size=(2, 6, 16)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 37, size=(2, 6, 5)).astype(np.int32))
        layer = GaussianIndexedRegressionLayer(n_regression_targets=37)
        params = layer.init(jax.random.PRNGKey(0), x, idx)
        dist = layer.apply(params, x, idx)
        # Reference formulation straight from the projection params.
        kernel = params["params"]["proj"]["kernel"]
        bias = params["params"]["proj"]["bias"]
        z_ref = x @ kernel + bias
        mean_ref = jnp.take_along_axis(z_ref, 2 * idx, axis=-1).astype(jnp.float32)
        std_ref = _elu_plus_one(
            jnp.take_along_axis(z_ref, 2 * idx + 1, axis=-1).astype(jnp.float32)
        )
        np.testing.assert_allclose(np.asarray(dist.loc), np.asarray(mean_ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(dist.scale), np.asarray(std_ref), rtol=1e-6)

    def test_second_order_structure_not_required(self):
        # The op is used in first-order training only; jit + value_and_grad
        # must compose.
        z, ci, g = _case(4)
        f = jax.jit(
            jax.value_and_grad(lambda zz: (vocab_gather(zz, ci, impl="pallas_interpret") * g).sum())
        )
        val, grad = f(z)
        assert np.isfinite(float(val)) and grad.shape == z.shape


class TestDispatch:
    def test_auto_off_tpu_is_xla(self, cpu_backend):
        z, ci, _ = _case(5)
        np.testing.assert_array_equal(
            np.asarray(vocab_gather(z, ci)),
            np.asarray(jnp.take_along_axis(z, ci, axis=-1).astype(jnp.float32)),
        )

    def test_unknown_impl_rejected(self):
        z, ci, _ = _case(6)
        with pytest.raises(ValueError, match="vocab_gather impl"):
            vocab_gather(z, ci, impl="cuda")
