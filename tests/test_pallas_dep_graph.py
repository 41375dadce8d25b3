"""The hand-tiled Pallas dep-graph attention kernel (`ops/pallas_dep_graph.py`).

Parity contract (ISSUE 7): the kernel pins **bit-exact-or-last-ulp** parity
vs the fused-XLA reference (`ops.band_attention._dep_graph_attention_xla`),
forward AND backward. Measured bounds, pinned here: bf16 forward is
bit-exact (the value-dtype rounding absorbs reduction-order freedom); fp32
forward agrees to <= 2 ulp (XLA reduces the softmax denominator / PV sum
with a pairwise tree, the kernel sequentially — same math, different
association); gradients inherit the same last-ulp envelope. Dropout parity
is exact by construction: both impls consume one precomputed keep-mask.

CPU CI runs the kernel in Pallas interpreter mode (the `pallas` marker,
``pallas_heads`` precedent); the compiled kernel is compared with the XLA
formulation on the chip by ``chip_smoke.py``'s kernel phase.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.ops.band_attention import dep_graph_attention
from eventstreamgpt_tpu.ops.impl_select import ENV_VAR, resolve_impl

pytestmark = pytest.mark.pallas

# CPU CI runs the kernel code in interpreter mode; the compiled kernel is
# compiled for the chip in tests/test_chip_compile.py and compared with the
# XLA formulation on the chip by chip_smoke.py.
KERNEL = "pallas_interpret"


@pytest.fixture
def cpu_backend():
    """The backend question is asked when a test runs, never at import:
    these cases pin the off-chip behaviour (tier-1 runs on the CPU). The
    on-chip kernel-vs-XLA comparisons live in ``chip_smoke.py``."""
    if jax.default_backend() == "tpu":
        pytest.skip("pins the non-TPU resolution")


# fp32 "last-ulp" envelope: XLA's pairwise reductions vs the kernel's
# sequential ones reassociate identical math (module docstring).
ULP = dict(rtol=5e-7, atol=5e-7)
GRAD = dict(rtol=3e-5, atol=3e-6)


def _qkv(seed=0, N=12, S=4, H=2, D=8, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(N, S, H, D)).astype(np.float32)).astype(dtype)  # noqa: E731
    return mk(), mk(), mk()


class TestForwardParity:
    @pytest.mark.parametrize("q_offset,window", [(1, None), (0, None), (1, 2), (0, 2)])
    def test_fp32_last_ulp(self, q_offset, window):
        q, k, v = _qkv(seed=q_offset * 10 + (window or 0))
        qq = q[:, q_offset:] if q_offset else q
        ref = dep_graph_attention(qq, k, v, q_offset=q_offset, window=window, impl="xla")
        out = dep_graph_attention(qq, k, v, q_offset=q_offset, window=window, impl=KERNEL)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **ULP)

    def test_bf16_bit_exact(self):
        q, k, v = _qkv(seed=3, dtype=jnp.bfloat16)
        ref = dep_graph_attention(q, k, v, impl="xla")
        out = dep_graph_attention(q, k, v, impl=KERNEL)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(ref, dtype=np.float32), np.asarray(out, dtype=np.float32)
        )

    def test_row_tile_padding_edge(self):
        # N far from the row-tile multiple: padded rows must not leak.
        q, k, v = _qkv(seed=4, N=33)
        ref = dep_graph_attention(q, k, v, impl="xla")
        out = dep_graph_attention(q, k, v, impl=KERNEL)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **ULP)

    def test_causality(self):
        q, k, v = _qkv(seed=5)
        out1 = dep_graph_attention(q[:, 1:], k, v, q_offset=1, impl=KERNEL)
        out2 = dep_graph_attention(
            q[:, 1:], k.at[:, -1].add(5.0), v.at[:, -1].add(5.0), q_offset=1, impl=KERNEL
        )
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-6, atol=1e-6
        )
        assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


class TestBackwardParity:
    def _grads(self, impl, dropout=None, dtype=jnp.float32, seed=6):
        q, k, v = _qkv(seed=seed, dtype=dtype)
        mask, rate = dropout if dropout else (None, 0.0)

        def loss(q_, k_, v_):
            out = dep_graph_attention(
                q_[:, 1:], k_, v_, q_offset=1,
                dropout_mask=mask, dropout_rate=rate, impl=impl,
            )
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def test_fp32_grads_last_ulp(self):
        gx = self._grads("xla")
        gp = self._grads(KERNEL)
        for a, b, name in zip(gx, gp, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), err_msg=f"d{name}", **GRAD
            )

    def test_dropout_fwd_and_bwd_parity(self):
        N, S, H = 12, 4, 2
        mask = jax.random.bernoulli(jax.random.PRNGKey(0), 0.9, (N, S - 1, S, H))
        gx = self._grads("xla", dropout=(mask, 0.1))
        gp = self._grads(KERNEL, dropout=(mask, 0.1))
        for a, b, name in zip(gx, gp, "qkv"):
            # Wider ABSOLUTE envelope than the no-dropout case: the softmax
            # backward's dL = P·(dP − ΣP·dP) cancels near-uniform rows to
            # ~1e-3 magnitudes, where XLA's saved-probs-vs-recomputed-probs
            # reassociation shows up as ~1e-5 absolute noise (still last-ulp
            # relative to the O(1) gradient scale).
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-5, atol=2e-5, err_msg=f"d{name}"
            )

    def test_dropout_applies_at_degenerate_width_one_mask(self):
        """Q=S=H=1 flattens the keep-mask to (N, 1) — the same trailing
        width as the no-dropout dummy operand. The kernel's STATIC
        has_drop flag (not shape inference) must still apply the mask:
        an all-drop mask zeroes the single attention path."""
        q, k, v = _qkv(seed=8, N=4, S=1, H=1, D=8)
        mask = jnp.zeros((4, 1, 1, 1), bool)  # drop everything
        out = dep_graph_attention(
            q, k, v, dropout_mask=mask, dropout_rate=0.5, impl=KERNEL
        )
        ref = dep_graph_attention(
            q, k, v, dropout_mask=mask, dropout_rate=0.5, impl="xla"
        )
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_bf16_grads_close(self):
        gx = self._grads("xla", dtype=jnp.bfloat16)
        gp = self._grads(KERNEL, dtype=jnp.bfloat16)
        for a, b, name in zip(gx, gp, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float32),
                np.asarray(b, dtype=np.float32),
                rtol=3e-2,
                atol=3e-2,
                err_msg=f"d{name}",
            )

    def test_jit_value_and_grad_composes(self):
        q, k, v = _qkv(seed=7)
        f = jax.jit(
            jax.value_and_grad(
                lambda q_: (dep_graph_attention(q_, k, v, impl=KERNEL) ** 2).sum()
            )
        )
        val, grad = f(q)
        assert np.isfinite(float(val)) and grad.shape == q.shape


class TestModelLevelParity:
    """The NA encoder under `dep_graph_attention_impl` — loss + grads."""

    def test_na_loss_and_grads_match_xla_impl(self):
        from eventstreamgpt_tpu.models.na_model import NAPPTForGenerativeSequenceModeling

        from .models.test_na_model import make_batch, make_config

        batch = make_batch()
        model_x = NAPPTForGenerativeSequenceModeling(
            make_config(dep_graph_attention_impl="xla")
        )
        model_p = NAPPTForGenerativeSequenceModeling(
            make_config(dep_graph_attention_impl=KERNEL)
        )
        params = model_x.init(jax.random.PRNGKey(0), batch)
        loss_x, grads_x = jax.value_and_grad(lambda p: model_x.apply(p, batch).loss)(params)
        loss_p, grads_p = jax.value_and_grad(lambda p: model_p.apply(p, batch).loss)(params)
        np.testing.assert_allclose(float(loss_x), float(loss_p), rtol=1e-6)
        for gx, gp in zip(
            jax.tree_util.tree_leaves(grads_x), jax.tree_util.tree_leaves(grads_p)
        ):
            np.testing.assert_allclose(np.asarray(gx), np.asarray(gp), rtol=2e-4, atol=1e-6)


class TestImplSelection:
    def test_auto_off_tpu_is_xla(self, monkeypatch, cpu_backend):
        monkeypatch.delenv(ENV_VAR, raising=False)
        assert resolve_impl(None) == "xla"
        assert resolve_impl("auto") == "xla"

    def test_env_override_retargets_auto(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "pallas_interpret")
        assert resolve_impl(None) == "pallas_interpret"
        # Explicit impl still wins over the env override.
        assert resolve_impl("xla") == "xla"

    def test_env_override_drives_all_ops_consistently(self, monkeypatch):
        """Satellite contract: one override, every Pallas op agrees with its
        XLA fallback — vocab_gather, the dep-graph kernel, fused sampling."""
        from eventstreamgpt_tpu.ops.fused_sampling import fused_categorical
        from eventstreamgpt_tpu.ops.pallas_heads import vocab_gather

        monkeypatch.setenv(ENV_VAR, "pallas_interpret")
        rng = np.random.default_rng(11)
        z = jnp.asarray(rng.normal(size=(2, 3, 300)).astype(np.float32))
        ci = jnp.asarray(rng.integers(0, 300, size=(2, 3, 7)).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(vocab_gather(z, ci)),  # auto -> interpret via env
            np.asarray(vocab_gather(z, ci, impl="xla")),
        )
        q, k, v = _qkv(seed=12)
        np.testing.assert_allclose(
            np.asarray(dep_graph_attention(q, k, v)),  # auto -> interpret
            np.asarray(dep_graph_attention(q, k, v, impl="xla")),
            **ULP,
        )
        logits = jnp.asarray(rng.normal(size=(5, 64)).astype(np.float32))
        key = jax.random.PRNGKey(3)
        np.testing.assert_array_equal(
            np.asarray(fused_categorical(logits, key)),  # auto -> interpret
            np.asarray(fused_categorical(logits, key, impl="xla")),
        )

    def test_unknown_impl_rejected(self):
        q, k, v = _qkv(seed=13)
        with pytest.raises(ValueError, match="dep_graph_attention impl"):
            dep_graph_attention(q, k, v, impl="cuda")

    def test_probs_transform_rejected_on_explicit_kernel(self):
        q, k, v = _qkv(seed=14)
        with pytest.raises(ValueError, match="dropout_mask"):
            dep_graph_attention(q, k, v, probs_transform=lambda p: p, impl=KERNEL)

    def test_probs_transform_degrades_auto_to_xla(self, monkeypatch):
        """The public probs_transform API must keep working under auto
        resolution (including an env retarget onto the kernel) — only an
        EXPLICIT kernel request errors."""
        q, k, v = _qkv(seed=15)
        ref = dep_graph_attention(q, k, v, probs_transform=lambda p: p * 1.0, impl="xla")
        monkeypatch.setenv(ENV_VAR, "pallas_interpret")
        out = dep_graph_attention(q, k, v, probs_transform=lambda p: p * 1.0)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
