"""Pallas flash-attention backend tests.

``attention_implementation="pallas_flash"`` routes global-attention layers
through the fused TPU flash-attention kernel (causal + segment masking, no
(L, L) logits in HBM) with a guarded fallback to the einsum path. The CI
suite runs on virtual CPU devices where the kernel cannot execute, so these
tests pin the *fallback* behavior: the config is accepted, and results are
bitwise the einsum path's. Kernel-vs-einsum numerical parity on the real
chip (flash global layers, splash local layers on packed segments) is
exercised by ``chip_smoke.py``'s kernel phase.
"""

import jax
import numpy as np
import pytest

from __graft_entry__ import _make_model_and_batch
from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

@pytest.fixture
def cpu_backend():
    """The backend question is asked when a test runs, never at import:
    these cases pin the off-chip behaviour (tier-1 runs on the CPU). The
    on-chip kernel-vs-XLA comparisons live in ``chip_smoke.py``."""
    if jax.default_backend() == "tpu":
        pytest.skip("pins the non-TPU resolution")



def make_pallas_twin(model):
    cfg = StructuredTransformerConfig.from_dict(
        {**model.config.to_dict(), "attention_implementation": "pallas_flash", "attention_dropout": 0.0}
    )
    return CIPPTForGenerativeSequenceModeling(cfg)


class TestConfig:
    def test_field_round_trips(self):
        cfg = StructuredTransformerConfig(attention_implementation="pallas_flash")
        assert StructuredTransformerConfig.from_dict(cfg.to_dict()).attention_implementation == (
            "pallas_flash"
        )

    def test_invalid_value_rejected(self):
        with pytest.raises(ValueError, match="attention_implementation"):
            StructuredTransformerConfig(attention_implementation="flash3")


class TestFallback:
    def test_cpu_fallback_is_einsum_exact(self, cpu_backend):
        """Off-TPU (or any unmet precondition) the pallas config's *kernel*
        layers must produce exactly the einsum path's numbers — same trace,
        same params. Global-only stack: narrow-window local layers ride the
        backend-independent band einsum instead (tested for parity below)."""
        model, batch = _make_model_and_batch(batch_size=2, seq_len=128, n_data=4, hidden=32, vocab=32)
        cfg_global = StructuredTransformerConfig.from_dict(
            {**model.config.to_dict(), "seq_attention_types": "global", "attention_dropout": 0.0}
        )
        einsum_model = CIPPTForGenerativeSequenceModeling(cfg_global)
        pallas_model = CIPPTForGenerativeSequenceModeling(
            StructuredTransformerConfig.from_dict(
                {**cfg_global.to_dict(), "attention_implementation": "pallas_flash"}
            )
        )
        params = einsum_model.init(jax.random.PRNGKey(0), batch)
        out_e = einsum_model.apply(params, batch)
        out_p = pallas_model.apply(params, batch)
        np.testing.assert_array_equal(np.asarray(out_p.loss), np.asarray(out_e.loss))

    def test_band_local_matches_einsum_model(self):
        """Default ["local", "global"] stack under pallas_flash: the local
        layer rides the chunked band einsum on every backend; the model's
        loss and grads must match the full-mask einsum path to fp32 noise."""
        model, batch = _make_model_and_batch(batch_size=2, seq_len=128, n_data=4, hidden=32, vocab=32)
        pallas_model = make_pallas_twin(model)
        params = model.init(jax.random.PRNGKey(0), batch)
        out_e = model.apply(params, batch)
        out_p = pallas_model.apply(params, batch)
        np.testing.assert_allclose(float(out_p.loss), float(out_e.loss), rtol=1e-5)
        ge = jax.grad(lambda p: model.apply(p, batch).loss)(params)
        gp = jax.grad(lambda p: pallas_model.apply(p, batch).loss)(params)
        for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-3, atol=1e-5)

    def test_band_packed_segments_and_padding(self):
        """Band path on a packed batch (segment ids + padding tail) matches
        the einsum sliding-window path, including segment isolation."""
        model, batch = _make_model_and_batch(batch_size=2, seq_len=128, n_data=4, hidden=32, vocab=32)
        cfg_local = StructuredTransformerConfig.from_dict(
            {
                **model.config.to_dict(),
                "seq_attention_types": "local",
                "seq_window_size": 32,
                "attention_dropout": 0.0,
            }
        )
        einsum_model = CIPPTForGenerativeSequenceModeling(cfg_local)
        pallas_model = CIPPTForGenerativeSequenceModeling(
            StructuredTransformerConfig.from_dict(
                {**cfg_local.to_dict(), "attention_implementation": "pallas_flash"}
            )
        )
        seg = np.zeros((2, 128), np.int64)
        seg[:, 50:] = 1
        event_mask = np.asarray(batch.event_mask).copy()
        event_mask[:, 110:] = False
        batch = batch.replace(
            segment_ids=jax.numpy.asarray(seg), event_mask=jax.numpy.asarray(event_mask)
        )
        params = einsum_model.init(jax.random.PRNGKey(0), batch)
        out_e = einsum_model.apply(params, batch)
        out_p = pallas_model.apply(params, batch)
        np.testing.assert_allclose(float(out_p.loss), float(out_e.loss), rtol=1e-5)
        ge = jax.grad(lambda p: einsum_model.apply(p, batch).loss)(params)
        gp = jax.grad(lambda p: pallas_model.apply(p, batch).loss)(params)
        for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-3, atol=1e-5)

    def test_band_op_matches_reference_windows(self):
        """Direct op-level parity across window/length combinations."""
        from eventstreamgpt_tpu.ops.band_attention import band_local_attention

        rng = np.random.default_rng(0)
        for (B, H, L, D, W) in [(2, 2, 128, 16, 32), (1, 3, 96, 8, 16), (2, 1, 64, 32, 64)]:
            q = jax.numpy.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
            k = jax.numpy.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
            v = jax.numpy.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
            seg_np = np.zeros((B, L), np.int32)
            seg_np[:, L // 3 :] = 1
            seg_np[:, -7:] = -1  # padding convention
            seg = jax.numpy.asarray(seg_np)
            out = band_local_attention(q, k, v, seg, W)

            # Any chunk size >= W that divides L is result-identical: the
            # chunk is a pure performance knob (fp32 here, so exact).
            for C in {W, 2 * W, L}:
                if L % C == 0:
                    out_c = band_local_attention(q, k, v, seg, W, chunk_size=C)
                    np.testing.assert_allclose(
                        np.asarray(out_c), np.asarray(out), rtol=1e-6, atol=1e-6
                    )

            pos = np.arange(L)
            m = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - W)
            m = m[None, None] & (seg_np[:, None, :, None] == seg_np[:, None, None, :]).transpose(0, 1, 3, 2)
            logits = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k))
            logits = np.where(m, logits, np.finfo(np.float32).min)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            ref = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
            np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_param_tree_identical_across_backends(self):
        model, batch = _make_model_and_batch(batch_size=2, seq_len=128, n_data=4, hidden=32, vocab=32)
        pallas_model = make_pallas_twin(model)
        p_e = model.init(jax.random.PRNGKey(0), batch)
        p_p = pallas_model.init(jax.random.PRNGKey(0), batch)
        assert jax.tree_util.tree_structure(p_e) == jax.tree_util.tree_structure(p_p)
