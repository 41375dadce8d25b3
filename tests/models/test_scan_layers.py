"""Scan-over-layers parity suite (r10 scale-up round).

The contract `config.scan_layers=True` must honor (models/transformer.py):

* **Forward bit-equivalence**: with parameters migrated from the unrolled
  layout (`stack_layer_params` — a pure relayout), the scanned encoder's
  loss is BITWISE equal to the unrolled encoder's, CI and NA, shallow
  (one scan group) and deep (multiple groups), with and without remat.
* **Gradient envelope**: grads agree to the documented last-ulp envelope —
  XLA compiles the scan body as its own computation, so reduction
  reassociation produces ≲1e-5 absolute differences on cancellation-
  dominated near-zero elements while the loss itself stays bit-exact.
  (Dropout streams are the one *designed* divergence: `nn.scan` splits the
  rng per step instead of folding per-named-scope, so training-mode draws
  differ between layouts — same distribution, different stream.)
* **Cached decode parity**: generation (the per-layer KV caches threaded
  through the scan as stacked inputs/outputs) reproduces the unrolled
  path — bit-exact for CI, structure/integer-exact for NA.
* **Migration**: `stack_layer_params` ∘ `unstack_layer_params` is the
  identity, and the stacked tree is structurally identical to a fresh
  `scan_layers=True` init — an unrolled checkpoint restores into a
  scanned model and vice versa.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.transformer import (
    scan_period,
    stack_layer_params,
    unstack_layer_params,
)
from eventstreamgpt_tpu.training import build_model

from __graft_entry__ import _make_model_and_batch


def _deepen(model, num_hidden_layers, **overrides):
    cfg = StructuredTransformerConfig.from_dict(
        {**model.config.to_dict(), "num_hidden_layers": num_hidden_layers, **overrides}
    )
    return build_model(cfg)


def _scan_twin(model):
    """The scanned model sharing ``model``'s architecture."""
    cfg = StructuredTransformerConfig.from_dict(
        {**model.config.to_dict(), "scan_layers": True}
    )
    return build_model(cfg)


def _flat(tree):
    return np.concatenate(
        [np.asarray(x).ravel() for x in jax.tree_util.tree_leaves(tree)]
    )


class TestScanPeriod:
    def test_alternating_default_stack(self):
        cfg = StructuredTransformerConfig(num_hidden_layers=4)
        # default seq_attention_types ["local", "global"] → period 2
        assert scan_period(cfg) == (2, 2)

    def test_uniform_stack_scans_per_layer(self):
        cfg = StructuredTransformerConfig(num_hidden_layers=4, seq_attention_types="global")
        assert scan_period(cfg) == (1, 4)

    def test_aperiodic_stack_degenerates_to_one_group(self):
        cfg = StructuredTransformerConfig(
            num_hidden_layers=3,
            seq_attention_types=[(["local"], 2), (["global"], 1)],
        )
        assert scan_period(cfg) == (3, 1)


class TestMigration:
    @pytest.mark.parametrize("na", [False, True], ids=["ci", "na"])
    def test_round_trip_and_structure(self, na):
        model, batch = _make_model_and_batch(batch_size=2, seq_len=8, na=na)
        model = _deepen(model, 4)
        params = model.init(jax.random.PRNGKey(0), batch)
        stacked = stack_layer_params(params, model.config)
        # Structure matches a fresh scan_layers init (checkpoint-compatible).
        scan_model = _scan_twin(model)
        ref = jax.eval_shape(scan_model.init, jax.random.PRNGKey(0), batch)
        assert jax.tree_util.tree_structure(ref) == jax.tree_util.tree_structure(stacked)
        for a, b in zip(
            jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(stacked)
        ):
            assert a.shape == b.shape and a.dtype == b.dtype
        # Round trip is the identity, bitwise.
        back = unstack_layer_params(stacked, model.config)
        assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestScanForwardParity:
    @pytest.mark.parametrize("na", [False, True], ids=["ci", "na"])
    @pytest.mark.parametrize("depth", [2, 4], ids=["1group", "2groups"])
    def test_loss_bitwise_and_grads_within_envelope(self, na, depth):
        model, batch = _make_model_and_batch(batch_size=2, seq_len=16, na=na)
        model = _deepen(model, depth)
        scan_model = _scan_twin(model)
        params = model.init(jax.random.PRNGKey(0), batch)
        sparams = stack_layer_params(params, model.config)

        loss_u = model.apply(params, batch).loss
        loss_s = scan_model.apply(sparams, batch).loss
        # Two separately compiled programs: equal within one float32 ulp.
        loss_u, loss_s = np.float32(loss_u), np.float32(loss_s)
        assert abs(loss_u - loss_s) <= np.spacing(abs(loss_u))

        gu = jax.grad(lambda p: model.apply(p, batch).loss)(params)
        gs = unstack_layer_params(
            jax.grad(lambda p: scan_model.apply(p, batch).loss)(sparams), model.config
        )
        fu, fs = _flat(gu), _flat(gs)
        # The documented envelope: the scan body compiles separately, so
        # reduction reassociation moves cancellation-dominated elements by
        # ≲1e-5 absolute; scale-relative error stays at fp32 ulp level.
        scale = float(np.max(np.abs(fu)))
        np.testing.assert_allclose(fu, fs, rtol=1e-4, atol=1e-5 * max(scale, 1.0))

    @pytest.mark.parametrize(
        "policy", ["block", "dots_no_batch", "save_attention"]
    )
    def test_remat_policies_keep_parity(self, policy):
        """Per-layer remat composes with the scan (nn.remat inside nn.scan)
        without touching numerics: the scanned loss under every policy is
        bitwise the no-remat scanned loss."""
        model, batch = _make_model_and_batch(batch_size=2, seq_len=16)
        model = _deepen(model, 4)
        params = stack_layer_params(
            model.init(jax.random.PRNGKey(0), batch), model.config
        )
        base = _scan_twin(model).apply(params, batch).loss
        rematted = build_model(
            StructuredTransformerConfig.from_dict(
                {
                    **model.config.to_dict(),
                    "scan_layers": True,
                    "gradient_checkpointing": policy,
                }
            )
        )
        loss_p = rematted.apply(params, batch).loss
        assert np.asarray(base).tobytes() == np.asarray(loss_p).tobytes()
        g = jax.grad(lambda p: rematted.apply(p, batch).loss)(params)
        assert all(
            np.isfinite(np.asarray(x)).all() for x in jax.tree_util.tree_leaves(g)
        )

    def test_output_hidden_states_parity(self):
        model, batch = _make_model_and_batch(batch_size=2, seq_len=8)
        model = _deepen(model, 4)
        scan_model = _scan_twin(model)
        params = model.init(jax.random.PRNGKey(0), batch)
        sparams = stack_layer_params(params, model.config)
        out_u = model.apply(params, batch, output_hidden_states=True)
        out_s = scan_model.apply(sparams, batch, output_hidden_states=True)
        assert len(out_u.hidden_states) == len(out_s.hidden_states)
        # Collecting per-layer ys changes the compiled program, so the
        # intermediate hiddens carry last-ulp reassociation noise; the
        # final (ln_f) state and the loss stay bit-exact (tested above).
        for a, b in zip(out_u.hidden_states, out_s.hidden_states):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
            )

    def test_output_attentions_raises_under_scan(self):
        model, batch = _make_model_and_batch(batch_size=2, seq_len=8)
        scan_model = _scan_twin(model)
        params = stack_layer_params(
            model.init(jax.random.PRNGKey(0), batch), model.config
        )
        with pytest.raises(NotImplementedError, match="output_attentions"):
            scan_model.apply(params, batch, output_attentions=True)

    def test_dropout_runs_under_scan(self):
        """Training-mode dropout traces and runs (split_rngs per scan step);
        the draws legitimately differ from the unrolled stream — only
        finiteness and determinism per rng are pinned."""
        model, batch = _make_model_and_batch(batch_size=2, seq_len=8)
        scan_model = _scan_twin(model)
        params = stack_layer_params(
            model.init(jax.random.PRNGKey(0), batch), model.config
        )
        l1 = scan_model.apply(params, batch, rngs={"dropout": jax.random.PRNGKey(3)})
        l2 = scan_model.apply(params, batch, rngs={"dropout": jax.random.PRNGKey(3)})
        assert np.asarray(l1.loss).tobytes() == np.asarray(l2.loss).tobytes()
        assert np.isfinite(float(l1.loss))


@pytest.mark.slow
class TestScanGenerationParity:
    """Cached decode through the scan (stacked KVCache xs/ys): generation and
    the serving engine reproduce the unrolled layout's outputs."""

    def test_ci_generate_structure_exact(self):
        """The one-program cached generate through the scanned stack:
        sampled event structure and integer content are exact vs the
        unrolled layout; floats at near-ulp tolerance (the scanned fused
        generation program reassociates identical math differently at tiny
        CPU widths — the same envelope the engine's NA parity documents)."""
        from .. import test_generation as tg
        from eventstreamgpt_tpu.generation import generate
        from eventstreamgpt_tpu.models.ci_model import (
            CIPPTForGenerativeSequenceModeling,
        )

        config = tg.ci_config()
        prompt = tg.make_prompt(B=2, L=3)
        model = CIPPTForGenerativeSequenceModeling(config)
        params = model.init(jax.random.PRNGKey(0), prompt)
        scan_cfg = StructuredTransformerConfig.from_dict(
            {**config.to_dict(), "scan_layers": True}
        )
        scan_model = CIPPTForGenerativeSequenceModeling(scan_cfg)
        sparams = stack_layer_params(params, config)
        key = jax.random.PRNGKey(7)
        o1 = generate(model, params, prompt, config, key, max_new_events=4, use_cache=True)
        o2 = generate(
            scan_model, sparams, prompt, scan_cfg, key, max_new_events=4, use_cache=True
        )
        for f in (
            "event_mask",
            "dynamic_indices",
            "dynamic_measurement_indices",
            "dynamic_values_mask",
        ):
            np.testing.assert_array_equal(
                np.asarray(getattr(o1, f)), np.asarray(getattr(o2, f))
            )
        for f in ("time_delta", "dynamic_values"):
            np.testing.assert_allclose(
                np.asarray(getattr(o1, f)),
                np.asarray(getattr(o2, f)),
                rtol=1e-5,
                atol=1e-6,
            )

    def test_na_generate_structure_exact(self):
        """NA cached decode threads BOTH cache levels (seq + dep-graph)
        through scan carries, including the target-0 cache reset and the
        per-level decode: event structure and integer content must be exact
        vs the unrolled layout; floats at near-ulp tolerance (the scanned
        program fuses differently at tiny CPU widths — the same envelope
        the engine's NA parity test documents)."""
        from .. import test_generation as tg
        from eventstreamgpt_tpu.generation import generate
        from eventstreamgpt_tpu.models.na_model import (
            NAPPTForGenerativeSequenceModeling,
        )

        config = tg.na_config()
        prompt = tg.make_prompt(B=2, L=3)
        model = NAPPTForGenerativeSequenceModeling(config)
        params = model.init(jax.random.PRNGKey(0), prompt)
        scan_cfg = StructuredTransformerConfig.from_dict(
            {**config.to_dict(), "scan_layers": True}
        )
        scan_model = NAPPTForGenerativeSequenceModeling(scan_cfg)
        sparams = stack_layer_params(params, config)
        key = jax.random.PRNGKey(7)
        o1 = generate(model, params, prompt, config, key, max_new_events=3, use_cache=True)
        o2 = generate(
            scan_model, sparams, prompt, scan_cfg, key, max_new_events=3, use_cache=True
        )
        for f in (
            "event_mask",
            "dynamic_indices",
            "dynamic_measurement_indices",
            "dynamic_values_mask",
        ):
            np.testing.assert_array_equal(
                np.asarray(getattr(o1, f)), np.asarray(getattr(o2, f))
            )
        for f in ("time_delta", "dynamic_values"):
            np.testing.assert_allclose(
                np.asarray(getattr(o1, f)),
                np.asarray(getattr(o2, f)),
                rtol=1e-5,
                atol=1e-6,
            )

    def test_engine_serves_scanned_checkpoint_bitwise(self):
        """The continuous-batching engine drives a scan_layers model without
        modification (the vector-cursor KV caches ride the scan like the
        scalar ones) and reproduces the unrolled engine's results bitwise
        for CI requests."""
        from .. import test_engine as te

        config, model, params, prompt = te.build("ci")
        scan_cfg = StructuredTransformerConfig.from_dict(
            {**config.to_dict(), "scan_layers": True}
        )
        from eventstreamgpt_tpu.models.ci_model import (
            CIPPTForGenerativeSequenceModeling,
        )

        scan_model = CIPPTForGenerativeSequenceModeling(scan_cfg)
        sparams = stack_layer_params(params, config)
        reqs = te.mixed_requests(prompt)
        res_u = te.engine_for(model, params, config, prompt).run(
            [r for r in reqs]
        )
        res_s = te.engine_for(scan_model, sparams, scan_cfg, prompt).run(
            te.mixed_requests(prompt)
        )
        assert len(res_u) == len(res_s)
        for a, b in zip(res_u, res_s):
            assert a.n_generated == b.n_generated
            for fa, fb in zip(
                jax.tree_util.tree_leaves(a.batch), jax.tree_util.tree_leaves(b.batch)
            ):
                np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
