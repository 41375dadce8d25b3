"""Long-context packed-attention tests (SURVEY §5.7; BASELINE config 5).

The gold invariant: a packed row holding several subjects produces, at each
subject's positions, exactly the encodings (and TTE labels/masks) that the
same subjects produce in separate padded rows — segment masking must make
packing invisible to the model's math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.data.types import EventStreamBatch
from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
from eventstreamgpt_tpu.models.na_model import NAPPTForGenerativeSequenceModeling
from eventstreamgpt_tpu.models.transformer import (
    ConditionallyIndependentPointProcessTransformer,
    time_from_deltas,
)

from . import _programs as programs

VOCAB = 32


def make_config(**kwargs):
    defaults = dict(
        vocab_sizes_by_measurement={"event_type": VOCAB // 2, "lab": VOCAB // 2 - 1},
        vocab_offsets_by_measurement={"event_type": 1, "lab": VOCAB // 2 + 1},
        measurements_idxmap={"event_type": 1, "lab": 2},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["lab"],
            "multivariate_regression": ["lab"],
        },
        max_seq_len=16,
        hidden_size=32,
        head_dim=8,
        num_attention_heads=4,
        num_hidden_layers=2,
        intermediate_size=32,
        TTE_generation_layer_type="exponential",
    )
    defaults.update(kwargs)
    return StructuredTransformerConfig(**defaults)


def make_subject(L, M=4, seed=0):
    rng = np.random.default_rng(seed)
    dyn_meas = np.full((L, M), 2, dtype=np.int64)
    dyn_meas[:, 0] = 1
    dyn_idx = np.where(
        dyn_meas == 1,
        rng.integers(1, VOCAB // 2 + 1, size=dyn_meas.shape),
        rng.integers(VOCAB // 2 + 1, VOCAB, size=dyn_meas.shape),
    )
    return {
        "time_delta": rng.uniform(0.5, 10.0, size=L).astype(np.float32),
        "dynamic_indices": dyn_idx,
        "dynamic_measurement_indices": dyn_meas,
        "dynamic_values": rng.normal(size=(L, M)).astype(np.float32),
        "dynamic_values_mask": (dyn_meas == 2) & (rng.random((L, M)) < 0.5),
    }


def padded_batch(subjects, L):
    """One subject per right-padded row."""
    B, M = len(subjects), subjects[0]["dynamic_indices"].shape[1]
    out = {
        "event_mask": np.zeros((B, L), dtype=bool),
        "time_delta": np.zeros((B, L), dtype=np.float32),
        "dynamic_indices": np.zeros((B, L, M), dtype=np.int64),
        "dynamic_measurement_indices": np.zeros((B, L, M), dtype=np.int64),
        "dynamic_values": np.zeros((B, L, M), dtype=np.float32),
        "dynamic_values_mask": np.zeros((B, L, M), dtype=bool),
    }
    for i, s in enumerate(subjects):
        n = len(s["time_delta"])
        out["event_mask"][i, :n] = True
        for k in ("time_delta", "dynamic_indices", "dynamic_measurement_indices",
                  "dynamic_values", "dynamic_values_mask"):
            out[k][i, :n] = s[k]
    return EventStreamBatch(**{k: jnp.asarray(v) for k, v in out.items()})


def packed_batch(subjects, L):
    """All subjects concatenated into one row with segment ids."""
    M = subjects[0]["dynamic_indices"].shape[1]
    out = {
        "event_mask": np.zeros((1, L), dtype=bool),
        "time_delta": np.zeros((1, L), dtype=np.float32),
        "dynamic_indices": np.zeros((1, L, M), dtype=np.int64),
        "dynamic_measurement_indices": np.zeros((1, L, M), dtype=np.int64),
        "dynamic_values": np.zeros((1, L, M), dtype=np.float32),
        "dynamic_values_mask": np.zeros((1, L, M), dtype=bool),
        "segment_ids": np.zeros((1, L), dtype=np.int64),
    }
    pos = 0
    spans = []
    for i, s in enumerate(subjects):
        n = len(s["time_delta"])
        spans.append((pos, pos + n))
        out["event_mask"][0, pos : pos + n] = True
        out["segment_ids"][0, pos : pos + n] = i
        for k in ("time_delta", "dynamic_indices", "dynamic_measurement_indices",
                  "dynamic_values", "dynamic_values_mask"):
            out[k][0, pos : pos + n] = s[k]
        pos += n
    out["segment_ids"][0, pos:] = len(subjects) - 1
    return EventStreamBatch(**{k: jnp.asarray(v) for k, v in out.items()}), spans


class TestTimeFromDeltas:
    def test_segment_reset(self):
        batch = EventStreamBatch(
            event_mask=jnp.asarray([[True] * 6]),
            time_delta=jnp.asarray([[1.0, 2.0, 3.0, 5.0, 7.0, 1.0]]),
            segment_ids=jnp.asarray([[0, 0, 0, 1, 1, 1]]),
        )
        t = np.asarray(time_from_deltas(batch))
        # Segment 0: 0, 1, 3; segment 1 restarts: 0, 5, 12.
        np.testing.assert_allclose(t[0], [0.0, 1.0, 3.0, 0.0, 5.0, 12.0])


class TestPackedEquivalence:
    def test_encoder_packed_matches_padded(self):
        config = make_config()
        subjects = [make_subject(5, seed=1), make_subject(7, seed=2), make_subject(3, seed=3)]
        pad = padded_batch(subjects, L=8)
        pack, spans = packed_batch(subjects, L=16)

        encoder = ConditionallyIndependentPointProcessTransformer(config)
        params = programs.init(encoder, jax.random.PRNGKey(0), pad)

        enc_pad = np.asarray(programs.apply(encoder, params, pad).last_hidden_state)
        enc_pack = np.asarray(programs.apply(encoder, params, pack).last_hidden_state)

        for i, (lo, hi) in enumerate(spans):
            n = hi - lo
            np.testing.assert_allclose(
                enc_pack[0, lo:hi], enc_pad[i, :n], rtol=2e-4, atol=2e-5,
            )

    def test_local_attention_window_respects_segments(self):
        config = make_config(seq_attention_types=["local", "local"], seq_window_size=3)
        subjects = [make_subject(6, seed=4), make_subject(6, seed=5)]
        pad = padded_batch(subjects, L=6)
        pack, spans = packed_batch(subjects, L=12)

        encoder = ConditionallyIndependentPointProcessTransformer(config)
        params = programs.init(encoder, jax.random.PRNGKey(0), pad)
        enc_pad = np.asarray(programs.apply(encoder, params, pad).last_hidden_state)
        enc_pack = np.asarray(programs.apply(encoder, params, pack).last_hidden_state)
        for i, (lo, hi) in enumerate(spans):
            np.testing.assert_allclose(
                enc_pack[0, lo:hi], enc_pad[i, : hi - lo], rtol=2e-4, atol=2e-5,
            )

    def test_ci_model_trains_on_packed_batches(self):
        config = make_config()
        subjects = [make_subject(5, seed=1), make_subject(7, seed=2)]
        pack, _ = packed_batch(subjects, L=16)
        model = CIPPTForGenerativeSequenceModeling(config)
        params = programs.init(model, jax.random.PRNGKey(0), pack)
        assert np.isfinite(float(programs.loss(model, params, pack)))
        grads = programs.grad(model, params, pack)
        assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))

    def test_tte_mask_excludes_cross_segment_gaps(self):
        config = make_config()
        subjects = [make_subject(4, seed=1), make_subject(4, seed=2)]
        pack, _ = packed_batch(subjects, L=8)
        model = CIPPTForGenerativeSequenceModeling(config)
        params = programs.init(model, jax.random.PRNGKey(0), pack)
        out = programs.apply(model, params, pack)
        # TTE labels: positions 0..6 (L-1); position 3 bridges the segments
        # and must be masked (label forced to the 1.0 filler).
        tte_true = np.asarray(out.labels.time_to_event)
        assert tte_true[0, 3] == 1.0

    @staticmethod
    def _na_config(**kwargs):
        return make_config(
            structured_event_processing_mode="nested_attention",
            measurements_per_dep_graph_level=[[], ["event_type"], ["lab"]],
            dep_graph_attention_types=["global"],
            **kwargs,
        )

    @pytest.mark.slow  # full NA model traces on two layouts
    def test_na_model_packed_matches_padded(self):
        """Gold invariant for NA: the dep-graph walk over packed rows matches
        separate padded rows at every subject position — segment-aware seq
        attention AND segment-aware history embeddings."""
        config = self._na_config()
        subjects = [make_subject(5, seed=1), make_subject(7, seed=2), make_subject(3, seed=3)]
        pad = padded_batch(subjects, L=8)
        pack, spans = packed_batch(subjects, L=16)

        model = NAPPTForGenerativeSequenceModeling(config)
        params = model.init(jax.random.PRNGKey(0), pad)

        out_pad = model.apply(params, pad)
        out_pack = model.apply(params, pack)

        # Compare per-measurement classification logits at each subject's
        # positions (dist params carry the encodings through the level walk).
        for meas, (_, dist_pad) in out_pad.preds.classification.items():
            dist_pack = out_pack.preds.classification[meas][1]
            lp_pad = np.asarray(dist_pad.logits)
            lp_pack = np.asarray(dist_pack.logits)
            for i, (lo, hi) in enumerate(spans):
                n = hi - lo
                np.testing.assert_allclose(
                    lp_pack[0, lo:hi], lp_pad[i, :n], rtol=2e-4, atol=2e-4
                )

        assert np.isfinite(float(out_pack.loss))
        grads = jax.grad(lambda p: model.apply(p, pack).loss)(params)
        assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))

    def test_na_packed_rejects_cached_decoding(self):
        config = self._na_config()
        subjects = [make_subject(4, seed=1)]
        pack, _ = packed_batch(subjects, L=8)
        model = NAPPTForGenerativeSequenceModeling(config)
        params = programs.init(model, jax.random.PRNGKey(0), pack)
        with pytest.raises(NotImplementedError, match="KV-cached"):
            programs.apply(model, params, pack, use_cache=True)


class TestBatchSlicing:
    def test_slice_preserves_segment_ids(self):
        subjects = [make_subject(4, seed=1), make_subject(4, seed=2)]
        pack, _ = packed_batch(subjects, L=8)
        sliced = pack.slice((slice(0, 1), slice(0, 6)))
        assert sliced.segment_ids is not None
        np.testing.assert_array_equal(
            np.asarray(sliced.segment_ids), np.asarray(pack.segment_ids)[:1, :6]
        )


class TestPackedBatches:
    def test_packing_structure(self, tmp_path):
        from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
        from eventstreamgpt_tpu.data.synthetic import write_synthetic_dataset

        write_synthetic_dataset(
            tmp_path,
            n_subjects_per_split={"train": 32},
            n_labs=50,
            n_meds=20,
            mean_seq_len=24,
            max_seq_len=64,
            seed=0,
        )
        ds = JaxDataset(
            PytorchDatasetConfig(save_dir=tmp_path, max_seq_len=64, min_seq_len=4), "train"
        )
        total_events = 0
        n_segments = 0
        for batch in ds.packed_batches(batch_size=4, seq_len=64, shuffle=True, seed=0):
            em = np.asarray(batch.event_mask)
            seg = np.asarray(batch.segment_ids)
            B, L = em.shape
            assert L == 64
            total_events += int(em.sum())
            for b in range(B):
                real_segs = seg[b][em[b]]
                # Segments are contiguous, starting at 0.
                changes = (np.diff(real_segs) != 0).sum()
                n_uniq = len(np.unique(real_segs))
                assert changes == n_uniq - 1
                assert real_segs[0] == 0
                n_segments += n_uniq
                # Padding extends the last segment id.
                if em[b].sum() < L:
                    assert (seg[b][~em[b]] == real_segs[-1]).all()

        # Every subject appears exactly once (no subject exceeds seq_len here
        # beyond cropping; total events ≤ sum of capped lengths).
        capped = sum(min(ds.data.n_events(i), 64) for i in range(len(ds)))
        assert total_events == capped
        assert n_segments == len(ds)

    def test_packing_reduces_rows(self, tmp_path):
        from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
        from eventstreamgpt_tpu.data.synthetic import write_synthetic_dataset

        write_synthetic_dataset(
            tmp_path,
            n_subjects_per_split={"train": 64},
            n_labs=50,
            n_meds=20,
            mean_seq_len=20,
            max_seq_len=40,
            seed=1,
        )
        ds = JaxDataset(
            PytorchDatasetConfig(save_dir=tmp_path, max_seq_len=128, min_seq_len=4), "train"
        )
        packed_rows = sum(
            np.asarray(b.event_mask).shape[0]
            for b in ds.packed_batches(batch_size=8, seq_len=128, shuffle=False)
        )
        assert packed_rows < len(ds) / 2  # several subjects per 128-row
