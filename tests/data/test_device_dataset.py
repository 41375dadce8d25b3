"""`DeviceDataset`: on-device collation must mirror host collation exactly.

The device-resident path exists to eliminate per-batch host→device transfer
(the round-5 feed-path bottleneck); correctness contract: given the same
seed, `DeviceDataset.batches` / `.packed_batches` produce batches
bit-identical to `JaxDataset.batches` / `.packed_batches`, including crop
randomness, padding sides, fill-row blanking, labels, and resume
fast-forward. Runs on the CPU backend (conftest) — the kernels are plain
jnp gathers, identical on any backend.
"""

from pathlib import Path
import shutil

import numpy as np
import pytest

from eventstreamgpt_tpu.data import DeviceDataset, JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.data.config import SeqPaddingSide, SubsequenceSamplingStrategy

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds_dev")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return dst


def make_ds(sample_dir, **kwargs):
    defaults = dict(save_dir=sample_dir, max_seq_len=8, min_seq_len=2)
    defaults.update(kwargs)
    return JaxDataset(PytorchDatasetConfig(**defaults), "tuning")


def assert_batches_equal(dev_b, host_b):
    import dataclasses

    for f in dataclasses.fields(host_b):
        hv = getattr(host_b, f.name)
        dv = getattr(dev_b, f.name)
        if hv is None:
            assert dv is None, f.name
            continue
        if isinstance(hv, dict):
            assert set(hv) == set(dv), f.name
            for k in hv:
                np.testing.assert_array_equal(
                    np.asarray(dv[k]), np.asarray(hv[k]), err_msg=f"{f.name}[{k}]"
                )
                assert np.asarray(dv[k]).dtype == np.asarray(hv[k]).dtype, f"{f.name}[{k}]"
            continue
        np.testing.assert_array_equal(np.asarray(dv), np.asarray(hv), err_msg=f.name)
        assert np.asarray(dv).dtype == np.asarray(hv).dtype, f.name


class TestPaddedParity:
    @pytest.mark.parametrize("pad", [SeqPaddingSide.RIGHT, SeqPaddingSide.LEFT])
    def test_epoch_bitwise_identical(self, sample_dir, pad):
        ds = make_ds(sample_dir, seq_padding_side=pad)
        dd = DeviceDataset(ds)
        host = list(ds.batches(3, shuffle=True, seed=7, drop_last=False))
        dev = list(dd.batches(3, shuffle=True, seed=7, drop_last=False))
        assert len(host) == len(dev) and len(host) > 1
        for db, hb in zip(dev, host):
            assert_batches_equal(db, hb)

    def test_random_crops_share_rng_stream(self, sample_dir):
        """RANDOM subsequence sampling must land on identical crops."""
        ds = make_ds(
            sample_dir,
            max_seq_len=4,
            subsequence_sampling_strategy=SubsequenceSamplingStrategy.RANDOM,
        )
        dd = DeviceDataset(ds)
        for db, hb in zip(
            dd.batches(2, shuffle=True, seed=3), ds.batches(2, shuffle=True, seed=3)
        ):
            assert_batches_equal(db, hb)

    def test_fill_rows_blanked_like_host(self, sample_dir):
        ds = make_ds(sample_dir)
        dd = DeviceDataset(ds)
        B = len(ds) + 2  # forces a short final batch with cyclic fill
        (db,) = list(dd.batches(B, shuffle=False, seed=0, drop_last=False))
        (hb,) = list(ds.batches(B, shuffle=False, seed=0, drop_last=False))
        assert not np.asarray(db.valid_mask)[-2:].any()
        assert not np.asarray(db.event_mask)[-2:].any()
        assert_batches_equal(db, hb)

    def test_skip_batches_resume_matches(self, sample_dir):
        ds = make_ds(
            sample_dir,
            max_seq_len=4,
            subsequence_sampling_strategy=SubsequenceSamplingStrategy.RANDOM,
        )
        dd = DeviceDataset(ds)
        full = list(dd.batches(2, shuffle=True, seed=11))
        resumed = list(dd.batches(2, shuffle=True, seed=11, skip_batches=2))
        assert len(resumed) == len(full) - 2
        for rb, fb in zip(resumed, full[2:]):
            assert_batches_equal(rb, fb)

    def test_capped_max_n_dynamic_clips_like_host(self, sample_dir):
        """config.max_n_dynamic below the data's true max: the dense tables
        must clip trailing slots exactly as host collation does."""
        ds = make_ds(sample_dir, max_n_dynamic=2)
        assert ds.max_n_dynamic == 2
        dd = DeviceDataset(ds)
        for db, hb in zip(
            dd.batches(3, shuffle=False, seed=0, drop_last=False),
            ds.batches(3, shuffle=False, seed=0, drop_last=False),
        ):
            assert_batches_equal(db, hb)

    def test_light_fields_and_counts(self, sample_dir):
        ds = make_ds(
            sample_dir,
            do_include_start_time_min=True,
            do_include_subject_id=True,
            do_include_subsequence_indices=True,
        )
        dd = DeviceDataset(ds)
        pairs = list(dd.batches(3, shuffle=False, seed=0, drop_last=False, with_counts=True))
        host = list(ds.batches(3, shuffle=False, seed=0, drop_last=False))
        for (db, n_events), hb in zip(pairs, host):
            assert_batches_equal(db, hb)
            assert n_events == int(np.asarray(hb.event_mask).sum())


class TestPackedParity:
    def test_packed_epoch_bitwise_identical(self, sample_dir):
        ds = make_ds(sample_dir, max_seq_len=16)
        dd = DeviceDataset(ds)
        host = list(ds.packed_batches(2, seq_len=16, shuffle=True, seed=5))
        dev = list(dd.packed_batches(2, seq_len=16, shuffle=True, seed=5))
        assert len(host) == len(dev) and len(host) >= 1
        for db, hb in zip(dev, host):
            assert_batches_equal(db, hb)

    def test_packed_counts(self, sample_dir):
        ds = make_ds(sample_dir, max_seq_len=16)
        dd = DeviceDataset(ds)
        for db, n_events in dd.packed_batches(2, seq_len=16, seed=5, with_counts=True):
            assert n_events == int(np.asarray(db.event_mask).sum())


@pytest.fixture(scope="module")
def synth_dir(tmp_path_factory):
    """Self-contained synthetic dataset (no external fixture dependency) for
    the sharded-layout tests — multi-host behavior must be testable anywhere."""
    from eventstreamgpt_tpu.data.synthetic import write_synthetic_dataset

    dst = tmp_path_factory.mktemp("synth_ds_sharded")
    write_synthetic_dataset(
        dst,
        n_subjects_per_split={"train": 32, "tuning": 8},
        n_event_types=8,
        n_labs=32,
        n_meds=8,
        mean_seq_len=12,
        max_seq_len=24,
        seed=0,
    )
    return dst


def make_synth_ds(synth_dir, **kwargs):
    defaults = dict(save_dir=synth_dir, max_seq_len=8, min_seq_len=2)
    defaults.update(kwargs)
    return JaxDataset(PytorchDatasetConfig(**defaults), "train")


class TestShardedLayout:
    """The pod layout (``data_shards > 1``): dense tables sharded over the
    mesh's ``data`` axis, plans dealt shard-major from one rng stream. The
    contract is the same bit-exactness the replicated layout pins, against
    host collation of the SAME dealt plan stream (``n_shards=K``); these run
    single-process over the 8-device virtual CPU mesh — the multi-process
    mechanics (per-process shard upload, gloo collectives) are covered by
    ``tests/test_multiprocess_feed.py``.
    """

    def _mesh(self, k):
        import jax
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:k]), ("data",))

    def test_padded_epoch_bitwise_identical(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        dd = DeviceDataset(ds, mesh=self._mesh(4), data_shards=4)
        host = list(ds.batches(8, shuffle=True, seed=7, drop_last=False, n_shards=4))
        dev = list(dd.batches(8, shuffle=True, seed=7, drop_last=False))
        assert len(host) == len(dev) and len(host) > 1
        for db, hb in zip(dev, host):
            assert_batches_equal(db, hb)

    def test_packed_epoch_bitwise_identical(self, synth_dir):
        ds = make_synth_ds(synth_dir, max_seq_len=16)
        dd = DeviceDataset(ds, mesh=self._mesh(4), data_shards=4)
        host = list(ds.packed_batches(4, seq_len=16, shuffle=True, seed=5, n_shards=4))
        dev = list(dd.packed_batches(4, seq_len=16, shuffle=True, seed=5))
        assert len(host) == len(dev) and len(host) >= 1
        for db, hb in zip(dev, host):
            assert_batches_equal(db, hb)

    def test_skip_batches_resume_matches(self, synth_dir):
        ds = make_synth_ds(
            synth_dir,
            max_seq_len=4,
            subsequence_sampling_strategy=SubsequenceSamplingStrategy.RANDOM,
        )
        dd = DeviceDataset(ds, mesh=self._mesh(2), data_shards=2)
        full = list(dd.batches(4, shuffle=True, seed=11))
        resumed = list(dd.batches(4, shuffle=True, seed=11, skip_batches=2))
        assert len(resumed) == len(full) - 2
        for rb, fb in zip(resumed, full[2:]):
            assert_batches_equal(rb, fb)

    def test_dealt_plan_streams_identical_across_callers(self, synth_dir):
        """Every process derives the SAME dealt plans from the shared seed —
        the property multi-host correctness rests on."""
        ds = make_synth_ds(synth_dir)
        a = list(ds.plan_batches(8, shuffle=True, seed=3, n_shards=4))
        b = list(ds.plan_batches(8, shuffle=True, seed=3, n_shards=4))
        assert len(a) == len(b) > 0
        for pa, pb in zip(a, b):
            np.testing.assert_array_equal(pa.subject_indices, pb.subject_indices)
            np.testing.assert_array_equal(pa.starts, pb.starts)
            np.testing.assert_array_equal(pa.valid_mask, pb.valid_mask)

    def test_shard_rows_reference_own_pool_only(self, synth_dir):
        """Dealt plans keep each batch row inside its shard's subject pool, so
        the sharded collate's gathers stay shard-local (no collectives)."""
        ds = make_synth_ds(synth_dir)
        bounds = ds.subject_shards(4)
        for plan in ds.plan_batches(8, shuffle=True, seed=3, n_shards=4):
            rows = plan.subject_indices.reshape(4, 2)
            for k in range(4):
                assert (rows[k] >= bounds[k]).all() and (rows[k] < bounds[k + 1]).all()

    def test_single_shard_stream_is_the_historical_stream(self, synth_dir):
        """n_shards=1 must reproduce the pre-sharding plan stream bit-for-bit
        (resume compatibility for existing single-host checkpoints)."""
        ds = make_synth_ds(synth_dir)
        a = list(ds.plan_batches(4, shuffle=True, seed=7))
        b = list(ds.plan_batches(4, shuffle=True, seed=7, n_shards=1))
        assert len(a) == len(b) > 0
        for pa, pb in zip(a, b):
            np.testing.assert_array_equal(pa.subject_indices, pb.subject_indices)
            np.testing.assert_array_equal(pa.starts, pb.starts)

    def test_batch_size_must_divide_by_shards(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        with pytest.raises(ValueError, match="divisible"):
            next(ds.plan_batches(6, shuffle=True, seed=0, n_shards=4))

    def test_data_shards_must_match_mesh_axis(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        with pytest.raises(ValueError, match="data"):
            DeviceDataset(ds, mesh=None, data_shards=2)
        with pytest.raises(ValueError, match="must equal the mesh"):
            DeviceDataset(ds, mesh=self._mesh(4), data_shards=2)

    def test_more_shards_than_subjects_raises(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        with pytest.raises(ValueError, match="shard"):
            ds.subject_shards(len(ds) + 1)

    def test_event_balanced_pools_cover_all_subjects(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        bounds = ds.subject_shards(4)
        assert bounds[0] == 0 and bounds[-1] == ds.data.n_subjects
        assert (np.diff(bounds) >= 1).all()


class TestFinitenessGuard:
    """Table-build-time NaN validation: a poisoned DL cache must fail loudly
    at DeviceDataset construction (resident batches then skip per-batch NaN
    readbacks on the strength of this check — zero_shot_evaluator lineage)."""

    def _poison(self, ds, field):
        arr = np.asarray(getattr(ds.data, field), np.float32).copy()
        # Poison an OBSERVED value so the guard can't be satisfied by masking.
        if field == "dynamic_values":
            obs = np.asarray(ds.data.dynamic_values_observed)
            arr[np.argmax(obs)] = np.nan
        else:
            arr[0] = np.nan
        object.__setattr__(ds.data, field, arr)
        return ds

    @pytest.mark.parametrize("field", ["time_delta", "dynamic_values"])
    def test_poisoned_cache_fails_at_build(self, synth_dir, field):
        ds = self._poison(make_synth_ds(synth_dir), field)
        with pytest.raises(ValueError, match="non-finite"):
            DeviceDataset(ds)

    def test_clean_cache_builds(self, synth_dir):
        assert DeviceDataset(make_synth_ds(synth_dir)).nbytes > 0


class TestTopologyGate:
    """`create` / `try_create` on explicit vs auto residency: single-process
    keeps the replicated layout; error paths are loud, not silent."""

    def test_create_single_process_is_replicated(self, synth_dir):
        dd = DeviceDataset.create(make_synth_ds(synth_dir))
        assert dd.data_shards == 1

    def test_try_create_budget_gate_still_applies(self, synth_dir):
        ds = make_synth_ds(synth_dir)
        assert DeviceDataset.try_create(ds, max_bytes=1) is None
        dd = DeviceDataset.try_create(ds)
        assert dd is not None and dd.data_shards == 1

    def test_sharded_estimate_accounts_for_padding(self, synth_dir):
        """The sharded estimate pads every shard to the largest pool, so it
        must bound the actually-built sharded tables (the per-process budget
        gate divides it by process count) and never undercut the unsharded
        estimate on skewed cohorts."""
        import jax
        from jax.sharding import Mesh

        ds = make_synth_ds(synth_dir)
        est = DeviceDataset.estimate_sharded_nbytes(ds, 4)
        assert est >= DeviceDataset.estimate_nbytes(ds) - ds.data.subject_event_offsets.nbytes
        dd = DeviceDataset(ds, mesh=Mesh(np.asarray(jax.devices()[:4]), ("data",)), data_shards=4)
        assert dd.nbytes <= est
        with pytest.raises(ValueError, match="shard"):
            DeviceDataset.estimate_sharded_nbytes(ds, len(ds) + 1)


class TestResidency:
    def test_upload_size_reported(self, sample_dir):
        ds = make_ds(sample_dir)
        dd = DeviceDataset(ds)
        assert dd.nbytes > 0
        # Resident bytes ≈ dense-table size (CSR × M/avg_fill) — bounded by
        # dataset scale, not epoch count × batch traffic.
        assert dd.nbytes < 64 * 1024 * 1024

    def test_mesh_sharded_outputs(self, sample_dir):
        import jax
        from jax.sharding import Mesh

        ds = make_ds(sample_dir)
        devices = np.asarray(jax.devices()[:4]).reshape(4)
        mesh = Mesh(devices, ("data",))
        dd = DeviceDataset(ds, mesh=mesh)
        (db, hb), *_ = zip(
            dd.batches(4, shuffle=False, seed=0, drop_last=False),
            ds.batches(4, shuffle=False, seed=0, drop_last=False),
        )
        assert_batches_equal(db, hb)
        assert "data" in str(db.dynamic_indices.sharding.spec)
