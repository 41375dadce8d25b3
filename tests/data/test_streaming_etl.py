"""Streaming sharded ETL + incremental fit + online admission (r11).

Fast units (tier-1): shard planning, ``__row_pos__`` plumbing,
sufficient-statistic merge algebra, append-only vocabulary growth, and the
numeric DL-chunk ordering fix. Slow e2e (own CI chunk): the
2-worker-vs-serial bit-identity pin (frames + DL-cache file hashes), the
append-subjects contract (old shard files untouched on disk, frozen vocab
indices, documented drift vs a full re-fit), and online admission through a
real `GenerationEngine` (raw events → frozen transform → prefill request →
generated continuation, bit-identical to the batch ETL's transform for the
same subject). Everything runs on synthetic raw CSVs — no reference-data
dependency. See docs/ingestion.md for the contracts.
"""

import hashlib
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from eventstreamgpt_tpu.data.config import (
    DatasetConfig,
    DatasetSchema,
    InputDFSchema,
    MeasurementConfig,
)
from eventstreamgpt_tpu.data.dataset_base import shard_subject_ids
from eventstreamgpt_tpu.data.dataset_pandas import Dataset
from eventstreamgpt_tpu.data.preprocessing import StandardScaler, StddevCutoffOutlierDetector
from eventstreamgpt_tpu.data.synthetic import write_synthetic_raw_csvs
from eventstreamgpt_tpu.data.time_dependent_functor import AgeFunctor
from eventstreamgpt_tpu.data.types import (
    DataModality,
    InputDataType,
    InputDFType,
    TemporalityType,
)
from eventstreamgpt_tpu.data.vocabulary import Vocabulary

pytestmark = pytest.mark.etl


def make_schema(raw_dir: Path) -> DatasetSchema:
    static_schema = InputDFSchema(
        input_df=str(raw_dir / "subjects.csv"),
        type=InputDFType.STATIC,
        subject_id_col="MRN",
        data_schema={
            "eye_color": InputDataType.CATEGORICAL,
            "dob": (InputDataType.TIMESTAMP, "%m/%d/%Y"),
        },
    )
    admissions_schema = InputDFSchema(
        input_df=str(raw_dir / "admit_vitals.csv"),
        type=InputDFType.RANGE,
        event_type=("OUTPATIENT_VISIT", "ADMISSION", "DISCHARGE"),
        start_ts_col="admit_date",
        end_ts_col="disch_date",
        ts_format="%m/%d/%Y, %H:%M:%S",
        data_schema={"department": InputDataType.CATEGORICAL},
    )
    vitals_schema = InputDFSchema(
        input_df=str(raw_dir / "admit_vitals.csv"),
        type=InputDFType.EVENT,
        event_type="VITALS",
        ts_col="vitals_date",
        ts_format="%m/%d/%Y, %H:%M:%S",
        data_schema={"HR": InputDataType.FLOAT, "temp": InputDataType.FLOAT},
    )
    return DatasetSchema(static=static_schema, dynamic=[admissions_schema, vitals_schema])


def make_config(save_dir: Path) -> DatasetConfig:
    return DatasetConfig(
        measurement_configs={
            "eye_color": MeasurementConfig(
                temporality=TemporalityType.STATIC,
                modality=DataModality.SINGLE_LABEL_CLASSIFICATION,
            ),
            "age": MeasurementConfig(
                temporality=TemporalityType.FUNCTIONAL_TIME_DEPENDENT,
                functor=AgeFunctor(dob_col="dob"),
            ),
            "department": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC,
                modality=DataModality.MULTI_LABEL_CLASSIFICATION,
            ),
            "HR": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC,
                modality=DataModality.UNIVARIATE_REGRESSION,
            ),
            "temp": MeasurementConfig(
                temporality=TemporalityType.DYNAMIC,
                modality=DataModality.UNIVARIATE_REGRESSION,
            ),
        },
        min_events_per_subject=3,
        agg_by_time_scale="1h",
        min_valid_column_observations=5,
        min_valid_vocab_element_observations=5,
        min_true_float_frequency=0.1,
        min_unique_numerical_observations=20,
        outlier_detector_config={"cls": "stddev_cutoff", "stddev_cutoff": 4.0},
        normalizer_config={"cls": "standard_scaler"},
        save_dir=save_dir,
    )


def build_dataset(raw_dir: Path, save_dir: Path, n_workers: int = 1) -> Dataset:
    save_dir.mkdir(parents=True, exist_ok=True)
    ESD = Dataset(
        config=make_config(save_dir), input_schema=make_schema(raw_dir), n_workers=n_workers
    )
    ESD.split([0.8, 0.1], seed=1)
    ESD.preprocess(n_workers=n_workers)
    ESD.save(do_overwrite=True)
    ESD.cache_deep_learning_representation(do_overwrite=True, n_workers=n_workers)
    return ESD


def file_sigs(d: Path) -> dict[str, tuple[int, str]]:
    return {
        fp.name: (fp.stat().st_mtime_ns, hashlib.sha256(fp.read_bytes()).hexdigest())
        for fp in sorted(d.glob("*.parquet"))
    }


# ------------------------------------------------------------ fast: planning
class TestShardPlanning:
    def test_contiguous_by_mapped_id_and_deterministic(self):
        m = {f"s{i}": i for i in range(10)}
        shards = shard_subject_ids(m, 3)
        assert [sorted(s.values()) for s in shards] == [
            sorted(s.values()) for s in shard_subject_ids(m, 3)
        ]
        flat = [v for s in shards for v in sorted(s.values())]
        assert flat == list(range(10)), "shards must tile the id space contiguously in order"

    def test_more_workers_than_subjects_drops_empties(self):
        shards = shard_subject_ids({"a": 0, "b": 1}, 8)
        assert len(shards) == 2 and all(len(s) == 1 for s in shards)

    def test_single_shard_is_the_whole_map(self):
        m = {"a": 0, "b": 1, "c": 2}
        assert shard_subject_ids(m, 1) == [m]


class TestRowPosPlumbing:
    def test_positions_survive_subject_filtering(self):
        df = pd.DataFrame(
            {
                "MRN": ["a", "b", "a", "c", "b"],
                "ts": pd.to_datetime(["2020-01-01"] * 5),
                "v": [1.0, 2.0, 3.0, 4.0, 5.0],
            }
        )
        out = Dataset._load_input_df(
            df,
            [("ts", InputDataType.TIMESTAMP), ("v", InputDataType.FLOAT)],
            subject_id_col="MRN",
            subject_ids_map={"b": 1},
            subject_id_dtype=np.int64,
            keep_row_pos=True,
        )
        # Subject b's rows sat at source positions 1 and 4.
        assert out["__row_pos__"].tolist() == [1, 4]

    def test_serial_path_has_no_marker(self):
        df = pd.DataFrame(
            {"MRN": ["a"], "ts": pd.to_datetime(["2020-01-01"]), "v": [1.0]}
        )
        out = Dataset._load_input_df(
            df,
            [("ts", InputDataType.TIMESTAMP), ("v", InputDataType.FLOAT)],
            subject_id_col="MRN",
            subject_ids_map={"a": 0},
            subject_id_dtype=np.int64,
        )
        assert "__row_pos__" not in out.columns


class TestParseOnceHandoff:
    """r12 satellite: the sharded build parses each raw source ONCE in the
    parent and streams per-shard parquet slices with original row positions
    stamped — the fast units pin the position plumbing; the slow e2e
    (`TestParallelBuildBitIdentity` + the parse-count test below) pins
    bit-identity and the 1×-parse contract."""

    @staticmethod
    def _df():
        return pd.DataFrame(
            {
                "MRN": ["a", "b", "a", "c", "b", "c"],
                "ts": pd.to_datetime(["2020-01-01"] * 6),
                "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0],
            }
        )

    def test_load_honors_stamped_positions(self):
        # A pre-sliced handoff frame carries ORIGINAL source positions; the
        # loader must keep them, not re-derive slice-local row order.
        df = self._df().assign(__row_pos__=np.arange(6, dtype=np.int64))
        sliced = df[df["MRN"].isin(["b"])]  # source positions 1 and 4
        out = Dataset._load_input_df(
            sliced,
            [("ts", InputDataType.TIMESTAMP), ("v", InputDataType.FLOAT)],
            subject_id_col="MRN",
            subject_ids_map={"b": 1},
            subject_id_dtype=np.int64,
            keep_row_pos=True,
        )
        assert out["__row_pos__"].tolist() == [1, 4]

    def test_marker_dropped_without_keep_row_pos(self):
        df = self._df().assign(__row_pos__=np.arange(6, dtype=np.int64))
        out = Dataset._load_input_df(
            df,
            [("v", InputDataType.FLOAT)],
            subject_id_col="MRN",
            subject_ids_map={"a": 0, "b": 1, "c": 2},
            subject_id_dtype=np.int64,
        )
        assert "__row_pos__" not in out.columns

    def test_preparse_slices_disjoint_and_stamped(self, tmp_path):
        src = str(tmp_path / "events.csv")
        self._df().to_csv(src, index=False)
        shards = [{"a": 0, "b": 1}, {"c": 2}]
        slices = Dataset._preparse_shard_sources(
            {src: []}, shards, "MRN", tmp_path / "stream"
        )
        # The handoff is parquet slice PATHS under stream_dir (bounded
        # parent RSS: nothing raw survives the preparse loop), not frames.
        assert all(Path(m[src]).is_file() for m in slices)
        s0 = pd.read_parquet(slices[0][src])
        s1 = pd.read_parquet(slices[1][src])
        assert s0["__row_pos__"].tolist() == [0, 1, 2, 4]
        assert s1["__row_pos__"].tolist() == [3, 5]
        # Row-disjoint: together the slices tile the kept rows exactly once.
        assert sorted(s0["__row_pos__"].tolist() + s1["__row_pos__"].tolist()) == list(
            range(6)
        )

    def test_no_path_sources_is_a_noop(self, tmp_path):
        assert (
            Dataset._preparse_shard_sources({}, [{"a": 0}], "MRN", tmp_path) is None
        )


# ------------------------------------------- fast: sufficient-stat algebra
class TestSufficientStats:
    def test_merge_equals_direct_stats(self):
        S = StandardScaler()
        a, b = np.array([1.0, 2.0, 3.0]), np.array([4.0, 5.0])
        merged = S.merge_stats(S.sufficient_stats(a), S.sufficient_stats(b))
        direct = S.sufficient_stats(np.concatenate([a, b]))
        assert merged == direct

    def test_scaler_params_from_stats_match_fit(self):
        S = StandardScaler()
        x = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
        p_fit = S.fit(x)
        p_stats = S.params_from_stats(S.sufficient_stats(x))
        # Same moments through a different accumulation — equal to float
        # tolerance, NOT guaranteed bitwise (the documented drift source).
        assert np.isclose(p_fit["mean_"], p_stats["mean_"], rtol=1e-12)
        assert np.isclose(p_fit["std_"], p_stats["std_"], rtol=1e-12)

    def test_cutoff_params_from_stats(self):
        S = StddevCutoffOutlierDetector(stddev_cutoff=2.0)
        x = np.array([1.0, 3.0, 5.0])
        p_fit = S.fit(x)
        p_stats = S.params_from_stats(S.sufficient_stats(x))
        for k in ("thresh_large_", "thresh_small_"):
            assert np.isclose(p_fit[k], p_stats[k], rtol=1e-12)

    def test_singleton_group_has_nan_std_like_fit(self):
        S = StandardScaler()
        p = S.params_from_stats(S.sufficient_stats([7.0]))
        assert p["mean_"] == 7.0 and np.isnan(p["std_"])

    def test_grouped_stats(self):
        S = StandardScaler()
        out = S.sufficient_stats_grouped(
            pd.Series([1.0, 2.0, 4.0]), pd.Series(["a", "a", "b"])
        )
        assert out == {
            "a": {"count": 2, "sum": 3.0, "sumsq": 5.0},
            "b": {"count": 1, "sum": 4.0, "sumsq": 16.0},
        }


# --------------------------------------------- fast: append-only vocabulary
class TestVocabularyFreeze:
    def test_existing_indices_never_move(self):
        v = Vocabulary(vocabulary=["a", "b", "c", "UNK"], obs_frequencies=[5, 3, 2, 1])
        before = list(v.vocabulary)
        # New counts that would re-rank everything under a full re-fit.
        v.extend_with_counts({"c": 1000, "z": 500, "y": 900}, prior_total=11)
        assert v.vocabulary[: len(before)] == before
        assert v.vocabulary[len(before):] == ["y", "z"], "appended by count desc"

    def test_tie_break_matches_fit_rule(self):
        v = Vocabulary(vocabulary=["a", "UNK"], obs_frequencies=[1, 1])
        v.extend_with_counts({"m": 5, "q": 5}, prior_total=2)
        # count ties break by element, descending — the fit's lexsort rule.
        assert v.vocabulary[-2:] == ["q", "m"]

    def test_frequencies_merge_against_prior_total(self):
        v = Vocabulary(vocabulary=["a", "UNK"], obs_frequencies=[3, 1])
        v.extend_with_counts({"a": 4}, prior_total=4)
        # a: (0.75*4 + 4) / 8
        assert np.isclose(v.obs_frequencies[v.idxmap["a"]], 7 / 8)

    def test_idxmap_cache_invalidated(self):
        v = Vocabulary(vocabulary=["a", "UNK"], obs_frequencies=[1, 1])
        _ = v.idxmap
        v.extend_with_counts({"z": 1}, prior_total=2)
        assert v.idxmap["z"] == len(v.vocabulary) - 1


# ------------------------------------------------ fast: chunk-order fix
class TestChunkOrdering:
    def test_dl_rep_chunks_order_numerically(self, tmp_path):
        from eventstreamgpt_tpu.data.jax_dataset import JaxDataset

        for i in (0, 2, 10):
            pd.DataFrame({"subject_id": [i]}).to_parquet(tmp_path / f"train_{i}.parquet")
        table = JaxDataset._read_dl_reps(tmp_path, "train")
        assert table.column("subject_id").to_pylist() == [0, 2, 10], "lexicographic order would give [0, 10, 2]"


# ----------------------------------------------------- slow: bit-identity
@pytest.mark.slow
class TestParallelBuildBitIdentity:
    @pytest.fixture(scope="class")
    def arms(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("par_etl")
        raw = write_synthetic_raw_csvs(root / "raw", n_subjects=60, seed=3)
        serial = build_dataset(raw, root / "serial" / "sample", n_workers=1)
        pooled = build_dataset(raw, root / "pooled" / "sample", n_workers=3)
        return serial, pooled

    def test_frames_bit_identical(self, arms):
        serial, pooled = arms
        for attr in ("subjects_df", "events_df", "dynamic_measurements_df"):
            pd.testing.assert_frame_equal(getattr(serial, attr), getattr(pooled, attr))

    def test_dl_cache_files_byte_identical(self, arms):
        serial, pooled = arms
        s = file_sigs(Path(serial.config.save_dir) / "DL_reps")
        p = file_sigs(Path(pooled.config.save_dir) / "DL_reps")
        assert sorted(s) == sorted(p) and s
        for name in s:
            assert s[name][1] == p[name][1], f"{name} bytes differ between arms"

    def test_sharded_build_direct_parity(self, arms):
        serial, _ = arms
        stream_dir = Path(serial.config.save_dir) / ".tmp_shards"
        schema = make_schema(Path(serial.config.save_dir).parent.parent / "raw")
        subjects_df, id_map = Dataset.build_subjects_dfs(schema.static)
        dtype = subjects_df["subject_id"].dtype
        ev_a, me_a = Dataset.build_event_and_measurement_dfs(
            id_map, schema.static.subject_id_col, dtype, schema.dynamic_by_df
        )
        ev_b, me_b = Dataset.build_event_and_measurement_dfs_sharded(
            id_map, schema.static.subject_id_col, dtype, schema.dynamic_by_df,
            n_workers=3, stream_dir=stream_dir,
        )
        pd.testing.assert_frame_equal(ev_a, ev_b)
        pd.testing.assert_frame_equal(me_a, me_b)

    def test_each_source_parsed_exactly_once(self, tmp_path, monkeypatch):
        """r12 parse-once pin: the whole 3-worker sharded build parses each
        raw source file exactly once (in the parent — workers read streamed
        parquet slices through `_read_df`, never `_parse_source`). The parse
        log is a file so forked workers' calls (there must be none) would
        land in it too."""
        raw = write_synthetic_raw_csvs(tmp_path / "raw", n_subjects=12, seed=5)
        schema = make_schema(raw)
        subjects_df, id_map = Dataset.build_subjects_dfs(schema.static)
        dtype = subjects_df["subject_id"].dtype

        log = tmp_path / "parse_log.txt"
        orig = Dataset._parse_source.__func__

        def logged(cls, src):
            with open(log, "a") as f:
                f.write(f"{src}\n")
            return orig(cls, src)

        monkeypatch.setattr(Dataset, "_parse_source", classmethod(logged))
        ev, me = Dataset.build_event_and_measurement_dfs_sharded(
            id_map,
            schema.static.subject_id_col,
            dtype,
            schema.dynamic_by_df,
            n_workers=3,
            stream_dir=tmp_path / "shards",
        )
        assert len(ev) > 0 and len(me) > 0
        parses = log.read_text().splitlines()
        assert sorted(parses) == sorted(map(str, schema.dynamic_by_df)), (
            f"each source must parse exactly once; saw {parses}"
        )


# --------------------------------------------------- slow: append-subjects
@pytest.mark.slow
class TestAppendSubjects:
    @pytest.fixture(scope="class")
    def appended(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("append_etl")
        raw_a = write_synthetic_raw_csvs(root / "raw_a", n_subjects=40, seed=3)
        # The append batch carries departments the base fit never saw
        # (n_departments=14 vs 12) — the append-only growth + UNK case.
        raw_b = write_synthetic_raw_csvs(
            root / "raw_b", n_subjects=12, seed=9, n_departments=14
        )
        ESD = build_dataset(raw_a, root / "proc" / "sample")
        DL = Path(ESD.config.save_dir) / "DL_reps"
        before_sigs = file_sigs(DL)
        before_events = ESD.events_df.copy()
        before_vocab = {
            m: list(c.vocabulary.vocabulary)
            for m, c in ESD.measurement_configs.items()
            if c.vocabulary is not None
        }
        before_hr = dict(ESD.measurement_configs["HR"].measurement_metadata["normalizer"])
        info = ESD.append_subjects(make_schema(raw_b), split="train")
        return dict(
            root=root, raw_a=raw_a, raw_b=raw_b, ESD=ESD, DL=DL, info=info,
            before_sigs=before_sigs, before_events=before_events,
            before_vocab=before_vocab, before_hr=before_hr,
        )

    def test_old_shard_files_untouched(self, appended):
        after = file_sigs(appended["DL"])
        for name, sig in appended["before_sigs"].items():
            assert after[name] == sig, f"old shard {name} was rewritten (mtime/hash moved)"
        new_files = set(after) - set(appended["before_sigs"])
        assert new_files == {p.name for p in appended["info"]["chunk_paths"]}

    def test_frozen_vocab_indices_never_move(self, appended):
        ESD = appended["ESD"]
        for m, old in appended["before_vocab"].items():
            new = ESD.measurement_configs[m].vocabulary.vocabulary
            assert new[: len(old)] == old, f"{m}: frozen indices moved"

    def test_unseen_department_appends_and_transforms_to_unk(self, appended):
        ESD = appended["ESD"]
        vocab = ESD.measurement_configs["department"].vocabulary.vocabulary
        new_els = set(vocab) - set(appended["before_vocab"]["department"])
        assert any(el.startswith("DEPT_1") for el in new_els), (
            "the append batch's unseen departments must append to the live vocabulary"
        )
        # In the NEW cache chunk they are UNK (frozen unified layout):
        # unified index of department's UNK = the measure's offset.
        rep = pd.read_parquet(appended["info"]["chunk_paths"][0])
        assert len(rep) == len(appended["info"]["subject_ids"])
        # Frozen layout: no cached index may reach past the frozen total.
        frozen_total = ESD.vocabulary_config.total_vocab_size
        max_idx = max(
            int(np.max([np.max(ev) for ev in row if len(ev)]))
            for row in rep["dynamic_indices"]
            if len(row)
        )
        assert max_idx < frozen_total

    def test_old_event_order_and_rows_unchanged(self, appended):
        ESD = appended["ESD"]
        n_old = len(appended["before_events"])
        head = ESD.events_df.head(n_old).reset_index(drop=True)
        pd.testing.assert_frame_equal(
            head, appended["before_events"].reset_index(drop=True), check_dtype=False
        )

    def test_scaler_updates_from_sufficient_stats(self, appended):
        ESD = appended["ESD"]
        new_hr = ESD.measurement_configs["HR"].measurement_metadata["normalizer"]
        old_hr = appended["before_hr"]
        assert new_hr != old_hr, "HR scaler params must move with the new observations"
        stats = ESD._preproc_stats["normalizer"]["HR"]["HR"]
        S = StandardScaler()
        expect = S.params_from_stats(stats)
        assert np.isclose(new_hr["mean_"], expect["mean_"]) and np.isclose(
            new_hr["std_"], expect["std_"]
        )

    def test_drift_contract_vs_full_refit(self, appended):
        """What may drift vs a from-scratch re-fit on the union, and what
        may not. Allowed: scaler moments (different accumulation + per-era
        outlier thresholds). Not allowed: the incremental cache's vocab
        indices (frozen prefix), old event order, old cache rows."""
        root, ESD = appended["root"], appended["ESD"]
        raw_u = root / "raw_union"
        raw_u.mkdir()
        for name in ("subjects.csv", "admit_vitals.csv"):
            a = pd.read_csv(appended["raw_a"] / name)
            b = pd.read_csv(appended["raw_b"] / name)
            pd.concat([a, b], ignore_index=True).to_csv(raw_u / name, index=False)
        scratch = build_dataset(raw_u, root / "scratch" / "sample")

        # Scaler moments: close (same data) but NOT pinned equal — drift by
        # accumulation order and threshold era is the documented allowance.
        inc = ESD.measurement_configs["HR"].measurement_metadata["normalizer"]
        ref = scratch.measurement_configs["HR"].measurement_metadata["normalizer"]
        assert np.isclose(inc["mean_"], ref["mean_"], rtol=0.05)
        assert np.isclose(inc["std_"], ref["std_"], rtol=0.05)

        # Vocab: the scratch re-fit re-sorts by merged frequency; the
        # incremental vocabulary must instead keep its frozen prefix while
        # covering the same element set.
        inc_v = ESD.measurement_configs["department"].vocabulary.vocabulary
        ref_v = scratch.measurement_configs["department"].vocabulary.vocabulary
        assert set(inc_v) == set(ref_v)
        assert inc_v[: len(appended["before_vocab"]["department"])] == appended[
            "before_vocab"
        ]["department"]

    def test_jax_dataset_consumes_appended_chunks(self, appended):
        from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig

        ds = JaxDataset(
            PytorchDatasetConfig(
                save_dir=Path(appended["ESD"].config.save_dir), max_seq_len=16, min_seq_len=2
            ),
            "train",
        )
        new_ids = set(appended["info"]["subject_ids"])
        assert new_ids <= set(ds.subject_ids), "appended subjects must reach the feed"

    def test_append_after_reload_from_disk(self, appended):
        """The production path: the sidecars (frozen layout in E.pkl, stats
        in preprocessor_sufficient_stats.json) must round-trip through
        save()/load() so a later session can append."""
        root = appended["root"]
        raw_c = write_synthetic_raw_csvs(root / "raw_c", n_subjects=6, seed=21)
        save2 = root / "proc2" / "sample"
        ESD2 = build_dataset(appended["raw_a"], save2)
        del ESD2
        loaded = Dataset.load(save2)
        assert loaded._frozen_vocab is not None
        assert loaded._preproc_stats is not None
        # A stray non-chunk parquet (no numeric suffix) must be skipped by
        # the next-chunk scan, not crash it.
        pd.DataFrame({"x": [1]}).to_parquet(save2 / "DL_reps" / "zzz.parquet")
        info = loaded.append_subjects(make_schema(raw_c), split="train")
        assert info["subject_ids"] and all(p.exists() for p in info["chunk_paths"])

    def test_reingesting_existing_subjects_is_rejected(self, appended):
        """A raw subject key already in the dataset must not silently mint a
        second numeric subject with half a history."""
        with pytest.raises(ValueError, match="already\\s+exist"):
            appended["ESD"].append_subjects(make_schema(appended["raw_a"]), split="train")

    def test_frozen_transform_configs_survive_reload_resort(self, appended):
        """Vocabulary.__post_init__ re-sorts by merged frequency on load, so
        the live element order stops extending the snapshot; the frozen
        transform configs must rebuild from the SNAPSHOT, keeping exactly
        the fit-time element set in the fit-time order."""
        reloaded = Dataset.load(Path(appended["ESD"].config.save_dir))
        frozen = reloaded._frozen_vocab["measurement_vocabs"]["department"]
        cfgs = reloaded._frozen_transform_configs()
        assert cfgs["department"].vocabulary.vocabulary == list(frozen)
        assert frozen == appended["before_vocab"]["department"]

    def test_replayed_batch_rejected_after_reload(self, appended):
        """append persists its fit state by default (do_save=True), so a
        RELOADED dataset still rejects the same batch — a retried ingestion
        job cannot double-admit subjects."""
        reloaded = Dataset.load(Path(appended["ESD"].config.save_dir))
        with pytest.raises(ValueError, match="already\\s+exist"):
            reloaded.append_subjects(make_schema(appended["raw_b"]), split="train")

    def test_append_requires_stats_sidecar(self, appended, tmp_path):
        ESD = appended["ESD"]
        stats, ESD._preproc_stats = ESD._preproc_stats, None
        try:
            with pytest.raises(ValueError, match="sufficient statistics"):
                ESD._update_fit_from_shard(ESD)
        finally:
            ESD._preproc_stats = stats


# ------------------------------------------------- slow: online admission
@pytest.mark.slow
class TestOnlineAdmission:
    @pytest.fixture(scope="class")
    def stack(self, tmp_path_factory):
        import jax

        from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
        from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
        from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
        from eventstreamgpt_tpu.serving import GenerationEngine

        root = tmp_path_factory.mktemp("ingest_etl")
        raw = write_synthetic_raw_csvs(root / "raw", n_subjects=40, seed=3)
        ESD = build_dataset(raw, root / "proc" / "sample")

        # One surviving subject's raw rows, re-streamed as "live" input.
        batch_rep = ESD.build_DL_cached_representation()
        target = int(sorted(batch_rep["subject_id"].dropna().astype(int))[0])
        subjects = pd.read_csv(raw / "subjects.csv")
        adm = pd.read_csv(raw / "admit_vitals.csv")
        mrn = subjects["MRN"].iloc[target]
        raw_one = root / "raw_one"
        raw_one.mkdir()
        subjects[subjects["MRN"] == mrn].to_csv(raw_one / "subjects.csv", index=False)
        adm[adm["MRN"] == mrn].to_csv(raw_one / "admit_vitals.csv", index=False)

        ds = JaxDataset(
            PytorchDatasetConfig(
                save_dir=Path(ESD.config.save_dir),
                max_seq_len=8,
                min_seq_len=2,
                do_include_start_time_min=True,
            ),
            "train",
        )
        cfg = StructuredTransformerConfig(
            hidden_size=32,
            head_dim=8,
            num_attention_heads=4,
            num_hidden_layers=2,
            intermediate_size=32,
            TTE_generation_layer_type="log_normal_mixture",
            TTE_lognormal_generation_num_components=2,
        )
        cfg.set_to_dataset(ds)
        model = CIPPTForGenerativeSequenceModeling(cfg)
        template = next(ds.batches(2, shuffle=False))
        params = model.init(jax.random.PRNGKey(0), template)
        engine = GenerationEngine(
            model, params, cfg, template=template, n_slots=2, max_len=8,
            decode_chunk=2, min_bucket=2,
            # This stack tests the ingest→engine loop, not serving
            # numerics: the UNTRAINED toy model's log-normal-mixture TTE
            # head legitimately samples inf at init, which the decode
            # health sentinel would (correctly) quarantine as a poisoned
            # slot — docs/reliability.md "Serving failure domains".
            health_sentinel=False,
        )
        return dict(
            ESD=ESD, raw_one=raw_one, target=target, mrn=mrn,
            batch_rep=batch_rep, template=template, engine=engine,
        )

    @staticmethod
    def _norm(x):
        if isinstance(x, np.ndarray):
            x = x.tolist()
        if isinstance(x, (list, tuple)):
            return [TestOnlineAdmission._norm(e) for e in x]
        # repr-normalize scalars so nan == nan and 1.0 (float) == 1.0
        # (np.float64) — the comparison is about VALUES being bit-identical.
        return repr(float(x)) if isinstance(x, (float, np.floating)) else repr(x)

    def test_transform_bit_identical_to_batch_etl(self, stack):
        from eventstreamgpt_tpu.serving.ingest import OnlineIngester

        ing = OnlineIngester(stack["ESD"], max_n_dynamic=8)
        subs = ing.ingest(make_schema(stack["raw_one"]))
        assert len(subs) == 1 and subs[0].subject_key == str(stack["mrn"])

        row_batch = stack["batch_rep"][
            stack["batch_rep"]["subject_id"] == stack["target"]
        ].iloc[0]
        row_online = subs[0].dl_row
        for col in (
            "time",
            "dynamic_measurement_indices",
            "dynamic_indices",
            "dynamic_values",
            "static_measurement_indices",
            "static_indices",
        ):
            assert self._norm(row_batch[col]) == self._norm(row_online[col]), (
                f"online-admission {col} differs from the batch ETL's"
            )
        assert pd.Timestamp(row_batch["start_time"]) == pd.Timestamp(row_online["start_time"])

    def test_raw_events_to_generated_continuation(self, stack):
        from eventstreamgpt_tpu.serving.ingest import OnlineIngester

        ing = OnlineIngester.from_template(
            stack["ESD"], stack["template"], max_prompt_events=4
        )
        reqs = ing.requests(make_schema(stack["raw_one"]), max_new_events=3)
        assert len(reqs) == 1
        prompt = reqs[0].prompt
        assert prompt.batch_size == 1 and prompt.sequence_length == 4
        assert (
            prompt.dynamic_indices.shape[-1]
            == stack["template"].dynamic_indices.shape[-1]
        )
        results = stack["engine"].run(reqs)
        assert len(results) == 1
        r = results[0]
        assert r.request_id == str(stack["mrn"])
        assert r.n_generated == 3, "the admitted stream must generate its continuation"

    def test_prompt_matches_template_widths(self, stack):
        from eventstreamgpt_tpu.serving.ingest import OnlineIngester

        ing = OnlineIngester.from_template(stack["ESD"], stack["template"])
        subs = ing.ingest(make_schema(stack["raw_one"]))
        t = stack["template"]
        assert subs[0].prompt.dynamic_indices.shape[-1] == t.dynamic_indices.shape[-1]
        assert subs[0].prompt.static_indices.shape[-1] == t.static_indices.shape[-1]

    def test_static_free_template_yields_static_free_prompts(self, stack):
        """A template without static fields must produce prompts without
        them — a structural mismatch would fail the engine's slot-state
        tree_map at admission."""
        from eventstreamgpt_tpu.serving.ingest import OnlineIngester

        bare = stack["template"].replace(
            static_indices=None, static_measurement_indices=None
        )
        ing = OnlineIngester.from_template(stack["ESD"], bare)
        subs = ing.ingest(make_schema(stack["raw_one"]))
        assert subs[0].prompt.static_indices is None
        assert subs[0].prompt.static_measurement_indices is None

    def test_dirty_stream_produces_typed_rejections_not_poisoned_prompts(
        self, stack, monkeypatch
    ):
        """Admission hardening (ISSUE 15): malformed / non-finite raw event
        values produce a per-request typed rejection — counted in the
        ingester's `padding_report` — instead of entering a prefill and
        poisoning a decode slot. The dirty stream here corrupts the
        transformed rep (an inf observed value on one subject, a NaN event
        time on another path of the same subject re-run) at the one point
        every raw corruption funnels through."""
        from eventstreamgpt_tpu.serving.ingest import OnlineIngester

        ing = OnlineIngester.from_template(stack["ESD"], stack["template"])
        schema = make_schema(stack["raw_one"])

        real_transform = OnlineIngester.transform

        def dirty_values(self, input_schema):
            shard, rep, id_map = real_transform(self, input_schema)
            for i in rep.index:
                vals = rep.at[i, "dynamic_values"]
                if not np.isscalar(vals):
                    vals[0][0] = float("inf")  # an observed value gone bad
                    break
            return shard, rep, id_map

        monkeypatch.setattr(OnlineIngester, "transform", dirty_values)
        subs = ing.ingest(schema)
        assert subs == []  # the dirty subject never became a prompt
        assert len(ing.rejections) == 1
        rej = ing.rejections[0]
        assert "non-finite" in rej.reason
        from eventstreamgpt_tpu.serving import MalformedPromptRejected

        assert isinstance(rej.error, MalformedPromptRejected)
        report = ing.padding_report()
        assert report["malformed_rejected_total"] == 1
        assert report["admitted_subjects"] == 0
        assert ing.requests(schema, max_new_events=4) == []

        # NaN event times reject the same way (second corruption mode).
        def dirty_times(self, input_schema):
            shard, rep, id_map = real_transform(self, input_schema)
            for i in rep.index:
                times = rep.at[i, "time"]
                if not np.isscalar(times):
                    times[0] = float("nan")
                    break
            return shard, rep, id_map

        monkeypatch.setattr(OnlineIngester, "transform", dirty_times)
        assert ing.ingest(schema) == []
        assert len(ing.rejections) == 3  # +1 from the requests() call above
        assert "time" in ing.rejections[-1].reason

        # And the clean stream still admits through the SAME ingester.
        monkeypatch.setattr(OnlineIngester, "transform", real_transform)
        clean = ing.ingest(schema)
        assert len(clean) == 1
        assert ing.padding_report()["admitted_subjects"] == 1
