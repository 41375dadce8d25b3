"""Host-side dataset over the DL cache, feeding static-shape device batches.

TPU-native rebuild of ``/root/reference/EventStream/data/pytorch_dataset.py``.
Behavioral parity: reads ``DL_reps/{split}*.parquet`` plus
``vocabulary_config.json`` / ``inferred_measurement_configs.json`` artifacts
(including those produced by the reference itself — pandas/pyarrow replaces
Polars), converts absolute times to deltas (next-event minus current, last
filled with 1; ``pytorch_dataset.py:245-256``), computes inter-event-time
statistics and quarantines malformed subjects (``:258-287``), restricts to
task windows (``:311-459``), samples subsequences per the configured strategy
(``:471-520``), and collates with right/left padding into an
`EventStreamBatch` (``:527-683``).

The *representation* diverges deliberately (SURVEY.md §7.3): instead of
per-subject Python lists padded in a per-item loop (the reference's known CPU
bottleneck), events are held as contiguous CSR-style numpy arrays (values +
offsets), taken at load time straight from the parquet file's Arrow list
offsets and flat child arrays: no Python object is made per event. Collation
is then a handful of vectorized gathers into **static-shape**
``(B, max_seq_len, max_n_dynamic)`` buffers, so XLA compiles the training step
exactly once and the host never bottlenecks the chip.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from ..utils import SeedableMixin
from ..utils.scopes import host_span
from .config import (
    MeasurementConfig,
    PytorchDatasetConfig,
    SeqPaddingSide,
    SubsequenceSamplingStrategy,
    VocabularyConfig,
)
from .types import EventStreamBatch


def to_int_index(col: pd.Series) -> tuple[pd.Series, list]:
    """Maps string/categorical labels to integer indices (sorted unique order).

    Reference: ``pytorch_dataset.py:22-55`` (polars ``to_int_index``).
    """
    vocab = sorted(col.dropna().unique().tolist())
    mapping = {v: i for i, v in enumerate(vocab)}
    return col.map(mapping), vocab


@dataclasses.dataclass
class BatchPlan:
    """The host-decided, rng-dependent part of one batch (~100 bytes).

    Produced by `JaxDataset.plan_batches`; consumed by host collation
    (`JaxDataset.batches`) and on-device collation
    (`DeviceDataset <device_dataset.DeviceDataset>`) identically.
    """

    subject_indices: np.ndarray  # (B,) int32
    starts: np.ndarray  # (B,) int32 — subsequence crop start per subject
    kept: np.ndarray  # (B,) int32 — events kept (min(seq_len, L))
    valid_mask: np.ndarray  # (B,) bool — False for cyclic fill rows
    n_events: int  # real (non-fill, non-pad) events in the batch
    start_time: np.ndarray | None = None  # (B,) float32, when configured


@dataclasses.dataclass
class _CSRData:
    """Flattened ragged event data for one split.

    ``event_*`` arrays are indexed by global event id; ``data_*`` by global
    data-element id. ``subject_event_offsets[i] : subject_event_offsets[i+1]``
    is subject ``i``'s event range.

    Collation-speed layout choices (the host is the system bottleneck at
    ~0.3 ms device steps): values are stored **NaN-cleaned** with a separate
    observed mask, so the per-batch hot path is pure gathers — no
    ``isnan``/``nan_to_num`` passes; offset/index arrays are int32 whenever
    sizes permit, halving index-arithmetic memory traffic.
    """

    subject_event_offsets: np.ndarray  # (n_subjects + 1,) int
    time_delta: np.ndarray  # (n_events,) float32
    event_data_offsets: np.ndarray  # (n_events + 1,) int
    dynamic_indices: np.ndarray  # (n_data,) int
    dynamic_measurement_indices: np.ndarray  # (n_data,) int
    dynamic_values: np.ndarray  # (n_data,) float32, 0 where unobserved
    dynamic_values_observed: np.ndarray  # (n_data,) bool
    static_offsets: np.ndarray  # (n_subjects + 1,) int
    static_indices: np.ndarray  # (n_static,) int
    static_measurement_indices: np.ndarray  # (n_static,) int
    start_time_min: np.ndarray  # (n_subjects,) float64 (minutes since epoch)

    @property
    def n_subjects(self) -> int:
        return len(self.subject_event_offsets) - 1

    def n_events(self, i: int) -> int:
        return int(self.subject_event_offsets[i + 1] - self.subject_event_offsets[i])


# ------------------------------------------------ Arrow lists -> flat arrays
def _unnest(col: pa.ChunkedArray) -> tuple[np.ndarray, pa.ChunkedArray]:
    """A list column's lengths (0 for a null list) and its values one level
    down. ``list`` and ``large_list`` alike; a column of Arrow's null type (an
    empty frame written by pandas) holds no list."""
    if pa.types.is_null(col.type):
        return np.zeros(len(col), np.int64), pa.chunked_array([], pa.null())
    lengths = pc.list_value_length(col).fill_null(0).to_numpy().astype(np.int64)
    return lengths, pc.list_flatten(col)


def _ints(values: pa.ChunkedArray, name: str) -> np.ndarray:
    """The values as stored (possibly a read-only view: `_shrink` copies)."""
    if values.null_count:
        raise ValueError(f"{name} holds {values.null_count} null elements")
    return values.to_numpy()


def _floats(values: pa.ChunkedArray, dtype) -> np.ndarray:
    """The values in ``dtype``, NaN where an element is null (possibly a
    read-only view)."""
    if pa.types.is_null(values.type):
        return np.full(len(values), np.nan, dtype)
    return values.to_numpy().astype(dtype, copy=False)


def _offsets(lengths: np.ndarray) -> np.ndarray:
    out = np.zeros(len(lengths) + 1, np.int64)
    np.cumsum(lengths, out=out[1:])
    return out


def _shrink(x: np.ndarray) -> np.ndarray:
    """An owned int32 copy when the values fit, else int64 (collation index
    arithmetic is memory-bound; half-width indices halve the traffic)."""
    if x.size == 0 or (x.min() >= np.iinfo(np.int32).min and x.max() <= np.iinfo(np.int32).max):
        return x.astype(np.int32)
    return x.astype(np.int64)


def _segments(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Positions of the runs ``starts[i] : starts[i] + lengths[i]``, one
    after another."""
    ends = np.cumsum(lengths)
    return np.arange(ends[-1] if len(ends) else 0) + np.repeat(starts - (ends - lengths), lengths)


def _aligned(values: np.ndarray, own: np.ndarray, target: np.ndarray, name: str, fill=None) -> np.ndarray:
    """``values``, one run of ``own[e]`` elements an event, laid out as runs
    of ``target[e]``: an event's own run where the two agree, nothing where
    the target is empty and, with a ``fill``, runs of it where the event holds
    no elements (a null list beside non-empty indices)."""
    if np.array_equal(own, target):
        return values
    ok = (own == target) | (target == 0)
    if fill is not None:
        ok |= own == 0
    if not ok.all():
        e = int(np.flatnonzero(~ok)[0])
        raise ValueError(f"event {e} holds {own[e]} {name} for {target[e]} indices")
    take = (own == target) & (target > 0)
    src = _segments((np.cumsum(own) - own)[take], target[take])
    if fill is None:  # every event with elements is taken
        return values[src]
    out = np.full(int(target.sum()), fill, values.dtype)
    out[_segments((np.cumsum(target) - target)[take], target[take])] = values[src]
    return out


class JaxDataset(SeedableMixin):
    """A dataset over the cached DL representation, yielding numpy batches.

    API mirrors the reference ``PytorchDataset`` (``pytorch_dataset.py:58``):
    ``len``, ``__getitem__`` → per-subject dict, ``collate`` → batch; plus a
    vectorized `collate_indices` fast path used by `batches`.
    """

    TASK_TYPES = {"multi_class_classification", "binary_classification", "regression"}

    @classmethod
    def normalize_task(cls, col: pd.Series) -> tuple[str, pd.Series, list | None]:
        """Infers task type and normalizes labels (``pytorch_dataset.py:108``)."""
        dtype = col.dtype
        if pd.api.types.is_bool_dtype(dtype):
            return "binary_classification", col.astype(np.float32), [False, True]
        if pd.api.types.is_integer_dtype(dtype):
            return "multi_class_classification", col, list(range(int(col.max()) + 1))
        if pd.api.types.is_float_dtype(dtype):
            return "regression", col, None
        if isinstance(dtype, pd.CategoricalDtype) or pd.api.types.is_object_dtype(dtype):
            normalized, vocab = to_int_index(col)
            return "multi_class_classification", normalized, vocab
        raise TypeError(f"Can't process label of {dtype} type!")

    def __init__(self, config: PytorchDatasetConfig, split: str):
        super().__init__()
        with host_span("startup/dataset_read", id="startup") as span:
            self._read(config, split)
            d = self.data
            span.counts.update(subjects=d.n_subjects, events=len(d.time_delta), data=len(d.dynamic_indices))

    def _read(self, config: PytorchDatasetConfig, split: str) -> None:
        self.config = config
        self.split = split
        self.task_types: dict[str, str] = {}
        self.task_vocabs: dict[str, list] = {}

        save_dir = Path(config.save_dir)
        self.vocabulary_config = VocabularyConfig.from_json_file(save_dir / "vocabulary_config.json")

        with open(save_dir / "inferred_measurement_configs.json") as f:
            inferred = {
                k: MeasurementConfig.from_dict(v, base_dir=save_dir)
                for k, v in json.load(f).items()
            }
        self.measurement_configs = {k: v for k, v in inferred.items() if not v.is_dropped}

        if config.task_df_name is not None:
            self.has_task = True
            table, self.tasks = self._load_task_data(save_dir, config.task_df_name, split)
        else:
            self.has_task = False
            self.tasks = None
            self.task_vocabs = None
            table = self._read_dl_reps(save_dir / "DL_reps", split)

        self.do_produce_static_data = "static_indices" in table.column_names
        self.seq_padding_side = config.seq_padding_side
        self.max_seq_len = config.max_seq_len

        # Every subject of the files, in file order, as flat arrays.
        lists = [c for c in table.column_names if pa.types.is_list(t := table.schema.field(c).type) or pa.types.is_large_list(t)]
        subjects = table.drop_columns(lists).to_pandas().reset_index(drop=True)
        n_subjects = len(subjects)
        counts, events = _unnest(table["dynamic_indices"])
        idx_len, idx = _unnest(events)
        meas_counts, events = _unnest(table["dynamic_measurement_indices"])
        meas_len, meas = _unnest(events)
        val_counts, events = _unnest(table["dynamic_values"])
        val_len, vals = _unnest(events)
        time_col = "time_delta" if "time_delta" in table.column_names else "time"
        time_counts, times = _unnest(table[time_col])
        for name, other in (("dynamic_measurement_indices", meas_counts), ("dynamic_values", val_counts), (time_col, time_counts)):
            if not np.array_equal(other, counts):
                raise ValueError(f"{name} holds other event counts than dynamic_indices")
        # A null index or measurement list is an empty event; values follow
        # the indices, NaN where an event's value list is null.
        per_event = np.where((idx_len == 0) | (meas_len == 0), 0, idx_len)
        dyn_idx = _aligned(_ints(idx, "dynamic_indices"), idx_len, per_event, "dynamic_indices")
        dyn_meas = _aligned(_ints(meas, "dynamic_measurement_indices"), meas_len, per_event, "measurements")
        raw_vals = _aligned(_floats(vals, np.float32), val_len, per_event, "values", fill=np.nan)

        ev_offsets = _offsets(counts)
        ev_subject = np.repeat(np.arange(n_subjects), counts)
        # An event's delta is real (not the 1.0 filler) when its subject has a next event.
        real = np.ones(len(ev_subject), bool)
        real[ev_offsets[1:][counts > 0] - 1] = False
        if time_col == "time_delta":
            deltas = times.to_numpy()  # as stored: the statistics read them so
            time_delta = deltas.astype(np.float32)
        else:
            # ``time`` (absolute minutes) -> minutes to the next event, 1 at a
            # subject's last (``pytorch_dataset.py:245-256``).
            t = _floats(times, np.float64)  # graftcheck: allow GC002 -- host-side: absolute minutes, differenced in float64 as the reference does
            time_delta = np.empty(len(t), np.float32)
            time_delta[:-1] = (t[1:] - t[:-1]).astype(np.float32)
            time_delta[~real] = 1.0
            deltas = time_delta
            if "start_time" in subjects.columns:
                # start_time advances to the first event's absolute time.
                first = np.zeros(n_subjects)
                first[counts > 0] = t[ev_offsets[:-1][counts > 0]]
                subjects["start_time"] = pd.to_datetime(subjects["start_time"]) + pd.to_timedelta(
                    pd.Series(first, index=subjects.index), unit="m"
                )

        # Filter short sequences.
        keep = counts >= config.min_seq_len

        # Inter-event-time stats + malformed-subject quarantine
        # (reference ``pytorch_dataset.py:258-287``), over the real deltas of
        # the kept subjects, in their order.
        all_deltas = deltas[real & keep[ev_subject]]
        if len(all_deltas) == 0:
            all_deltas = np.asarray([1.0])
        min_delta = float(all_deltas.min())
        if min_delta <= 0:
            with np.errstate(invalid="ignore"):
                bad = keep & (np.bincount(ev_subject[real & (deltas <= 0)], minlength=n_subjects) > 0)
            print(
                f"WARNING: Observed inter-event times <= 0 for {int(bad.sum())} subjects!\n"
                f"ESD Subject IDs: {', '.join(str(x) for x in subjects['subject_id'][bad].tolist())}\n"
                f"Global min: {min_delta}"
            )
            if config.save_dir is not None:
                fp = Path(config.save_dir) / f"malformed_data_{split}.parquet"
                self._malformed_rows(table, subjects, np.flatnonzero(bad), keep, time_col, time_delta, ev_offsets).to_parquet(fp)
                print(f"Wrote malformed data records to {fp}")
            print("Removing malformed subjects")
            keep &= ~bad
            all_deltas = deltas[real & keep[ev_subject]]

        with np.errstate(divide="ignore", invalid="ignore"):
            logs = np.log(all_deltas[all_deltas > 0])
        self.mean_log_inter_event_time_min = float(logs.mean()) if len(logs) else 0.0
        self.std_log_inter_event_time_min = float(logs.std(ddof=1)) if len(logs) > 1 else 1.0

        sel = np.flatnonzero(keep)
        # Train-subset subsampling (``pytorch_dataset.py:291-303``): the rows
        # ``DataFrame.sample`` would pick from the kept subjects.
        if config.train_subset_size not in (None, "FULL") and split == "train":
            if isinstance(config.train_subset_size, int) and config.train_subset_size > 0:
                n = min(config.train_subset_size, len(sel))
            elif isinstance(config.train_subset_size, float) and 0 < config.train_subset_size < 1:
                n = int(round(config.train_subset_size * len(sel)))
            else:
                raise TypeError(
                    f"Can't process subset size of {type(config.train_subset_size)}, "
                    f"{config.train_subset_size}"
                )
            sel = sel[pd.Series(np.arange(len(sel))).sample(n=n, random_state=config.train_subset_seed).to_numpy()]

        subjects = subjects.iloc[sel].reset_index(drop=True)
        self.subject_ids = subjects["subject_id"].tolist()
        self.stream_labels = (
            {t: np.asarray(subjects[t].to_numpy()) for t in self.tasks} if self.has_task else None
        )

        if self.do_produce_static_data:
            st_counts, st = _unnest(table["static_indices"])
            st_meas_counts, st_meas = _unnest(table["static_measurement_indices"])
            if not np.array_equal(st_meas_counts, st_counts):
                raise ValueError("static_measurement_indices holds other counts than static_indices")
            st_idx, st_meas = _ints(st, "static_indices"), _ints(st_meas, "static_measurement_indices")
        else:
            st_counts, st_idx, st_meas = np.zeros(n_subjects, np.int64), np.zeros(0, np.int64), np.zeros(0, np.int64)

        if not np.array_equal(sel, np.arange(n_subjects)):
            ev = _segments(ev_offsets[:-1][sel], counts[sel])
            data = _segments(_offsets(per_event)[:-1][ev], per_event[ev])
            counts, time_delta, per_event = counts[sel], time_delta[ev], per_event[ev]
            dyn_idx, dyn_meas, raw_vals = dyn_idx[data], dyn_meas[data], raw_vals[data]
            st = _segments(_offsets(st_counts)[:-1][sel], st_counts[sel])
            st_counts, st_idx, st_meas = st_counts[sel], st_idx[st], st_meas[st]

        if "start_time" in subjects.columns:
            start_time_min = (
                pd.to_datetime(subjects["start_time"]).map(lambda t: t.timestamp() / 60.0).to_numpy(copy=True)
            )
        else:
            start_time_min = np.zeros(len(subjects), dtype=np.float64)  # graftcheck: allow GC002 -- host-side minutes since the epoch

        observed = ~np.isnan(raw_vals)
        self.data = _CSRData(
            subject_event_offsets=_shrink(_offsets(counts)),
            time_delta=time_delta,
            event_data_offsets=_shrink(_offsets(per_event)),
            dynamic_indices=_shrink(dyn_idx),
            dynamic_measurement_indices=_shrink(dyn_meas),
            dynamic_values=np.where(observed, raw_vals, np.float32(0.0)),
            dynamic_values_observed=observed,
            static_offsets=_shrink(_offsets(st_counts)),
            static_indices=_shrink(st_idx),
            static_measurement_indices=_shrink(st_meas),
            start_time_min=start_time_min,
        )

        # Static data-element axis sizes for shape-stable collation.
        data_lens = np.diff(self.data.event_data_offsets)
        inferred_max_n = int(data_lens.max()) if len(data_lens) else 1
        self.max_n_dynamic = config.max_n_dynamic or max(inferred_max_n, 1)
        static_lens = np.diff(self.data.static_offsets)
        self.max_n_static = config.max_n_static or max(int(static_lens.max()) if len(static_lens) else 1, 1)

    # ------------------------------------------------------------------ I/O
    @staticmethod
    def _read_dl_reps(dl_dir: Path, split: str) -> pa.Table:
        """The split's chunk files as one Arrow table, lists kept as Arrow
        lists (``list`` or ``large_list``, as written)."""
        # Chunk order is load-bearing (subject order feeds the deterministic
        # batch stream); `append_subjects` grows chunk counts past 9, where
        # lexicographic sorting would interleave ("x_10" < "x_2") and shuffle
        # subjects between runs — so order numerically by the chunk suffix.
        def chunk_key(fp: Path):
            stem, _, suffix = fp.stem.rpartition("_")
            return (stem, int(suffix)) if suffix.isdigit() else (fp.stem, -1)

        files = sorted(Path(dl_dir).glob(f"{split}*.parquet"), key=chunk_key)
        if not files:
            raise FileNotFoundError(f"No DL_reps parquet files for split {split} in {dl_dir}")
        return pa.concat_tables([pq.read_table(fp) for fp in files], promote_options="permissive")

    @staticmethod
    def _malformed_rows(table, subjects, rows, kept, time_col, time_delta, ev_offsets) -> pd.DataFrame:
        """The quarantined subjects' rows as the reference writes them: the
        file's columns with ``time`` turned into ``time_delta`` (appended last)
        and ``start_time`` advanced, indexed by position among the subjects
        that passed ``min_seq_len``."""
        frame = table.take(rows).to_pandas()
        frame.index = pd.RangeIndex(int(kept.sum())).take((np.cumsum(kept) - 1)[rows])
        if time_col == "time":
            frame["time_delta"] = pd.Series(
                [time_delta[ev_offsets[s] : ev_offsets[s + 1]] for s in rows], index=frame.index, dtype=object
            )
            if "start_time" in frame.columns:
                frame["start_time"] = subjects["start_time"].iloc[rows].set_axis(frame.index)
            frame = frame.drop(columns=["time"])
        return frame

    def _load_task_data(self, save_dir: Path, task_df_name: str, split: str):
        """Task-restricted data loading (``pytorch_dataset.py:149-236``)."""
        task_dir = save_dir / "DL_reps" / "for_task" / task_df_name
        raw_task_df_fp = save_dir / "task_dfs" / f"{task_df_name}.parquet"
        task_info_fp = task_dir / "task_info.json"

        if any(task_dir.glob(f"{split}*.parquet")):
            table = self._read_dl_reps(task_dir, split)
            with open(task_info_fp) as f:
                task_info = json.load(f)
            tasks = sorted(task_info["tasks"])
            self.task_vocabs = task_info["vocabs"]
            self.task_types = task_info["types"]
            return table, tasks

        if not raw_task_df_fp.is_file():
            raise FileNotFoundError(
                f"Neither {task_dir} nor {raw_task_df_fp} exist, but config.task_df_name = "
                f"{task_df_name}!"
            )

        task_df = pd.read_parquet(raw_task_df_fp)
        tasks = sorted(c for c in task_df.columns if c not in ("subject_id", "start_time", "end_time"))
        for t in tasks:
            task_type, normalized, vocab = self.normalize_task(task_df[t])
            self.task_types[t] = task_type
            task_df[t] = normalized
            if vocab is not None:
                self.task_vocabs[t] = vocab

        task_info = {"tasks": sorted(tasks), "vocabs": self.task_vocabs, "types": self.task_types}
        if task_info_fp.is_file():
            with open(task_info_fp) as f:
                loaded = json.load(f)
            if loaded != task_info and split != "train":
                raise ValueError(
                    f"Task info differs from on disk!\nDisk:\n{loaded}\nLocal:\n{task_info}\n"
                    f"Split: {split}"
                )
        else:
            task_info_fp.parent.mkdir(exist_ok=True, parents=True)
            with open(task_info_fp, mode="w") as f:
                json.dump(task_info, f)

        for cached_fp in sorted((save_dir / "DL_reps").glob(f"{split}*.parquet")):
            out_fp = task_dir / cached_fp.name
            if out_fp.is_file():
                continue
            restricted = self._build_task_cached_df(task_df, pd.read_parquet(cached_fp))
            out_fp.parent.mkdir(exist_ok=True, parents=True)
            restricted.to_parquet(out_fp)

        return self._read_dl_reps(task_dir, split), tasks

    @staticmethod
    def _build_task_cached_df(task_df: pd.DataFrame, cached_data: pd.DataFrame) -> pd.DataFrame:
        """Slices each subject's event lists to task ``[start, end]`` windows.

        Reference: ``pytorch_dataset.py:311-459`` (searchsorted over absolute
        event times per task row).
        """
        # Window bounds computed vectorized up front; the remaining per-row
        # work is ragged-list slicing, done over plain numpy/python objects
        # (no pandas row objects) so host cost stays linear in task rows with
        # small constants (the previous iterrows version was
        # pandas-overhead-bound at MIMIC scale).
        cached = cached_data.set_index("subject_id")
        in_cache = task_df["subject_id"].isin(cached.index)
        tdf = task_df[in_cache].reset_index(drop=True)
        empty = pd.DataFrame(
            columns=list(cached_data.columns)
            + [c for c in task_df.columns if c not in ("subject_id", "start_time", "end_time")]
        )
        if not len(tdf):
            return empty

        sids = tdf["subject_id"].to_numpy()
        # Lookups only over subjects the task actually references: a small
        # task cohort must not pay per-subject conversion for a whole chunk.
        cached = cached.loc[np.unique(sids)]
        base_start = cached["start_time"].reindex(sids).to_numpy(dtype="datetime64[ns]")
        start_min = (
            tdf["start_time"].to_numpy(dtype="datetime64[ns]") - base_start
        ) / np.timedelta64(1, "m")
        end_min = (
            tdf["end_time"].to_numpy(dtype="datetime64[ns]") - base_start
        ) / np.timedelta64(1, "m")

        times_by_sid = {sid: np.asarray(t, dtype=np.float64) for sid, t in cached["time"].items()}
        col_by_sid = {
            c: cached[c].to_dict()
            for c in ("dynamic_indices", "dynamic_measurement_indices", "dynamic_values")
        }
        static_cols = [
            c for c in ("static_indices", "static_measurement_indices") if c in cached_data.columns
        ]
        static_by_sid = {c: cached[c].to_dict() for c in static_cols}
        label_cols = [c for c in task_df.columns if c not in ("subject_id", "start_time", "end_time")]
        labels = {t: tdf[t].to_numpy() for t in label_cols}

        rows = []
        for i in range(len(tdf)):
            sid = sids[i]
            times = times_by_sid[sid]
            lo = int(np.searchsorted(times, start_min[i], side="left"))
            hi = int(np.searchsorted(times, end_min[i], side="right"))
            if hi <= lo:
                continue
            new_row = {
                "subject_id": sid,
                "start_time": pd.Timestamp(base_start[i]) + pd.Timedelta(minutes=float(times[lo])),
                "time": times[lo:hi] - times[lo],
            }
            for c in ("dynamic_indices", "dynamic_measurement_indices", "dynamic_values"):
                new_row[c] = np.asarray(col_by_sid[c][sid][lo:hi], dtype=object)
            for c in static_cols:
                new_row[c] = static_by_sid[c][sid]
            for t in label_cols:
                new_row[t] = labels[t][i]
            rows.append(new_row)
        # All-windows-empty must still return the full column schema.
        return pd.DataFrame(rows) if rows else empty

    # ----------------------------------------------------------- item access
    def __len__(self) -> int:
        return self.data.n_subjects

    def _sample_start_idx(self, seq_len: int, rng: np.random.Generator) -> int:
        if seq_len <= self.max_seq_len:
            return 0
        strategy = self.config.subsequence_sampling_strategy
        if strategy == SubsequenceSamplingStrategy.RANDOM:
            return int(rng.integers(0, seq_len - self.max_seq_len))
        if strategy == SubsequenceSamplingStrategy.TO_END:
            return seq_len - self.max_seq_len
        if strategy == SubsequenceSamplingStrategy.FROM_START:
            return 0
        raise ValueError(f"Invalid sampling strategy: {strategy}!")

    def __getitem__(self, idx: int) -> dict:
        return self._seeded_getitem(idx)

    @SeedableMixin.WithSeed
    def _seeded_getitem(self, idx: int) -> dict:
        """Per-subject ragged dict, as in the reference ``__getitem__``."""
        d = self.data
        rng = np.random.default_rng(np.random.randint(0, 2**31))
        ev_lo, ev_hi = d.subject_event_offsets[idx], d.subject_event_offsets[idx + 1]
        seq_len = int(ev_hi - ev_lo)
        start_idx = self._sample_start_idx(seq_len, rng)
        end_idx = min(start_idx + self.max_seq_len, seq_len)

        events = np.arange(ev_lo + start_idx, ev_lo + end_idx)
        def nan_vals(e):
            sl = slice(d.event_data_offsets[e], d.event_data_offsets[e + 1])
            return np.where(d.dynamic_values_observed[sl], d.dynamic_values[sl], np.nan).tolist()

        out = {
            "time_delta": d.time_delta[events].tolist(),
            "dynamic_indices": [
                d.dynamic_indices[d.event_data_offsets[e] : d.event_data_offsets[e + 1]].tolist()
                for e in events
            ],
            "dynamic_measurement_indices": [
                d.dynamic_measurement_indices[
                    d.event_data_offsets[e] : d.event_data_offsets[e + 1]
                ].tolist()
                for e in events
            ],
            "dynamic_values": [nan_vals(e) for e in events],
        }
        if self.do_produce_static_data:
            st_lo, st_hi = d.static_offsets[idx], d.static_offsets[idx + 1]
            out["static_indices"] = d.static_indices[st_lo:st_hi].tolist()
            out["static_measurement_indices"] = d.static_measurement_indices[st_lo:st_hi].tolist()
        if self.config.do_include_subject_id:
            out["subject_id"] = self.subject_ids[idx]
        if self.config.do_include_start_time_min:
            out["start_time"] = float(
                d.start_time_min[idx] + d.time_delta[ev_lo : ev_lo + start_idx].sum()
            )
        if self.config.do_include_subsequence_indices:
            out["start_idx"] = start_idx
            out["end_idx"] = end_idx
        if self.has_task:
            for t in self.tasks:
                out[t] = self.stream_labels[t][idx]
        return out

    # ------------------------------------------------------------- collation
    def _draw_starts(
        self, subject_indices: np.ndarray, rng: np.random.Generator
    ) -> tuple[np.ndarray, np.ndarray]:
        """Draws subsequence crop starts for the given subjects.

        The single point where collation consumes randomness — shared by
        `collate_indices`, the resume fast-forward, and the device-resident
        plan stream (`plan_batches`) so all three advance the rng stream
        identically and produce bit-identical crops.

        Returns ``(starts, kept)``: the start offset into each subject's
        event range and the number of events kept (``min(seq_len, L)``).

        RANDOM draws from ``[0, seq_len - L)`` — an *exclusive* high bound,
        deliberately matching the reference's ``np.random.choice(seq_len -
        max_seq_len)`` (``pytorch_dataset.py:498``), which never samples the
        final full window. The packed path (`_pack_rows`), a net-new feature
        with no reference analog, uses the inclusive bound.
        """
        d = self.data
        idx = np.asarray(subject_indices)
        L = self.max_seq_len
        seq_lens = d.subject_event_offsets[idx + 1] - d.subject_event_offsets[idx]
        starts = np.zeros(len(idx), dtype=np.int32)
        over = seq_lens > L
        strategy = self.config.subsequence_sampling_strategy
        if strategy == SubsequenceSamplingStrategy.RANDOM:
            starts[over] = rng.integers(0, seq_lens[over] - L)
        elif strategy == SubsequenceSamplingStrategy.TO_END:
            starts[over] = seq_lens[over] - L
        elif strategy != SubsequenceSamplingStrategy.FROM_START:
            raise ValueError(f"Invalid sampling strategy: {strategy}!")
        return starts, np.minimum(seq_lens, L)

    def collate_indices(
        self, subject_indices: np.ndarray, rng: np.random.Generator | None = None
    ) -> EventStreamBatch:
        """Vectorized collation of the given subjects into a static-shape batch.

        All shapes are fixed by config — ``(B, max_seq_len)`` and
        ``(B, max_seq_len, max_n_dynamic)`` — regardless of batch content, so
        the jitted train step never recompiles.
        """
        rng = rng or np.random.default_rng()
        starts, kept = self._draw_starts(subject_indices, rng)
        return self._collate_with_starts(subject_indices, starts, kept)

    def _collate_with_starts(
        self,
        subject_indices: np.ndarray,
        starts: np.ndarray,
        kept: np.ndarray,
        start_time: np.ndarray | None = None,
    ) -> EventStreamBatch:
        """Collation body with the crop starts already drawn (rng-free).

        ``start_time`` short-circuits the per-row prior-delta summation when
        the caller (`batches` via `plan_batches`) already computed it.
        """
        d = self.data
        B = len(subject_indices)
        L = self.max_seq_len
        M = self.max_n_dynamic
        S = self.max_n_static

        ev_lo = d.subject_event_offsets[subject_indices]

        # (B, L) global event ids + validity. int32 end to end: the (B, L, M)
        # index arithmetic below is memory-bound and half-width indices halve
        # its traffic.
        pos = np.arange(L, dtype=np.int32)[None, :]
        if self.seq_padding_side == SeqPaddingSide.RIGHT:
            event_ids = ev_lo[:, None] + starts[:, None] + pos
            event_mask = pos < kept[:, None]
        else:
            pad = (L - kept)[:, None]
            event_ids = ev_lo[:, None] + starts[:, None] + (pos - pad)
            event_mask = pos >= pad
        event_ids = np.where(event_mask, event_ids, 0)

        time_delta = np.where(event_mask, d.time_delta[event_ids], 0.0).astype(np.float32)

        # (B, L, M) data-element gather. Values are pre-cleaned (0 where
        # unobserved) with a stored observed mask, so this is pure gathers —
        # no isnan / nan_to_num passes in the hot path.
        data_lo = d.event_data_offsets[event_ids]
        data_n = d.event_data_offsets[event_ids + 1] - data_lo
        mpos = np.arange(M, dtype=np.int32)[None, None, :]
        data_ids = data_lo[..., None] + mpos
        data_valid = (mpos < data_n[..., None]) & event_mask[..., None]
        data_ids = np.where(data_valid, data_ids, 0)

        dynamic_indices = np.where(data_valid, d.dynamic_indices[data_ids], 0)
        dynamic_meas = np.where(data_valid, d.dynamic_measurement_indices[data_ids], 0)
        values_mask = data_valid & d.dynamic_values_observed[data_ids]
        dynamic_values = np.where(values_mask, d.dynamic_values[data_ids], 0.0)

        batch = dict(
            event_mask=event_mask,
            time_delta=time_delta,
            dynamic_indices=dynamic_indices,
            dynamic_measurement_indices=dynamic_meas,
            dynamic_values=dynamic_values,
            dynamic_values_mask=values_mask,
        )

        if self.do_produce_static_data:
            st_lo = d.static_offsets[subject_indices]
            st_n = d.static_offsets[np.asarray(subject_indices) + 1] - st_lo
            spos = np.arange(S)[None, :]
            st_ids = st_lo[:, None] + spos
            st_valid = spos < st_n[:, None]
            st_ids = np.where(st_valid, st_ids, 0)
            batch["static_indices"] = np.where(st_valid, d.static_indices[st_ids], 0)
            batch["static_measurement_indices"] = np.where(
                st_valid, d.static_measurement_indices[st_ids], 0
            )

        if self.config.do_include_start_time_min:
            if start_time is None:
                prior = np.zeros(B, dtype=np.float64)
                for b, (lo, s) in enumerate(zip(ev_lo, starts)):
                    prior[b] = d.time_delta[lo : lo + s].sum()
                start_time = (d.start_time_min[subject_indices] + prior).astype(np.float32)
            batch["start_time"] = start_time
        if self.config.do_include_subsequence_indices:
            batch["start_idx"] = starts
            batch["end_idx"] = starts + kept
        if self.config.do_include_subject_id:
            batch["subject_id"] = np.asarray(
                [self.subject_ids[i] for i in subject_indices], dtype=np.int64
            )
        if self.has_task:
            batch["stream_labels"] = {
                t: np.asarray(
                    self.stream_labels[t][subject_indices],
                    dtype=np.int64 if self.task_types[t] == "multi_class_classification" else np.float32,
                )
                for t in self.tasks
            }

        return EventStreamBatch(**batch)

    def collate(self, batch: list[dict]) -> EventStreamBatch:
        """Collates ``__getitem__`` dicts (reference-compatible slow path).

        Pads to the same static shapes as `collate_indices`.
        """
        B = len(batch)
        L, M, S = self.max_seq_len, self.max_n_dynamic, self.max_n_static
        event_mask = np.zeros((B, L), dtype=bool)
        time_delta = np.zeros((B, L), dtype=np.float32)
        dynamic_indices = np.zeros((B, L, M), dtype=np.int64)
        dynamic_meas = np.zeros((B, L, M), dtype=np.int64)
        dynamic_values = np.zeros((B, L, M), dtype=np.float32)
        values_mask = np.zeros((B, L, M), dtype=bool)

        for b, e in enumerate(batch):
            n = len(e["time_delta"])
            offset = 0 if self.seq_padding_side == SeqPaddingSide.RIGHT else L - n
            event_mask[b, offset : offset + n] = True
            time_delta[b, offset : offset + n] = e["time_delta"]
            for j in range(n):
                row_i = e["dynamic_indices"][j] or []
                row_m = e["dynamic_measurement_indices"][j] or []
                row_v = e["dynamic_values"][j] or []
                k = len(row_i)
                dynamic_indices[b, offset + j, :k] = row_i
                dynamic_meas[b, offset + j, :k] = row_m
                vals = np.asarray(
                    [np.nan if v is None else v for v in row_v], dtype=np.float32
                )
                obs = ~np.isnan(vals)
                dynamic_values[b, offset + j, :k] = np.nan_to_num(vals, nan=0.0)
                values_mask[b, offset + j, :k] = obs

        out = dict(
            event_mask=event_mask,
            time_delta=time_delta,
            dynamic_indices=dynamic_indices,
            dynamic_measurement_indices=dynamic_meas,
            dynamic_values=dynamic_values,
            dynamic_values_mask=values_mask,
        )

        if self.do_produce_static_data:
            static_indices = np.zeros((B, S), dtype=np.int64)
            static_meas = np.zeros((B, S), dtype=np.int64)
            for b, e in enumerate(batch):
                k = len(e["static_indices"])
                static_indices[b, :k] = e["static_indices"]
                static_meas[b, :k] = e["static_measurement_indices"]
            out["static_indices"] = static_indices
            out["static_measurement_indices"] = static_meas

        if self.config.do_include_start_time_min:
            out["start_time"] = np.asarray([e["start_time"] for e in batch], dtype=np.float32)
        if self.config.do_include_subsequence_indices:
            out["start_idx"] = np.asarray([e["start_idx"] for e in batch], dtype=np.int64)
            out["end_idx"] = np.asarray([e["end_idx"] for e in batch], dtype=np.int64)
        if self.config.do_include_subject_id:
            out["subject_id"] = np.asarray([e["subject_id"] for e in batch], dtype=np.int64)
        if self.has_task:
            out["stream_labels"] = {
                t: np.asarray(
                    [e[t] for e in batch],
                    dtype=np.int64 if self.task_types[t] == "multi_class_classification" else np.float32,
                )
                for t in self.tasks
            }
        return EventStreamBatch(**out)

    # -------------------------------------------------------------- batching
    # ---------------------------------------------------------- shard pools
    def subject_shards(self, n_shards: int) -> np.ndarray:
        """Contiguous subject-pool boundaries for an ``n_shards``-way layout.

        Returns ``(n_shards + 1,)`` indices into the subject axis; shard ``k``
        owns subjects ``[bounds[k], bounds[k+1])``. Boundaries balance EVENT
        counts (not subject counts): the device-resident sharded layout pads
        every shard's dense event table to the largest shard, so balancing
        events minimizes padding waste and balances per-process HBM.

        The partition is a pure function of the dataset (no rng), so every
        process computes the identical layout.
        """
        n = self.data.n_subjects
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if n < n_shards:
            raise ValueError(
                f"cannot shard {n} subjects over {n_shards} shards; every shard "
                "needs at least one subject (lower the shard count or use the "
                "replicated layout)."
            )
        cum = np.asarray(self.data.subject_event_offsets, np.int64)
        total = cum[-1]
        targets = (np.arange(1, n_shards) * total) // n_shards
        bounds = np.searchsorted(cum, targets, side="left").astype(np.int64)
        bounds = np.concatenate([[0], bounds, [n]])
        # Event-balanced split points can collide on skewed cohorts; force
        # strictly increasing boundaries so every shard is non-empty.
        for k in range(1, n_shards + 1):
            bounds[k] = min(max(bounds[k], bounds[k - 1] + 1), n - (n_shards - k))
        return bounds

    def _shard_orders(
        self, n_shards: int, rng: np.random.Generator, shuffle: bool
    ) -> list[np.ndarray]:
        """Per-shard subject orders, drawn shard-by-shard from ONE rng stream.

        With ``n_shards == 1`` this consumes the rng exactly like the
        historical single-stream path (one ``rng.permutation(n)``), so the
        degenerate case reproduces the existing epoch streams bit-for-bit.
        """
        if n_shards == 1:
            n = self.data.n_subjects
            return [rng.permutation(n) if shuffle else np.arange(n)]
        bounds = self.subject_shards(n_shards)
        return [
            bounds[k]
            + (
                rng.permutation(bounds[k + 1] - bounds[k])
                if shuffle
                else np.arange(bounds[k + 1] - bounds[k])
            )
            for k in range(n_shards)
        ]

    # ------------------------------------------------------------- packing
    def _pack_rows(self, L: int, rng: np.random.Generator, order: np.ndarray):
        """First-fit packs subject (sub)sequences into rows of ``L`` events.

        Returns ``[(subject, start, n_events), ...]`` per row. Deterministic
        given the rng state and order (`packed_batch_count` relies on this to
        predict `packed_batches`' stream exactly).
        """
        d = self.data
        strategy = self.config.subsequence_sampling_strategy

        # Greedy first-fit packing over a bounded set of open rows: unbounded
        # first-fit is O(n·rows) in Python — quadratic host time at cohort
        # scale. A row closes once it cannot fit the smallest subject (or
        # when the open set exceeds a fixed cap), keeping packing linear with
        # essentially the same fill quality.
        min_len = int(
            min(
                (min(int(d.subject_event_offsets[s + 1] - d.subject_event_offsets[s]), L) for s in order),
                default=1,
            )
        )
        MAX_OPEN_ROWS = 64
        rows: list[list[tuple[int, int, int]]] = []  # [(subject, start, n_events)]
        row_fill: list[int] = []
        open_rows: list[int] = []
        for subj in order:
            lo, hi = d.subject_event_offsets[subj], d.subject_event_offsets[subj + 1]
            n_ev = int(hi - lo)
            start = 0
            if n_ev > L:
                if strategy == SubsequenceSamplingStrategy.RANDOM:
                    start = int(rng.integers(0, n_ev - L + 1))
                elif strategy == SubsequenceSamplingStrategy.TO_END:
                    start = n_ev - L
                n_ev = L
            placed = False
            for r in open_rows:
                if row_fill[r] + n_ev <= L:
                    rows[r].append((int(subj), start, n_ev))
                    row_fill[r] += n_ev
                    placed = True
                    break
            if not placed:
                rows.append([(int(subj), start, n_ev)])
                row_fill.append(n_ev)
                open_rows.append(len(rows) - 1)
            open_rows = [r for r in open_rows if row_fill[r] + min_len <= L]
            if len(open_rows) > MAX_OPEN_ROWS:
                open_rows = open_rows[-MAX_OPEN_ROWS:]
        return rows

    def packed_rows_dealt(
        self,
        batch_size: int,
        seq_len: int | None = None,
        shuffle: bool = True,
        seed: int | None = None,
        n_shards: int = 1,
    ) -> list:
        """The epoch's packed rows in batch order, optionally dealt per shard.

        ``n_shards == 1``: exactly the historical stream — one permutation,
        one `_pack_rows` pass (the trailing short batch, if any, is left for
        callers to keep or drop). ``n_shards > 1``: each shard's subject pool
        is packed separately (rows reference one pool only, so the sharded
        device tables can gather locally), rows are dealt shard-major with
        ``batch_size / n_shards`` rows per shard per batch, and only full
        batches survive (the per-shard row counts differ, so the stream stops
        at the shortest shard). All randomness comes from one shared rng
        stream, consumed shard-by-shard — every process derives the same
        rows.
        """
        L = seq_len or self.max_seq_len
        rng = np.random.default_rng(seed)
        if n_shards == 1:
            n = len(self)
            order = rng.permutation(n) if shuffle else np.arange(n)
            return self._pack_rows(L, rng, order)
        if batch_size % n_shards != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must be divisible by n_shards ({n_shards})."
            )
        b_local = batch_size // n_shards
        orders = self._shard_orders(n_shards, rng, shuffle)
        rows_by_shard = [self._pack_rows(L, rng, order) for order in orders]
        n_batches = min(len(r) // b_local for r in rows_by_shard)
        rows: list = []
        for i in range(n_batches):
            for shard_rows in rows_by_shard:
                rows.extend(shard_rows[i * b_local : (i + 1) * b_local])
        return rows

    def packed_row_plan(
        self, rows_chunk: list, L: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Materializes packed rows into a ``(B, L)`` event-id/segment plan.

        The single definition of the packed-row layout (incl. the convention
        that trailing padding shares the last segment id so it never creates
        a phantom segment boundary) — consumed by host collation
        (`packed_batches`) and by on-device collation
        (``DeviceDataset.packed_batches`` / ``packed_plan_chunks``) so the
        two can never drift.

        Returns ``(event_ids, segment_ids, event_mask, n_events)``.
        """
        d = self.data
        B = len(rows_chunk)
        event_ids = np.zeros((B, L), dtype=np.int64)
        seg = np.zeros((B, L), dtype=np.int64)
        mask = np.zeros((B, L), dtype=bool)
        n_events = 0
        for b, placements in enumerate(rows_chunk):
            pos = 0
            for s_idx, (subj, start, n_ev) in enumerate(placements):
                lo = d.subject_event_offsets[subj] + start
                event_ids[b, pos : pos + n_ev] = np.arange(lo, lo + n_ev)
                seg[b, pos : pos + n_ev] = s_idx
                mask[b, pos : pos + n_ev] = True
                pos += n_ev
            if placements and pos < L:
                seg[b, pos:] = seg[b, pos - 1]
            n_events += pos
        return event_ids, seg, mask, n_events

    def packed_batch_count(
        self,
        batch_size: int,
        seq_len: int | None = None,
        shuffle: bool = True,
        seed: int | None = None,
        n_shards: int = 1,
    ) -> int:
        """Number of **full** batches `packed_batches` will yield.

        Runs only the packing (no collation), so step budgets and LR
        schedules can be derived from the packed stream before training
        (packing several subjects per row makes the per-epoch batch count a
        packing-factor smaller than the padded count).
        """
        rows = self.packed_rows_dealt(
            batch_size, seq_len=seq_len, shuffle=shuffle, seed=seed, n_shards=n_shards
        )
        return len(rows) // batch_size

    def packed_batches(
        self,
        batch_size: int,
        seq_len: int | None = None,
        shuffle: bool = True,
        seed: int | None = None,
        n_shards: int = 1,
    ):
        """Yields packed long-context batches with per-event ``segment_ids``.

        The long-context path (SURVEY §5.7; BASELINE config 5): instead of one
        right/left-padded subject per row, whole subject sequences are
        greedily first-fit packed into rows of ``seq_len`` (default
        ``config.max_seq_len``), with ``segment_ids`` marking subject
        boundaries. Attention, temporal encoding, history embeddings, and
        next-event alignment are segment-aware in both the CI and NA models,
        so padding waste drops from ``1 - mean_len/max_len`` to near zero at
        long sequence lengths.

        Subjects longer than ``seq_len`` are cropped by the configured
        subsequence-sampling strategy. Static data and stream labels are
        per-subject, not per-row, and are omitted from packed batches (the
        packed path targets generative pretraining throughput).
        """
        L = seq_len or self.max_seq_len
        M = self.max_n_dynamic
        d = self.data
        rows = self.packed_rows_dealt(
            batch_size, seq_len=L, shuffle=shuffle, seed=seed, n_shards=n_shards
        )

        for lo_idx in range(0, len(rows), batch_size):
            chunk = rows[lo_idx : lo_idx + batch_size]
            B = len(chunk)
            event_ids, segment_ids, event_mask, _ = self.packed_row_plan(chunk, L)

            time_delta = np.where(event_mask, d.time_delta[event_ids], 0.0).astype(np.float32)

            data_lo = d.event_data_offsets[event_ids]
            data_n = d.event_data_offsets[event_ids + 1] - data_lo
            mpos = np.arange(M, dtype=np.int32)[None, None, :]
            data_ids = data_lo[..., None] + mpos
            data_valid = (mpos < data_n[..., None]) & event_mask[..., None]
            data_ids = np.where(data_valid, data_ids, 0)

            dynamic_indices = np.where(data_valid, d.dynamic_indices[data_ids], 0)
            dynamic_meas = np.where(data_valid, d.dynamic_measurement_indices[data_ids], 0)
            values_mask = data_valid & d.dynamic_values_observed[data_ids]
            dynamic_values = np.where(values_mask, d.dynamic_values[data_ids], 0.0)

            yield EventStreamBatch(
                event_mask=event_mask,
                time_delta=time_delta,
                dynamic_indices=dynamic_indices,
                dynamic_measurement_indices=dynamic_meas,
                dynamic_values=dynamic_values,
                dynamic_values_mask=values_mask,
                segment_ids=segment_ids,
                valid_mask=np.ones(B, dtype=bool),
            )

    def batches(
        self,
        batch_size: int,
        shuffle: bool = True,
        seed: int | None = None,
        drop_last: bool | None = None,
        skip_batches: int = 0,
        n_shards: int = 1,
    ):
        """Yields `EventStreamBatch`es of exactly ``batch_size`` subjects.

        The batch shape is always static. With ``drop_last=False`` (the
        default when ``shuffle=False``, i.e. eval), a final short batch is
        filled by cyclically repeating the epoch's first subjects — but every
        fill row is **blanked** (``event_mask`` and ``dynamic_values_mask``
        all False) and marked invalid in ``batch.valid_mask`` so eval loops
        never double-count subjects: weight per-subject metrics (incl.
        ``stream_labels``) by ``valid_mask``. With ``drop_last=True``
        (default when shuffling, i.e. training) the remainder is dropped.

        ``skip_batches`` fast-forwards past the first N batches without
        collating them (mid-epoch resume after preemption): the rng stream is
        advanced identically, so batch N+1 onward is bitwise-identical to an
        uninterrupted epoch.

        ``n_shards`` selects the dealt (sharded) plan stream — see
        `plan_batches`. Host collation handles dealt plans transparently
        (indices are global either way), which is what the multi-process
        parity tests lean on.
        """
        for plan in self.plan_batches(
            batch_size,
            shuffle=shuffle,
            seed=seed,
            drop_last=drop_last,
            skip_batches=skip_batches,
            n_shards=n_shards,
        ):
            b = self._collate_with_starts(
                plan.subject_indices, plan.starts, plan.kept, start_time=plan.start_time
            )
            if not plan.valid_mask.all():
                # Blank fill rows wherever they sit (a dealt stream can have
                # them mid-batch, one run per exhausted shard).
                event_mask = np.asarray(b.event_mask).copy()
                event_mask[~plan.valid_mask] = False
                values_mask = np.asarray(b.dynamic_values_mask).copy()
                values_mask[~plan.valid_mask] = False
                b = b.replace(
                    event_mask=event_mask, dynamic_values_mask=values_mask,
                    valid_mask=plan.valid_mask,
                )
            else:
                b = b.replace(valid_mask=plan.valid_mask)
            yield b

    def plan_batches(
        self,
        batch_size: int,
        shuffle: bool = True,
        seed: int | None = None,
        drop_last: bool | None = None,
        skip_batches: int = 0,
        n_shards: int = 1,
    ):
        """Yields `BatchPlan`s — the ~100-byte rng-dependent part of a batch.

        A plan is everything `batches` decides on the host (subject order,
        subsequence crop starts, fill-row validity) with none of the array
        materialization. `batches` collates plans on the host;
        `DeviceDataset` (``device_dataset.py``) collates them **on device**
        from HBM-resident arrays, so a training step's host→device traffic is
        the plan instead of the ~MB batch. Both consume the identical rng
        stream via `_draw_starts`, so device- and host-collated epochs are
        bit-identical and ``skip_batches`` resume semantics are shared.

        ``n_shards > 1`` selects the DEALT stream for the sharded
        device-resident layout (multi-host pods): subjects are partitioned
        into ``n_shards`` contiguous pools (`subject_shards`), each batch
        takes ``batch_size / n_shards`` rows from every pool in shard-major
        row order, and all randomness (per-pool permutations, then crop
        starts per batch) is drawn from the SAME single rng stream on every
        process — so all processes derive identical plans and each data-axis
        shard's rows reference only subjects resident in its own table
        shard. ``n_shards=1`` reproduces the historical global stream
        bit-for-bit. Plans always carry GLOBAL subject indices; the sharded
        collate kernel rebases them on device.
        """
        if batch_size % n_shards != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must be divisible by n_shards "
                f"({n_shards}) to deal equal per-shard rows."
            )
        b_local = batch_size // n_shards
        if drop_last is None:
            drop_last = shuffle
        rng = np.random.default_rng(seed)
        orders = self._shard_orders(n_shards, rng, shuffle)
        if drop_last:
            n_batches = min(len(o) // b_local for o in orders)
        else:
            n_batches = max(-(-len(o) // b_local) for o in orders)
        for i in range(n_batches):
            lo = i * b_local
            parts, valid_parts = [], []
            for order in orders:
                idx_k = order[lo : lo + b_local]
                n_real_k = len(idx_k)
                if n_real_k < b_local:
                    # np.resize repeats cyclically, so this stays full even
                    # when the pool is smaller than its per-batch share.
                    idx_k = np.concatenate([idx_k, np.resize(order, b_local - n_real_k)])
                parts.append(idx_k)
                valid_parts.append(np.arange(b_local) < n_real_k)
            idx = np.concatenate(parts)
            valid_mask = np.concatenate(valid_parts)
            starts, kept = self._draw_starts(idx, rng)
            if i < skip_batches:
                continue
            start_time = None
            if self.config.do_include_start_time_min:
                d = self.data
                ev_lo = d.subject_event_offsets[idx]
                prior = np.zeros(batch_size, dtype=np.float64)
                for b, (elo, s) in enumerate(zip(ev_lo, starts)):
                    prior[b] = d.time_delta[elo : elo + s].sum()
                start_time = (d.start_time_min[idx] + prior).astype(np.float32)
            yield BatchPlan(
                subject_indices=np.asarray(idx, dtype=np.int32),
                starts=starts.astype(np.int32),
                kept=kept.astype(np.int32),
                valid_mask=valid_mask,
                n_events=int(kept[valid_mask].sum()),
                start_time=start_time,
            )
