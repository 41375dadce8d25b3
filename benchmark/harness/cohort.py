"""The benchmark's own cohort generator: patient histories from a seed.

One vectorised pass (no per-event Python loop) makes a cohort in the shape of
the source project's MIMIC-IV tutorial data: ragged log-normal history
lengths, one ``event_type`` element per event, a bag of lab observations with
values and, on four events in ten, a few medications. It returns the cohort as
flat arrays (what the plain reference collates from) and writes the same
cohort as a deep-learning cache in the program's on-disk schema (what the
program's feed reads). The recipe follows ``data/synthetic.py`` of the
program, re-written in bulk; the arrays here are the benchmark's, not read
back from the program.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

_MEAS = {"event_type": 1, "lab": 2, "med": 3, "demo": 4}

# Inter-event gaps are uniform on [1, 240) minutes; mean and standard
# deviation of their logarithm (the TTE head's normalisation) follow from
# that, so they are the same for every seed.
_A, _B = 1.0, 240.0
MEAN_LOG_GAP = float((_B * np.log(_B) - _B - (_A * np.log(_A) - _A)) / (_B - _A))
STD_LOG_GAP = float(
    np.sqrt(
        (_B * (np.log(_B) ** 2 - 2 * np.log(_B) + 2) - _A * (np.log(_A) ** 2 - 2 * np.log(_A) + 2))
        / (_B - _A)
        - MEAN_LOG_GAP**2
    )
)


@dataclasses.dataclass
class Cohort:
    """A cohort as flat arrays; events of subject ``s`` are rows
    ``offsets[s]:offsets[s + 1]`` of the per-event tables."""

    vocab: dict
    offsets: np.ndarray  # (n_subjects + 1,)
    time_delta: np.ndarray  # (n_events,) minutes to the next event; 1.0 on a subject's last
    indices: np.ndarray  # (n_events, M) unified-vocabulary ids, 0 = empty slot
    measurements: np.ndarray  # (n_events, M) measurement ids, 0 = empty slot
    values: np.ndarray  # (n_events, M) float32, 0 where not observed
    observed: np.ndarray  # (n_events, M) bool
    static_indices: np.ndarray  # (n_subjects, 1)
    mean_log_inter_event_time: float
    std_log_inter_event_time: float

    @property
    def n_events(self) -> int:
        return int(self.offsets[-1])


def vocabulary(spec: dict) -> dict:
    """The unified-vocabulary layout of a cohort spec: pad/UNK at 0, then one
    slice per measurement."""
    sizes = {
        "event_type": spec["n_event_types"],
        "lab": spec["n_labs"],
        "med": spec["n_meds"],
        "demo": spec["n_static"],
    }
    offsets, at = {}, 1
    for name, size in sizes.items():
        offsets[name] = at
        at += size
    return {
        "vocab_size": at,
        "vocab_sizes": sizes,
        "vocab_offsets": offsets,
        "measurements_idxmap": dict(_MEAS),
        "single_label_classification": ["event_type"],
        "multi_label_classification": ["lab", "med"],
        "multivariate_regression": ["lab"],
    }


def make_cohort(spec: dict, seed: int) -> Cohort:
    """The cohort of ``spec`` (a workload file's ``cohort`` object) and seed."""
    rng = np.random.default_rng([seed, 0xC0407])
    vocab = vocabulary(spec)
    n_sub = int(spec["n_subjects"])
    # Every seed gets the same SET of history lengths in another order: the
    # device tables then have one shape, so one compiled program serves every
    # seed, and no seed has more work than another.
    lens = np.clip(
        np.random.default_rng(spec["lengths_seed"])
        .lognormal(np.log(spec["mean_seq_len"]), 0.6, n_sub)
        .astype(np.int64),
        spec["min_seq_len"],
        spec["max_seq_len"],
    )
    lens = rng.permutation(lens)
    offsets = np.zeros(n_sub + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    n_ev = int(offsets[-1])
    last = np.zeros(n_ev, bool)
    last[offsets[1:] - 1] = True
    time_delta = (_A + (_B - _A) * rng.random(n_ev, dtype=np.float32)).astype(np.float32)
    time_delta[last] = 1.0

    M = int(spec["max_obs_per_event"])
    n_obs = np.clip(rng.poisson(spec["mean_obs_per_event"], n_ev), 1, M).astype(np.int8)
    slot = np.arange(M, dtype=np.int8)[None, :]
    n_med = np.where(
        (n_obs > 2) & (rng.random(n_ev, dtype=np.float32) < 0.4),
        1 + rng.integers(0, 3, n_ev, dtype=np.int8) % np.maximum(np.minimum(3, n_obs - 2), 1),
        0,
    ).astype(np.int8)
    # Slot 0 is the event type, the last n_med filled slots are medications,
    # the slots between are labs, the rest stay empty.
    meas = np.full((n_ev, M), _MEAS["lab"], np.int8)
    meas[:, 0] = _MEAS["event_type"]
    meas[slot >= (n_obs - n_med)[:, None]] = _MEAS["med"]
    meas[slot >= n_obs[:, None]] = 0
    off_of = np.zeros(5, np.int32)
    span_of = np.ones(5, np.int32)
    for name in ("event_type", "lab", "med"):
        off_of[_MEAS[name]] = vocab["vocab_offsets"][name] + 1
        span_of[_MEAS[name]] = vocab["vocab_sizes"][name] - 1
    u = rng.random((n_ev, M), dtype=np.float32)
    span = span_of[meas]
    indices = off_of[meas] + np.minimum((u * span).astype(np.int32), span - 1)
    indices[meas == 0] = 0
    observed = meas == _MEAS["lab"]
    values = rng.standard_normal((n_ev, M), dtype=np.float32)
    values[~observed] = 0.0
    demo = vocab["vocab_offsets"]["demo"]
    static = demo + 1 + rng.integers(0, vocab["vocab_sizes"]["demo"] - 1, (n_sub, 1))
    return Cohort(
        vocab=vocab,
        offsets=offsets,
        time_delta=time_delta,
        indices=indices,
        measurements=meas.astype(np.int32),
        values=values,
        observed=observed,
        static_indices=static.astype(np.int32),
        mean_log_inter_event_time=MEAN_LOG_GAP,
        std_log_inter_event_time=STD_LOG_GAP,
    )


def write_dl_cache(cohort: Cohort, spec: dict, save_dir: Path) -> Path:
    """Writes the cohort as the program's deep-learning cache (train split):
    ``DL_reps/train_0.parquet``, ``vocabulary_config.json`` and
    ``inferred_measurement_configs.json``."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    save_dir = Path(save_dir)
    (save_dir / "DL_reps").mkdir(parents=True, exist_ok=True)
    v = cohort.vocab
    n_types = v["vocab_sizes"]["event_type"]
    (save_dir / "vocabulary_config.json").write_text(
        json.dumps(
            {
                "vocab_sizes_by_measurement": v["vocab_sizes"],
                "vocab_offsets_by_measurement": v["vocab_offsets"],
                "measurements_idxmap": v["measurements_idxmap"],
                "measurements_per_generative_mode": {
                    "single_label_classification": v["single_label_classification"],
                    "multi_label_classification": v["multi_label_classification"],
                    "multivariate_regression": v["multivariate_regression"],
                },
                "event_types_idxmap": {f"event_type_{i}": i for i in range(1, n_types)},
            }
        )
    )

    def vocab_entry(name: str, size: int) -> dict:
        freqs = np.linspace(2.0, 1.0, size - 1)
        return {
            "vocabulary": ["UNK"] + [f"{name}_{i}" for i in range(1, size)],
            "obs_frequencies": [0.0] + (freqs / freqs.sum()).tolist(),
        }

    def meas_config(name, temporality, modality, freq, values_column=None):
        return {
            "name": name,
            "temporality": temporality,
            "modality": modality,
            "observation_frequency": freq,
            "functor": None,
            "vocabulary": vocab_entry(name, v["vocab_sizes"][name]),
            "values_column": values_column,
            "_measurement_metadata": None,
        }

    (save_dir / "inferred_measurement_configs.json").write_text(
        json.dumps(
            {
                "lab": meas_config("lab", "dynamic", "multivariate_regression", 0.95, "lab_value"),
                "med": meas_config("med", "dynamic", "multi_label_classification", 0.4),
                "demo": meas_config("demo", "static", "single_label_classification", 1.0),
            }
        )
    )

    n_sub = len(cohort.offsets) - 1
    ev_off = cohort.offsets.astype(np.int32)
    filled = cohort.measurements != 0
    obs_off = np.zeros(cohort.n_events + 1, np.int32)
    np.cumsum(filled.sum(1), out=obs_off[1:])
    # Absolute minutes since the subject's first event, from the deltas.
    td = cohort.time_delta.astype(np.float64)
    csum = np.cumsum(td)
    before = np.concatenate([[0.0], csum[:-1]])
    times = before - np.repeat(before[cohort.offsets[:-1]], np.diff(cohort.offsets))

    def nested(flat: pa.Array) -> pa.Array:
        return pa.ListArray.from_arrays(ev_off, pa.ListArray.from_arrays(obs_off, flat))

    vals = np.where(cohort.observed, cohort.values, np.nan).astype(np.float32)
    one = np.arange(n_sub + 1, dtype=np.int32)
    table = pa.table(
        {
            "subject_id": pa.array(np.arange(n_sub, dtype=np.int64)),
            "static_measurement_indices": pa.ListArray.from_arrays(
                one, pa.array(np.full(n_sub, _MEAS["demo"], np.int64))
            ),
            "static_indices": pa.ListArray.from_arrays(
                one, pa.array(cohort.static_indices[:, 0].astype(np.int64))
            ),
            "time": pa.ListArray.from_arrays(ev_off, pa.array(times)),
            "dynamic_measurement_indices": nested(pa.array(cohort.measurements[filled].astype(np.int64))),
            "dynamic_indices": nested(pa.array(cohort.indices[filled].astype(np.int64))),
            "dynamic_values": nested(pa.array(vals[filled])),
        }
    )
    pq.write_table(table, save_dir / "DL_reps" / "train_0.parquet", compression="zstd")
    return save_dir


# ------------------------------------------------- the reference's collation
def packed_batch(cohort: Cohort, event_ids: np.ndarray, event_mask: np.ndarray) -> dict:
    """The batch of one packed plan, collated from the cohort's own arrays:
    row ``b`` holds events ``event_ids[b]`` where ``event_mask[b]``. Segment
    ids are worked out from the subject each event belongs to, and trailing
    padding takes the last segment's id."""
    ids = np.where(event_mask, event_ids, 0)
    subject = np.searchsorted(cohort.offsets, ids, side="right") - 1
    change = np.concatenate(
        [np.zeros_like(subject[:, :1], bool), (subject[:, 1:] != subject[:, :-1]) & event_mask[:, 1:]],
        axis=1,
    )
    m3 = event_mask[..., None]
    return {
        "event_mask": event_mask,
        "segment_ids": np.cumsum(change, axis=1).astype(np.int32),
        "time_delta": np.where(event_mask, cohort.time_delta[ids], 0.0).astype(np.float32),
        "dynamic_indices": np.where(m3, cohort.indices[ids], 0),
        "dynamic_measurement_indices": np.where(m3, cohort.measurements[ids], 0),
        "dynamic_values": np.where(m3, cohort.values[ids], 0.0).astype(np.float32),
        "dynamic_values_mask": cohort.observed[ids] & m3,
        "static_indices": None,
    }


def padded_batch(cohort: Cohort, subjects: np.ndarray, starts: np.ndarray, seq_len: int) -> dict:
    """The batch of one padded plan: row ``b`` holds up to ``seq_len`` events
    of ``subjects[b]`` from its ``starts[b]``-th on, padded on the right."""
    lo = cohort.offsets[subjects] + starts
    kept = np.minimum(cohort.offsets[subjects + 1] - cohort.offsets[subjects], seq_len)
    pos = np.arange(seq_len)[None, :]
    event_mask = pos < kept[:, None]
    out = packed_batch(cohort, lo[:, None] + pos, event_mask)
    out["segment_ids"] = None
    out["static_indices"] = cohort.static_indices[subjects]
    return out
