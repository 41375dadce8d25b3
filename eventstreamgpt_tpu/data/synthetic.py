"""Synthetic DL-cache generation: MIMIC-shaped datasets written to disk.

Fabricates the on-disk artifacts the data layer consumes — ``DL_reps/
{split}_0.parquet`` + ``vocabulary_config.json`` +
``inferred_measurement_configs.json`` in the reference's exact schema
(``/root/reference/sample_data/processed/sample/``) — at configurable scale.
Used by ``chip_smoke.py`` so the chip run exercises the real pipeline (parquet →
``JaxDataset`` → collation → device) rather than a resident synthetic
batch, and by tests needing bigger-than-sample fixtures.

Shape targets mirror the MIMIC-IV tutorial config (BASELINE.json config 2):
ragged sequence lengths, ~1 event type + a bag of lab observations per event,
a few-thousand-entry unified vocabulary.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pandas as pd

__all__ = ["write_synthetic_dataset", "write_synthetic_raw_csvs"]


def _vocab_entry(name: str, size: int) -> dict:
    """A MeasurementConfig 'vocabulary' dict with UNK at 0 (reference schema)."""
    freqs = np.linspace(2.0, 1.0, size - 1)
    freqs = freqs / freqs.sum()
    return {
        "vocabulary": ["UNK"] + [f"{name}_{i}" for i in range(1, size)],
        "obs_frequencies": [0.0] + freqs.tolist(),
    }


def write_synthetic_dataset(
    save_dir: Path | str,
    n_subjects_per_split: dict[str, int] | None = None,
    n_event_types: int = 40,
    n_labs: int = 2000,
    n_meds: int = 500,
    n_static: int = 16,
    mean_seq_len: int = 128,
    max_seq_len: int = 512,
    mean_obs_per_event: int = 14,
    max_obs_per_event: int = 24,
    seed: int = 0,
) -> Path:
    """Writes a synthetic processed dataset; returns ``save_dir``.

    Measurements: ``event_type`` (single-label), ``lab`` (multivariate
    regression + multi-label), ``med`` (multi-label), ``demo`` (static
    single-label). Sequence lengths are lognormal-ragged, clipped to
    ``[4, max_seq_len]``.
    """
    save_dir = Path(save_dir)
    (save_dir / "DL_reps").mkdir(parents=True, exist_ok=True)
    if n_subjects_per_split is None:
        n_subjects_per_split = {"train": 256, "tuning": 64, "held_out": 64}

    rng = np.random.default_rng(seed)

    # Unified vocabulary layout: UNK/pad at 0, then per-measurement slices.
    vocab_offsets = {"event_type": 1}
    vocab_sizes = {"event_type": n_event_types}
    vocab_offsets["lab"] = 1 + n_event_types
    vocab_sizes["lab"] = n_labs
    vocab_offsets["med"] = vocab_offsets["lab"] + n_labs
    vocab_sizes["med"] = n_meds
    vocab_offsets["demo"] = vocab_offsets["med"] + n_meds
    vocab_sizes["demo"] = n_static
    total_vocab = vocab_offsets["demo"] + n_static

    vocabulary_config = {
        "vocab_sizes_by_measurement": vocab_sizes,
        "vocab_offsets_by_measurement": vocab_offsets,
        "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
        "measurements_per_generative_mode": {
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["lab", "med"],
            "multivariate_regression": ["lab"],
        },
        "event_types_idxmap": {f"event_type_{i}": i for i in range(1, n_event_types)},
    }
    with open(save_dir / "vocabulary_config.json", "w") as f:
        json.dump(vocabulary_config, f)

    # event_type is deliberately absent: the reference keeps it out of
    # inferred_measurement_configs (it is the special event-type measurement).
    measurement_configs = {
        "lab": {
            "name": "lab",
            "temporality": "dynamic",
            "modality": "multivariate_regression",
            "observation_frequency": 0.95,
            "functor": None,
            "vocabulary": _vocab_entry("lab", n_labs),
            "values_column": "lab_value",
            "_measurement_metadata": None,
        },
        "med": {
            "name": "med",
            "temporality": "dynamic",
            "modality": "multi_label_classification",
            "observation_frequency": 0.4,
            "functor": None,
            "vocabulary": _vocab_entry("med", n_meds),
            "values_column": None,
            "_measurement_metadata": None,
        },
        "demo": {
            "name": "demo",
            "temporality": "static",
            "modality": "single_label_classification",
            "observation_frequency": 1.0,
            "functor": None,
            "vocabulary": _vocab_entry("demo", n_static),
            "values_column": None,
            "_measurement_metadata": None,
        },
    }
    with open(save_dir / "inferred_measurement_configs.json", "w") as f:
        json.dump(measurement_configs, f)

    subject_id = 0
    for split, n_subjects in n_subjects_per_split.items():
        rows = []
        for _ in range(n_subjects):
            L = int(np.clip(rng.lognormal(np.log(mean_seq_len), 0.6), 4, max_seq_len))
            # Strictly-positive inter-event times in minutes.
            deltas = rng.uniform(1.0, 240.0, size=L - 1).astype(np.float64)
            times = np.concatenate([[0.0], np.cumsum(deltas)])

            ev_meas, ev_idx, ev_val = [], [], []
            for _e in range(L):
                n_obs = int(np.clip(rng.poisson(mean_obs_per_event), 1, max_obs_per_event))
                meas = np.full(n_obs, 2, dtype=np.int64)  # labs by default
                meas[0] = 1  # exactly one event_type element
                if n_obs > 2 and rng.random() < 0.4:
                    meas[-(1 + int(rng.integers(0, min(3, n_obs - 2)))) :] = 3  # meds
                idx = np.empty(n_obs, dtype=np.int64)
                for m, (name, lo) in enumerate(
                    [("event_type", 1), ("lab", 2), ("med", 3)]
                ):
                    sel = meas == lo
                    if sel.any():
                        off, size = vocab_offsets[name], vocab_sizes[name]
                        idx[sel] = rng.integers(off + 1, off + size, size=int(sel.sum()))
                val = np.where(meas == 2, rng.normal(size=n_obs), np.nan).astype(np.float32)
                ev_meas.append(meas)
                ev_idx.append(idx)
                ev_val.append(val)

            rows.append(
                {
                    "subject_id": subject_id,
                    "static_measurement_indices": np.asarray([4], dtype=np.int64),
                    "static_indices": np.asarray(
                        [rng.integers(vocab_offsets["demo"] + 1, total_vocab)], dtype=np.int64
                    ),
                    "start_time": pd.Timestamp("2020-01-01") + pd.Timedelta(minutes=float(rng.uniform(0, 1e5))),
                    "time": times,
                    "dynamic_measurement_indices": ev_meas,
                    "dynamic_indices": ev_idx,
                    "dynamic_values": ev_val,
                }
            )
            subject_id += 1
        pd.DataFrame(rows).to_parquet(save_dir / "DL_reps" / f"{split}_0.parquet")

    return save_dir


def write_synthetic_raw_csvs(
    raw_dir: Path | str,
    n_subjects: int = 500,
    mean_admissions_per_subject: float = 3.0,
    mean_vitals_per_admission: float = 30.0,
    n_departments: int = 12,
    seed: int = 0,
) -> Path:
    """Writes raw CSVs in the reference ``sample_data/raw`` schema, at scale.

    Produces ``subjects.csv`` (MRN, dob, eye_color, height) and
    ``admit_vitals.csv`` (MRN, admit/disch range events, department,
    per-vitals-timestamp HR/temp readings) shaped like
    ``/root/reference/sample_data/raw/*.csv`` but with configurable row
    counts — the input side of an ETL run at scale.
    Returns ``raw_dir``.
    """
    raw_dir = Path(raw_dir)
    raw_dir.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(seed)

    # Int population (no materialized 90M-element array; Generator draws
    # without replacement via Floyd's algorithm).
    mrns = rng.choice(90_000_000, size=n_subjects, replace=False) + 10_000_000
    eye_colors = rng.choice(["BROWN", "BLUE", "GREEN", "HAZEL"], size=n_subjects)
    dob_year = rng.integers(1930, 2000, size=n_subjects)
    dob_month = rng.integers(1, 13, size=n_subjects)
    dob_day = rng.integers(1, 29, size=n_subjects)
    subjects = pd.DataFrame(
        {
            "MRN": mrns,
            "dob": [f"{m:02d}/{d:02d}/{y}" for y, m, d in zip(dob_year, dob_month, dob_day)],
            "eye_color": eye_colors,
            "height": rng.normal(170.0, 10.0, size=n_subjects),
        }
    )
    subjects.to_csv(raw_dir / "subjects.csv", index=False)

    departments = [f"DEPT_{i}" for i in range(n_departments)]
    n_adm = rng.poisson(mean_admissions_per_subject, size=n_subjects).clip(1)

    base = pd.Timestamp("2010-01-01")
    sub_rows, admit_list, disch_list, dept_list, vit_ts = [], [], [], [], []
    hr_list, temp_list = [], []
    for i in range(n_subjects):
        t = base + pd.Timedelta(minutes=int(rng.integers(0, 525_600)))
        for _ in range(int(n_adm[i])):
            stay_h = float(rng.uniform(24.0, 24.0 * 14))
            admit, disch = t, t + pd.Timedelta(hours=stay_h)
            dept = departments[int(rng.integers(n_departments))]
            n_vit = max(int(rng.poisson(mean_vitals_per_admission)), 1)
            offs = np.sort(rng.uniform(0.0, stay_h * 60.0, size=n_vit))
            for o in offs:
                sub_rows.append(mrns[i])
                admit_list.append(admit)
                disch_list.append(disch)
                dept_list.append(dept)
                vit_ts.append(admit + pd.Timedelta(minutes=float(o)))
            hr_list.append(rng.normal(85.0, 15.0, size=n_vit).round(1))
            temp_list.append(rng.normal(97.5, 1.2, size=n_vit).round(1))
            t = disch + pd.Timedelta(hours=float(rng.uniform(24.0, 24.0 * 60)))

    fmt = "%m/%d/%Y, %H:%M:%S"
    admit_vitals = pd.DataFrame(
        {
            "MRN": sub_rows,
            "admit_date": pd.Series(admit_list).dt.strftime(fmt),
            "disch_date": pd.Series(disch_list).dt.strftime(fmt),
            "department": dept_list,
            "vitals_date": pd.Series(vit_ts).dt.strftime(fmt),
            "HR": np.concatenate(hr_list),
            "temp": np.concatenate(temp_list),
        }
    )
    admit_vitals.to_csv(raw_dir / "admit_vitals.csv", index=False)
    return raw_dir
