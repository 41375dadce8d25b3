"""Zero-shot classification via generation ("generative prompting").

Rebuild of
``/root/reference/EventStream/transformer/lightning_modules/zero_shot_evaluator.py``:
for each eval batch, generate ``num_samples`` continuations per subject with
the pretrained generative model, apply a user ``Labeler`` to each generated
sequence, and average the resulting one-hot labels over samples (masked by
the labeler's per-sample predictability flag) into empirical class
probabilities (``get_generative_predictions`` :213-276). Subjects whose
samples were all unpredictable are dropped; ``frac_unpredictable`` is
tracked per split (:198-203). The driver (``zero_shot_evaluation`` :304-391)
bootstraps from a pretrain ``save_dir`` via `FinetuneConfig`, dynamically
imports ``task_dfs/{task}_labeler.py`` (class ``TaskLabeler``), and writes
``zero_shot_{split}_metrics.json``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np

from ..data.jax_dataset import JaxDataset
from ..data.device_dataset import DeviceDataset
from ..data.prefetch import prefetch_to_device
from ..generation import generate
from ..models.config import Split, StructuredTransformerConfig
from ..models.zero_shot_labeler import Labeler
from .checkpoint import load_pretrained
from .fine_tuning import FinetuneConfig, StreamClassificationMetrics
from .pretrain import build_model, data_parallel_mesh


def import_class_from_file(module_path: Path | str, class_name: str):
    """Dynamic import (reference ``zero_shot_evaluator.py:297``)."""
    spec = importlib.util.spec_from_file_location(class_name, module_path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, class_name)


def _aggregate_predictions(
    generated,
    batch,
    config: StructuredTransformerConfig,
    labeling_function: Labeler,
    num_samples: int,
    return_generated: bool = False,
):
    """Labels a generated batch and averages into empirical probabilities.

    The shared tail of both generation paths (cohort ``generate()`` and the
    serving engine): reference ``:213-276``'s label-and-aggregate logic.
    """
    B = batch.batch_size
    empirical_labels, labels_unpredicted = labeling_function(
        generated, input_seq_len=batch.sequence_length
    )

    num_labels = config.num_labels
    empirical_labels = np.asarray(empirical_labels, dtype=np.float64).reshape(
        B, num_samples, num_labels
    )
    labels_unpredicted = np.asarray(labels_unpredicted, dtype=bool).reshape(B, num_samples)

    weight = (~labels_unpredicted)[:, :, None].astype(np.float64)
    denom = weight.sum(axis=1)
    with np.errstate(invalid="ignore", divide="ignore"):
        probs = np.where(denom > 0, (empirical_labels * weight).sum(axis=1) / denom, 0.0)
    frac_unpredictable = labels_unpredicted.mean(axis=1)

    predictable = frac_unpredictable != 1.0
    # Fill rows in short eval batches are invalid regardless of the labeler.
    if batch.valid_mask is not None:
        predictable = predictable & np.asarray(batch.valid_mask)

    probs = probs[predictable]
    true_labels = np.asarray(batch.stream_labels[config.finetuning_task])[predictable]

    if config.id2label == {0: False, 1: True}:
        probs = probs[:, 1]
        true_labels = true_labels.astype(np.int64)

    output = SimpleNamespace(loss=float("nan"), preds=probs, labels=true_labels)
    frac = frac_unpredictable[
        np.asarray(batch.valid_mask) if batch.valid_mask is not None else slice(None)
    ]
    if return_generated:
        return output, frac, generated
    return output, frac


def get_generative_predictions(
    model,
    params,
    config: StructuredTransformerConfig,
    labeling_function: Labeler,
    batch,
    key: jax.Array,
    num_samples: int,
    max_new_events: int,
    use_cache: bool = True,
    mesh=None,
    do_validate_batch: bool = True,
    return_generated: bool = False,
    engine=None,
):
    """Generates, labels, and averages into empirical label probabilities.

    Reference ``:213-276``. Returns ``(StreamClassificationModelOutput-like,
    frac_unpredictable per original subject)``; subjects with no predictable
    samples are dropped from preds/labels. With ``return_generated`` the
    generated batch is appended to the tuple (the zero-shot bench counts
    generated events from it).

    With ``engine`` (a `serving.GenerationEngine` built on the same
    model/params/config), generation routes through the continuous-batching
    engine instead of the cohort ``generate()`` path: one request per
    (subject, sample) with key ``fold_in(key, row_index)``, dead rows
    stopping early on device instead of burning the full horizon. The
    labeling/aggregation tail is identical.

    A PAGED engine (``paged_kv=True``) routes through
    `GenerationEngine.fork` instead: subject ``s``'s shared history
    prefills ONCE into refcounted copy-on-write blocks and its
    ``num_samples`` branches draw from ``fold_in(fold_in(key, s), j)`` —
    one prefill per subject instead of ``num_samples`` (the scheduler's
    ``prefill_rows_computed`` counter shows exactly one row per subject),
    branch results bitwise equal to per-(subject, sample) requests with
    those explicit keys.
    """
    if engine is not None:
        generated = _generate_via_engine(
            engine, batch, key, num_samples, max_new_events
        )
    else:
        generated = generate(
            model,
            params,
            batch,
            config,
            key,
            max_new_events=max_new_events,
            num_return_sequences=num_samples,
            use_cache=use_cache,
            mesh=mesh,
            do_validate_batch=do_validate_batch,
        )
    return _aggregate_predictions(
        generated, batch, config, labeling_function, num_samples, return_generated
    )


def _generate_via_engine(engine, batch, key: jax.Array, num_samples: int, max_new_events: int):
    """Runs one eval batch's expanded rows through the serving engine.

    Row order and semantics match ``generate(num_return_sequences=
    num_samples)``: the batch expands in-order, every row keeps its nominal
    prompt length (rows whose prompts end in padding generate only masked
    events — the engine just stops decoding them early), and the assembled
    result has the fixed ``prompt_len + max_new_events`` shape the labeler
    contract expects. Request keys are ``fold_in(key, row_index)`` — a
    bit-deterministic function of the eval key and dataset order,
    independent of slot placement or co-scheduled batches.
    """
    from ..serving import Request

    expanded = batch.repeat_batch_elements(num_samples)
    n_rows = expanded.batch_size
    prompt_len = batch.sequence_length
    if engine.paged_kv:
        # One prefill per SUBJECT: subject s's history lands once in
        # frozen CoW blocks and its num_samples branches share it,
        # branch j drawing from fold_in(fold_in(key, s), j). Branch
        # results are bitwise equal to per-(subject, sample) requests
        # with those keys (the fork contract) — the evaluator's paged
        # parity pin. The non-paged flat fold_in(key, row) derivation
        # below is untouched (byte-stable with its own pins).
        for s in range(batch.batch_size):
            engine.fork(
                batch.slice((slice(s, s + 1), slice(None))),
                num_samples,
                max_new_events,
                key=jax.random.fold_in(key, s),
                request_ids=[s * num_samples + j for j in range(num_samples)],
            )
        results = engine.run()
    else:
        requests = [
            Request(
                prompt=expanded.slice((slice(i, i + 1), slice(None))),
                max_new_events=max_new_events,
                key=jax.random.fold_in(key, i),
                request_id=i,
            )
            for i in range(n_rows)
        ]
        results = engine.run(requests)

    # Reassemble into the fixed cohort shape; rows stopped early pad out
    # with masked events exactly where generate() would have written them.
    target_len = prompt_len + max_new_events
    M = batch.n_data_elements
    out = {
        "event_mask": np.zeros((n_rows, target_len), bool),
        "time_delta": np.zeros((n_rows, target_len), np.float32),
        "dynamic_indices": np.zeros((n_rows, target_len, M), np.int64),
        "dynamic_measurement_indices": np.zeros((n_rows, target_len, M), np.int64),
        "dynamic_values": np.zeros((n_rows, target_len, M), np.float32),
        "dynamic_values_mask": np.zeros((n_rows, target_len, M), bool),
    }
    for res in results:
        if res.error is not None:
            # A faulted request completes WITH its typed error and no
            # content (serving/errors.py); an evaluation must not go on
            # without the row.
            raise RuntimeError(
                f"zero-shot generation request {res.request_id!r} failed: {res.error!r}"
            )
        i = res.request_id
        row = res.batch
        n = min(res.n_events, target_len)
        for field, dst in out.items():
            src = np.asarray(getattr(row, field))[0, :n]
            dst[i, :n] = src.astype(dst.dtype)
    from ..data.types import EventStreamBatch

    return EventStreamBatch(
        event_mask=out["event_mask"],
        time_delta=out["time_delta"],
        static_indices=np.asarray(expanded.static_indices)
        if expanded.static_indices is not None
        else None,
        static_measurement_indices=np.asarray(expanded.static_measurement_indices)
        if expanded.static_measurement_indices is not None
        else None,
        dynamic_indices=out["dynamic_indices"],
        dynamic_measurement_indices=out["dynamic_measurement_indices"],
        dynamic_values=out["dynamic_values"],
        dynamic_values_mask=out["dynamic_values_mask"],
        start_time=np.asarray(expanded.start_time)
        if expanded.start_time is not None
        else None,
    )


def zero_shot_evaluation(
    cfg: FinetuneConfig, num_samples: int | None = None, use_engine: bool = True
) -> tuple[dict, dict]:
    """Runs zero-shot evaluation over tuning + held-out (reference ``:304-391``).

    Generation routes through the continuous-batching serving engine by
    default (``serving/engine.py``) with the paged copy-on-write KV cache:
    each subject's history prefills ONCE and its ``num_samples`` branches
    `fork` off the shared blocks with per-branch ``fold_in`` keys — plus
    bucketed prefill and per-row early stopping (rows whose prompts are
    padding-short stop on device instead of replaying the full horizon).
    NA models keep the monolithic per-(subject, sample) request path.
    ``use_engine=False`` keeps the PR4 cohort ``generate()`` path (one
    fused program per cohort shape, whole-batch stopping).
    """
    np.random.seed(cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)

    tuning_pyd = JaxDataset(cfg.data_config, split="tuning")
    held_out_pyd = JaxDataset(cfg.data_config, split="held_out")

    config = cfg.config
    batch_size = cfg.optimization_config.validation_batch_size

    # set_to_dataset must not shrink the generation budget or perturb the fit
    # TTE statistics (reference ``:317-323``).
    orig_max_seq_len = config.max_seq_len
    orig_mean = config.mean_log_inter_event_time_min
    orig_std = config.std_log_inter_event_time_min
    config.set_to_dataset(tuning_pyd)
    config.max_seq_len = orig_max_seq_len
    config.mean_log_inter_event_time_min = orig_mean
    config.std_log_inter_event_time_min = orig_std

    labeler_fp = Path(cfg.data_config.save_dir) / "task_dfs" / f"{cfg.task_df_name}_labeler.py"
    labeler_cls = import_class_from_file(labeler_fp, "TaskLabeler")
    labeling_function = labeler_cls(config=config)

    if num_samples is None:
        num_samples = (config.task_specific_params or {}).get("num_samples") or 1
    max_new_events = config.max_seq_len - tuning_pyd.max_seq_len
    if max_new_events <= 0:
        raise ValueError(
            f"config.max_seq_len ({config.max_seq_len}) must exceed the dataset's max_seq_len "
            f"({tuning_pyd.max_seq_len}) to leave room for generation."
        )

    model = build_model(config)
    if cfg.pretrained_weights_fp is None:
        raise ValueError("pretrained_weights_fp must be specified")
    init_batch = next(tuning_pyd.batches(min(batch_size, len(tuning_pyd)), shuffle=False))
    template = model.init(jax.random.PRNGKey(0), init_batch)
    params, _ = load_pretrained(cfg.pretrained_weights_fp, params_template=template)

    # Zero-shot is the most generation-hungry workload in the framework
    # (num_samples x generate per batch); shard the expanded batch over a
    # data mesh so all chips decode (the reference runs this under
    # Lightning DDP).
    mesh = data_parallel_mesh(batch_size * num_samples)

    engine = None
    if use_engine:
        from ..models.config import StructuredEventProcessingMode
        from ..serving import GenerationEngine

        n_slots = batch_size * num_samples
        max_len = tuning_pyd.max_seq_len + max_new_events
        # Paged CoW cache by default: each subject's shared history
        # prefills once and its num_samples branches fork off it
        # (`_generate_via_engine`). NA models keep the monolithic cache
        # (the paged layout is CI-only; the engine refuses the pair
        # loudly). block_size: the largest divisor of max_len <= 16
        # (the engine requires block_size | max_len).
        paged = (
            config.structured_event_processing_mode
            != StructuredEventProcessingMode.NESTED_ATTENTION
        )
        block_size = next(
            b for b in range(min(16, max_len), 0, -1) if max_len % b == 0
        )
        engine = GenerationEngine(
            model,
            params,
            config,
            template=init_batch,
            n_slots=n_slots,
            max_len=max_len,
            max_prompt_len=tuning_pyd.max_seq_len,
            # The engine key only seeds requests submitted WITHOUT explicit
            # keys; the evaluator always passes explicit fold_in keys. Fold
            # on a sentinel so the eval key itself is never consumed twice.
            base_key=jax.random.fold_in(key, 2**31 - 1),
            mesh=mesh,
            paged_kv=paged,
            block_size=block_size if paged else 16,
        )

    results = {}
    for split, dataset in ((Split.TUNING, tuning_pyd), (Split.HELD_OUT, held_out_pyd)):
        metrics = StreamClassificationMetrics(config, split)
        frac_unpredictable: list[np.ndarray] = []
        # Prompts collate ON DEVICE when the dataset fits HBM residency
        # (data/device_dataset.py): generate() then receives resident arrays
        # and its wrapper pays no per-batch wire transfer — at r05 bench
        # shapes the transfer was ~5x the fused generation program itself.
        # Oversized cohorts fall back to host collation in a prefetch thread.
        # No mesh here: the data mesh is sized for the num_samples-expanded
        # batch, which generate() itself expands and shards; prompts collate
        # unsharded. Multi-process runs therefore also take the host fallback
        # (the shared gate returns None without a 'data'-axis mesh to shard
        # the tables over); prompt collation is a trivial fraction of the
        # generation-bound workload, so residency is not worth a second mesh.
        device_ds = DeviceDataset.try_create(dataset)
        # NaN-cleanliness of resident prompts is guaranteed at table-build
        # time (DeviceDataset validates time_delta/dynamic_values finiteness
        # once, host-side), so skipping the per-batch device readback below
        # loses no safety.
        if device_ds is not None:
            batch_iter = (
                (b, None)
                for b in device_ds.batches(batch_size, shuffle=False, drop_last=False, seed=0)
            )
        else:
            # Collation runs in the prefetcher's worker thread, overlapping
            # the (device-bound) generation of the previous batch. Placement
            # stays on the host — generate() expands the batch by
            # num_return_sequences before sharding it over the mesh itself.
            batch_iter = prefetch_to_device(
                dataset.batches(batch_size, shuffle=False, drop_last=False, seed=0),
                lambda b: b,
            )
        try:
            for batch, _ in batch_iter:
                key, sub = jax.random.split(key)
                out, frac = get_generative_predictions(
                    model,
                    params,
                    config,
                    labeling_function,
                    batch,
                    sub,
                    num_samples=num_samples,
                    max_new_events=max_new_events,
                    mesh=mesh,
                    # Resident framework-collated prompts are NaN-clean by
                    # construction; the device-side validity readback costs
                    # a host round trip per batch.
                    do_validate_batch=device_ds is None,
                    engine=engine,
                )
                if len(out.labels):
                    metrics.update(out)
                frac_unpredictable.append(frac)
        finally:
            batch_iter.close()
        result = metrics.compute()
        result.pop(f"{split}_loss", None)  # zero-shot has no loss
        if frac_unpredictable:
            result[f"{split}_frac_unpredictable"] = float(
                np.concatenate(frac_unpredictable).mean()
            )
        results[str(split)] = result

    save_dir = Path(cfg.save_dir)
    if jax.process_index() == 0:
        print("Saving final metrics...")
        save_dir.mkdir(parents=True, exist_ok=True)
        with open(save_dir / "zero_shot_tuning_metrics.json", "w") as f:
            json.dump(results[str(Split.TUNING)], f)
        with open(save_dir / "zero_shot_held_out_metrics.json", "w") as f:
            json.dump(results[str(Split.HELD_OUT)], f)

    return results[str(Split.TUNING)], results[str(Split.HELD_OUT)]
