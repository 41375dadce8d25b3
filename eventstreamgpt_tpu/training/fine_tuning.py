"""The fine-tuning harness for stream classification.

Rebuild of ``/root/reference/EventStream/transformer/lightning_modules/fine_tuning.py``:

* ``FinetuneConfig`` (``:270-381``): bootstraps from a pretrain ``save_dir``
  — loads ``config.json`` + ``data_config.json``, applies overrides, sets
  the task dataframe, and derives few-shot save dirs for train subsets.
* the stream-classification metric sets (``:97-150``): binary /
  multiclass / multilabel accuracy + AUROC + AUPRC.
* ``train`` (``:384-514``): datasets → ``set_to_dataset`` → config dumps →
  model (optionally warm-started from pretrained encoder weights) → fit with
  tuning eval + early stopping → final tuning/held-out metric JSONs.

The train loop itself reuses the pretraining harness machinery (mesh,
jitted donated step, orbax checkpoints) — only the model/loss and metric
collection differ.
"""

from __future__ import annotations

import dataclasses
import json
import random
import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from ..data.config import PytorchDatasetConfig
from ..data.jax_dataset import JaxDataset
from ..data.prefetch import prefetch_to_device
from ..models.config import OptimizationConfig, Split, StructuredTransformerConfig
from ..models.fine_tuning_model import ESTForStreamClassification
from ..utils import config_dataclass
from .checkpoint import load_pretrained, save_pretrained
from .metrics import (
    BinaryAccuracy,
    BinaryAUROC,
    BinaryAveragePrecision,
    MeanMetric,
    MulticlassAccuracy,
    MulticlassAUROC,
    MulticlassAveragePrecision,
    MultilabelAccuracy,
    MultilabelAUROC,
    MultilabelAveragePrecision,
)
from .optimizer import build_optimizer
from .pretrain import TrainState, data_parallel_mesh, make_train_step, replicate, shard_batch

# ---------------------------------------------------------------- metrics
class StreamClassificationMetrics:
    """Binary/multiclass/multilabel metric set (reference ``:97-150``)."""

    def __init__(self, config: StructuredTransformerConfig, split: str, n_thresholds: int = 50):
        self.split = split
        self.loss = MeanMetric()
        problem = config.problem_type
        n = config.num_labels

        if problem == "single_label_classification" and n > 2:
            kw = {"num_classes": n}
            self.metrics = {
                "macro_AUROC": MulticlassAUROC(**kw, thresholds=n_thresholds, average="macro"),
                "weighted_AUROC": MulticlassAUROC(**kw, thresholds=n_thresholds, average="weighted"),
                "macro_accuracy": MulticlassAccuracy(**kw, average="macro"),
                "weighted_accuracy": MulticlassAccuracy(**kw, average="weighted"),
                "micro_accuracy": MulticlassAccuracy(**kw, average="micro"),
                "macro_AUPRC": MulticlassAveragePrecision(
                    **kw, thresholds=n_thresholds, average="macro"
                ),
                "weighted_AUPRC": MulticlassAveragePrecision(
                    **kw, thresholds=n_thresholds, average="weighted"
                ),
            }
        elif problem == "single_label_classification" and n == 2:
            self.metrics = {
                "AUROC": BinaryAUROC(thresholds=n_thresholds),
                "accuracy": BinaryAccuracy(),
                "AUPRC": BinaryAveragePrecision(thresholds=n_thresholds),
            }
        elif problem == "multi_label_classification":
            kw = {"num_labels": n}
            self.metrics = {
                "macro_AUROC": MultilabelAUROC(**kw, thresholds=n_thresholds, average="macro"),
                "weighted_AUROC": MultilabelAUROC(**kw, thresholds=n_thresholds, average="weighted"),
                "micro_AUROC": MultilabelAUROC(**kw, thresholds=n_thresholds, average="micro"),
                "macro_accuracy": MultilabelAccuracy(**kw, average="macro"),
                "weighted_accuracy": MultilabelAccuracy(**kw, average="weighted"),
                "micro_accuracy": MultilabelAccuracy(**kw, average="micro"),
                "macro_AUPRC": MultilabelAveragePrecision(
                    **kw, thresholds=n_thresholds, average="macro"
                ),
                "weighted_AUPRC": MultilabelAveragePrecision(
                    **kw, thresholds=n_thresholds, average="weighted"
                ),
                "micro_AUPRC": MultilabelAveragePrecision(
                    **kw, thresholds=n_thresholds, average="micro"
                ),
            }
        else:
            raise ValueError(f"{problem} not valid")

    def update(
        self, out, n_valid: int | None = None, valid_mask=None, skip_metrics=()
    ) -> None:
        preds = np.asarray(out.preds)
        labels = np.asarray(out.labels)
        B = len(labels)
        # Fill rows are blanked subjects — drop them. The dealt (sharded)
        # plan stream can leave fill rows MID-batch (one run per exhausted
        # pool), so a boolean mask is authoritative; ``n_valid`` keeps the
        # historical trailing-fill prefix convention for callers without one.
        if valid_mask is None:
            valid_mask = np.arange(B) < (B if n_valid is None else n_valid)
        else:
            valid_mask = np.asarray(valid_mask, bool)
        preds, labels = preds[valid_mask], labels[valid_mask]
        self.loss.update(float(out.loss), weight=int(valid_mask.sum()))
        for name, metric in self.metrics.items():
            if any(s in name for s in skip_metrics):
                continue
            metric.update(preds, labels)

    def compute(self) -> dict[str, float]:
        out = {f"{self.split}_loss": self.loss.compute()}
        for name, metric in self.metrics.items():
            v = metric.compute()
            if not (isinstance(v, float) and np.isnan(v)):
                out[f"{self.split}_{name}"] = v
        return out


# ----------------------------------------------------------------- config
@config_dataclass
class FinetuneConfig:
    """Fine-tuning driver config (reference ``FinetuneConfig`` :270-381)."""

    load_from_model_dir: str | Path | None = None
    seed: int = 1

    pretrained_weights_fp: str | Path | None = None
    save_dir: str | Path | None = None

    do_overwrite: bool = False
    # Debug mode: NaN provenance via ``jax_debug_nans`` (see PretrainConfig).
    do_detect_anomaly: bool = False

    optimization_config: OptimizationConfig = dataclasses.field(default_factory=OptimizationConfig)

    task_df_name: str | None = None

    data_config_overrides: dict[str, Any] = dataclasses.field(
        default_factory=lambda: {
            "subsequence_sampling_strategy": "to_end",
            "seq_padding_side": "right",
        }
    )

    trainer_config: dict[str, Any] = dataclasses.field(
        default_factory=lambda: {
            "log_every_n_steps": 10,
            "checkpoint_every_n_steps": 100,
            "max_checkpoints_to_keep": 2,
        }
    )

    task_specific_params: dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"pooling_method": "last", "num_samples": None}
    )

    config_overrides: dict[str, Any] = dataclasses.field(default_factory=dict)

    do_final_validation_on_metrics: bool = True
    # Auto-resume parity with pretrain: restore the newest verifiable
    # train-state checkpoint under save_dir and (for a mid-epoch one) skip
    # the batches already trained on — same key, same semantics.
    do_resume_from_checkpoint: bool = True

    def __post_init__(self):
        if isinstance(self.save_dir, str):
            self.save_dir = Path(self.save_dir)

        if self.load_from_model_dir is None:
            self.data_config = None
            self.config = None
            return

        self.load_from_model_dir = Path(self.load_from_model_dir)
        if self.task_df_name is None:
            raise ValueError("Missing mandatory parameter task_df_name!")

        if self.pretrained_weights_fp is None:
            self.pretrained_weights_fp = self.load_from_model_dir
        if self.save_dir is None:
            subset_size = self.data_config_overrides.get("train_subset_size", None)
            if subset_size in (None, "FULL"):
                self.save_dir = self.load_from_model_dir / "finetuning" / self.task_df_name
            else:
                if self.data_config_overrides.get("train_subset_seed", None) is None:
                    self.data_config_overrides["train_subset_seed"] = int(
                        random.randint(1, int(1e6))
                    )
                    print(
                        f"WARNING: train_subset_size={subset_size} but seed is unset. Setting to "
                        f"{self.data_config_overrides['train_subset_seed']}"
                    )
                self.save_dir = (
                    self.load_from_model_dir
                    / "finetuning"
                    / f"subset_size_{subset_size}"
                    / f"subset_seed_{self.data_config_overrides['train_subset_seed']}"
                    / self.task_df_name
                )

        data_config_fp = self.load_from_model_dir / "data_config.json"
        print(f"Loading data_config from {data_config_fp}")
        self.data_config = PytorchDatasetConfig.from_json_file(data_config_fp)
        self.data_config.task_df_name = self.task_df_name

        for param, val in (self.data_config_overrides or {}).items():
            if param == "task_df_name":
                print(
                    f"WARNING: task_df_name is set in data_config_overrides to {val}! "
                    f"Original is {self.task_df_name}. Ignoring data_config_overrides..."
                )
                continue
            print(f"Overwriting {param} in data_config from {getattr(self.data_config, param)} to {val}")
            setattr(self.data_config, param, val)

        config_fp = self.load_from_model_dir / "config.json"
        print(f"Loading config from {config_fp}")
        self.config = StructuredTransformerConfig.from_json_file(config_fp)

        if self.task_specific_params is not None:
            if self.config.task_specific_params is None:
                self.config.task_specific_params = {}
            self.config.task_specific_params.update(self.task_specific_params)

        for param, val in (self.config_overrides or {}).items():
            print(f"Overwriting {param} in config from {getattr(self.config, param)} to {val}")
            setattr(self.config, param, val)


# --------------------------------------------------------- pretrained graft
def init_from_pretrained_encoder(
    ft_params: Any, pretrained_dir: Path | str
) -> Any:
    """Grafts pretrained generative-model encoder weights into fresh
    fine-tuning params (HF ``from_pretrained`` partial-load semantics: only
    the encoder subtree transfers; pooling/logit layers stay fresh)."""
    pretrained, _ = load_pretrained(pretrained_dir)
    pre_encoder = pretrained["params"]["encoder"]
    ft_sd = serialization.to_state_dict(ft_params)
    ft_encoder = ft_sd["params"]["encoder"]

    def graft(dst: dict, src: dict, path=""):
        out = {}
        for k, v in dst.items():
            if k in src and isinstance(v, dict) and isinstance(src[k], dict):
                out[k] = graft(v, src[k], f"{path}/{k}")
            elif k in src and not isinstance(v, dict):
                sv = np.asarray(src[k])
                if sv.shape == np.asarray(v).shape:
                    out[k] = sv
                else:
                    print(f"WARNING: shape mismatch at {path}/{k}; keeping fresh init")
                    out[k] = v
            else:
                print(f"WARNING: {path}/{k} missing from pretrained weights; keeping fresh init")
                out[k] = v
        return out

    ft_sd["params"]["encoder"] = graft(ft_encoder, pre_encoder)
    return serialization.from_state_dict(ft_params, ft_sd)


# ------------------------------------------------------------------ driver
def train(cfg: FinetuneConfig) -> tuple[float | None, dict | None, dict | None]:
    """End-to-end fine-tuning (reference ``train`` :384-514)."""
    np.random.seed(cfg.seed)
    rng = jax.random.PRNGKey(cfg.seed)

    if getattr(cfg, "do_detect_anomaly", False):
        jax.config.update("jax_debug_nans", True)

    train_pyd = JaxDataset(cfg.data_config, split="train")
    tuning_pyd = JaxDataset(cfg.data_config, split="tuning")

    config = cfg.config
    data_config = cfg.data_config
    oc = cfg.optimization_config

    config.set_to_dataset(train_pyd)
    oc.set_to_dataset(train_pyd)

    save_dir = Path(cfg.save_dir)
    is_main = jax.process_index() == 0
    if is_main:
        save_dir.mkdir(parents=True, exist_ok=True)
        config_fp = save_dir / "config.json"
        # Same guard semantics as pretrain: resume waives the overwrite check
        # only when a checkpoint actually exists to resume from.
        has_resume_target = cfg.do_resume_from_checkpoint and any(
            p.name.isdigit() for p in (save_dir / "model_checkpoints").glob("*")
        )
        if config_fp.exists() and not cfg.do_overwrite and not has_resume_target:
            raise FileExistsError(f"{config_fp} already exists!")
        config.to_json_file(config_fp, do_overwrite=True)
        data_config.to_json_file(save_dir / "data_config.json", do_overwrite=True)
        oc.to_json_file(save_dir / "optimization_config.json", do_overwrite=True)

    model = ESTForStreamClassification(config)
    tx, lr_schedule = build_optimizer(oc)
    mesh = data_parallel_mesh(oc.batch_size, oc.validation_batch_size)

    if len(train_pyd) < oc.batch_size:
        raise ValueError(
            f"Train split has {len(train_pyd)} subjects but batch_size is {oc.batch_size}."
        )
    init_batch = next(train_pyd.batches(oc.batch_size, shuffle=True, seed=cfg.seed))
    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, init_batch)
    if cfg.pretrained_weights_fp is not None:
        params = init_from_pretrained_encoder(params, cfg.pretrained_weights_fp)

    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    state = replicate(state, mesh)

    tc = dict(cfg.trainer_config or {})

    # Reliability subsystem (eventstreamgpt_tpu/reliability/): same wiring
    # as pretrain — hardened checkpoint I/O, divergence sentinel + bounded
    # rollback, graceful preemption, deterministic fault hooks.
    from ..reliability import faults
    from ..reliability.integrity import ReliableCheckpointManager, resume_training_state
    from ..reliability.preemption import GracefulShutdown
    from ..reliability.sentinel import (
        DivergenceSentinel,
        HealthMonitor,
        RollbackController,
        SentinelConfig,
        finish_epoch,
    )

    sentinel_cfg = SentinelConfig.from_trainer_config(tc)
    sentinel = DivergenceSentinel(sentinel_cfg) if sentinel_cfg is not None else None
    rollback_ctl = (
        RollbackController(
            sentinel_cfg.max_rollbacks, save_dir / "divergence_diagnostics.json"
        )
        if sentinel_cfg is not None
        else None
    )
    with_health = sentinel is not None

    # The step body is pretrain's, verbatim (same fold-in rng, same update
    # math) — fine-tuning only swaps the model/loss. with_health adds the
    # sentinel's [loss, grad_norm] device flags to the step outputs.
    train_step = make_train_step(model, tx, with_health=with_health)
    eval_step = jax.jit(lambda params, batch: model.apply(params, batch))

    # Device-resident batches (r05 feed-path redesign): collate on device
    # from ~100-byte plans — stream labels ride along as host arrays — with
    # the host prefetch pipeline as the oversized-cohort fallback. Few-shot
    # fine-tuning cohorts essentially always fit the budget.
    # device_resident_data=False opts out (config parity with pretrain —
    # also what batch-level fault injection needs, since plans collate on
    # device out of reach of the host poisoning hook).
    from ..data.device_dataset import DeviceDataset

    resident_mode = tc.get("device_resident_data", "auto")
    if resident_mode is True:
        # Explicit opt-in fails loudly on unsupported topologies (pretrain
        # parity) instead of silently falling back to the host path.
        device_train = DeviceDataset.create(
            train_pyd, mesh=mesh, batch_sizes=(oc.batch_size, oc.validation_batch_size)
        )
    elif resident_mode is False:
        device_train = None
    else:
        device_train = DeviceDataset.try_create(
            train_pyd, mesh=mesh, batch_sizes=(oc.batch_size, oc.validation_batch_size)
        )
    _device_eval_cache: dict[int, "DeviceDataset | None"] = {}

    # Pallas kernels run once per batch shard on a multi-device mesh
    # (parallel/context.py); tracing-time state, as in pretraining.
    from ..parallel.context import kernel_mesh

    def evaluate(params, dataset, split) -> dict[str, float]:
        with kernel_mesh(mesh):
            return _evaluate(params, dataset, split)

    def _evaluate(params, dataset, split) -> dict[str, float]:
        metrics = StreamClassificationMetrics(config, split)
        # seed=0 pins random subsequence crops: eval passes must be comparable.
        if id(dataset) not in _device_eval_cache:
            _device_eval_cache[id(dataset)] = DeviceDataset.try_create(
                dataset, mesh=mesh, batch_sizes=(oc.validation_batch_size,)
            )
        dd = _device_eval_cache[id(dataset)]
        if dd is not None:
            for batch in dd.batches(
                oc.validation_batch_size, shuffle=False, drop_last=False, seed=0
            ):
                out = eval_step(params, batch)
                metrics.update(
                    out,
                    valid_mask=(
                        np.asarray(batch.valid_mask) if batch.valid_mask is not None else None  # graftcheck: allow GC001 -- valid_mask is a host array on device batches, no sync
                    ),
                )
            return metrics.compute()
        batch_iter = prefetch_to_device(
            dataset.batches(oc.validation_batch_size, shuffle=False, drop_last=False, seed=0),
            lambda b: shard_batch(b, mesh),
            host_stats_fn=lambda b: (
                np.asarray(b.valid_mask) if b.valid_mask is not None else None
            ),
        )
        try:
            for batch, valid_mask in batch_iter:
                out = eval_step(params, batch)
                metrics.update(out, valid_mask=valid_mask)
        finally:
            batch_iter.close()
        return metrics.compute()

    log_every = int(tc.get("log_every_n_steps") or 10)
    ckpt_every = int(tc.get("checkpoint_every_n_steps") or 100)
    keep = int(tc.get("max_checkpoints_to_keep") or 2)
    ckpt_mgr = ReliableCheckpointManager(
        save_dir / "model_checkpoints",
        max_to_keep=keep,
        retries=int(tc.get("ckpt_retries", 3)),
        backoff_base=float(tc.get("ckpt_backoff_base", 0.5)),
    )

    log_fp = save_dir / "train_log.jsonl" if is_main else None

    def log_record(rec: dict):
        if log_fp is not None:
            with open(log_fp, "a") as f:
                f.write(json.dumps(rec) + "\n")

    accum = oc.gradient_accumulation or 1
    best_tuning_loss = float("inf")
    epochs_since_best = 0
    global_step = 0
    stop = False
    tuning_metrics = None

    # Auto-resume (pretrain parity): restore the newest verifiable
    # train-state checkpoint; a mid-epoch one re-enters its epoch and skips
    # the batches already trained on (batch order is deterministic per
    # cfg.seed + epoch, so the skip is rng-exact).
    start_epoch = 0
    skip_batches = 0
    if cfg.do_resume_from_checkpoint and ckpt_mgr.latest_step() is not None:
        # Shared auto-resume (reliability/integrity.py; pretrain parity).
        state, resumed_step, start_epoch, skip_batches = resume_training_state(
            ckpt_mgr, state, lambda s: replicate(s, mesh)
        )
        global_step = resumed_step

    shutdown = GracefulShutdown()
    resume_epoch, resume_skip = start_epoch, skip_batches
    epoch = start_epoch
    with kernel_mesh(mesh), shutdown:
        while epoch < oc.max_epochs:
            epoch_t0 = time.perf_counter()
            window_losses = []
            epoch_skip = resume_skip if epoch == resume_epoch else 0
            if rollback_ctl is not None:
                epoch_skip = rollback_ctl.epoch_skip(epoch, epoch_skip)
            epoch_progress = epoch_skip
            # Shared health buffer + inspection gate (reliability/sentinel.py):
            # record per step without readback, inspect only at the flush
            # cadence — no host sync in the dispatch loop (see pretrain).
            health_mon = HealthMonitor(sentinel)
            if device_train is not None:
                batch_iter = (
                    (b, None)
                    for b in device_train.batches(
                        oc.batch_size,
                        shuffle=True,
                        seed=cfg.seed + epoch,
                        skip_batches=epoch_skip,
                    )
                )
            else:
                batch_iter = prefetch_to_device(
                    faults.wrap_batches(
                        train_pyd.batches(
                            oc.batch_size,
                            shuffle=True,
                            seed=cfg.seed + epoch,
                            skip_batches=epoch_skip,
                        ),
                        epoch=epoch,
                        first_index=epoch_skip,
                    ),
                    lambda b: shard_batch(b, mesh),
                )
            # Window records buffer their losses as device arrays and flush at
            # checkpoint cadence / epoch end — a float() per window here would
            # stall the dispatch pipeline on a host readback (GC001), exactly
            # the bug class graftcheck lints for.
            pending_logs: list[dict] = []

            def flush_pending() -> None:
                for rec in pending_logs:
                    rec["train_loss"] = float(jnp.mean(jnp.stack(rec.pop("_losses"))))  # graftcheck: allow GC001 -- flush runs only after the pipeline drains (ckpt/epoch end)
                    rec["lr"] = float(lr_schedule(rec["step"] // accum))  # graftcheck: allow GC001 -- flush runs only after the pipeline drains (ckpt/epoch end)
                    log_record(rec)
                pending_logs.clear()

            try:
                for step_in_epoch, (batch, _) in enumerate(batch_iter, start=epoch_skip):
                    if with_health:
                        state, (loss, health) = train_step(state, batch, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                        health_mon.record(health)
                    else:
                        state, loss = train_step(state, batch, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                    global_step += 1
                    epoch_progress = step_in_epoch + 1
                    faults.maybe_sigterm(global_step, shutdown)
                    window_losses.append(loss)
                    if global_step % log_every == 0:
                        pending_logs.append(
                            {
                                "split": str(Split.TRAIN),
                                "epoch": epoch,
                                "step": global_step,
                                "_losses": list(window_losses),
                            }
                        )
                        window_losses = []
                    if global_step % ckpt_every == 0:
                        # Shared inspect-then-save gate (see pretrain): the
                        # save commits only when THIS window vetted healthy.
                        if health_mon.vetted_save(
                            ckpt_mgr,
                            global_step,
                            lambda: serialization.to_state_dict(jax.device_get(state)),  # graftcheck: allow GC001 -- checkpoint readback + sentinel inspection, cadence-bounded
                            {
                                "epoch": epoch,
                                "epoch_complete": False,
                                "step_in_epoch": epoch_progress,
                            },
                            epoch=epoch,
                            progress=epoch_progress,
                        ):
                            # device_get drained the pipeline: persisting the
                            # window records here is sync-free and bounds
                            # preemption loss.
                            flush_pending()
                    if (
                        oc.max_training_steps is not None
                        and global_step // accum >= oc.max_training_steps
                    ):
                        stop = True
                        break
                    if shutdown.requested:
                        break
                    if health_mon.rollback_requested:
                        break
            finally:
                batch_iter.close()
                # Flush in the finally so a mid-epoch failure still writes the
                # loss trajectory leading up to it.
                flush_pending()

            # Post-epoch recovery tail — shared verbatim with pretrain
            # (reliability/sentinel.py finish_epoch): tail vetting, pending
            # rollback, or preemption drain (raises Preempted).
            outcome = finish_epoch(
                health_mon=health_mon,
                rollback_ctl=rollback_ctl,
                ckpt_mgr=ckpt_mgr,
                shutdown=shutdown,
                state=state,
                place_state=lambda s: replicate(s, mesh),
                log_record=log_record,
                epoch=epoch,
                epoch_progress=epoch_progress,
                global_step=global_step,
                accum=accum,
                max_training_steps=oc.max_training_steps,
                label="fine-tuning",
            )
            if outcome.action == "rollback":
                state = outcome.state
                global_step = outcome.global_step
                resume_epoch, resume_skip = outcome.resume_epoch, outcome.resume_skip
                stop = outcome.stop
                epoch = resume_epoch
                continue
            tail_healthy = outcome.tail_healthy

            tuning_metrics = evaluate(state.params, tuning_pyd, Split.TUNING)
            tuning_loss = tuning_metrics.get("tuning_loss", float("nan"))
            log_record(
                {
                    "split": str(Split.TUNING),
                    "epoch": epoch,
                    "step": global_step,
                    **tuning_metrics,
                    "epoch_time_s": time.perf_counter() - epoch_t0,
                }
            )
            print(f"finetune epoch {epoch}: tuning_loss={tuning_loss:.4f}")
            if tail_healthy:
                ckpt_mgr.save(
                    global_step,
                    serialization.to_state_dict(jax.device_get(state)),  # graftcheck: allow GC001 -- epoch-end checkpoint readback, pipeline already drained by eval
                    metadata={"epoch": epoch, "epoch_complete": True},
                )

            if np.isfinite(tuning_loss) and tuning_loss < best_tuning_loss - 1e-12:
                best_tuning_loss = tuning_loss
                epochs_since_best = 0
            else:
                epochs_since_best += 1
                if oc.patience is not None and epochs_since_best >= max(oc.patience, 1):
                    print(f"Early stopping at epoch {epoch} (patience {oc.patience})")
                    break
            if stop:
                break
            epoch += 1

    ckpt_mgr.wait_until_finished()
    params_host = jax.device_get(state.params)
    if is_main:
        save_pretrained(save_dir, params_host)

    if not cfg.do_final_validation_on_metrics:
        ckpt_mgr.close()
        return None, None, None

    held_out_pyd = JaxDataset(cfg.data_config, split="held_out")
    # The last epoch's tuning eval ran at these exact params with pinned eval
    # crops, so reuse it rather than paying a second pass.
    final_tuning = tuning_metrics
    if final_tuning is None:
        final_tuning = evaluate(state.params, tuning_pyd, Split.TUNING)
    final_held_out = evaluate(state.params, held_out_pyd, Split.HELD_OUT)

    if is_main:
        print("Saving final metrics...")
        with open(save_dir / "tuning_metrics.json", "w") as f:
            json.dump(final_tuning, f)
        with open(save_dir / "held_out_metrics.json", "w") as f:
            json.dump(final_held_out, f)

    ckpt_mgr.close()
    return final_tuning.get("tuning_loss"), final_tuning, final_held_out
