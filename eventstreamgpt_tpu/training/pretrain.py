"""The generative pretraining harness: sharded train step, epoch loop, driver.

TPU-native rebuild of the reference Lightning pretraining stack
(``/root/reference/EventStream/transformer/lightning_modules/generative_modeling.py:45-698``):

* ``ESTForGenerativeSequenceModelingLM.configure_optimizers`` → ``build_optimizer``
  (AdamW + polynomial decay w/ warmup, optax).
* Lightning DDP (``devices="auto"``) → a 1-D ``data`` mesh over
  ``jax.devices()``; the batch is sharded over the mesh, parameters are
  replicated, and gradient all-reduce is inserted by XLA under ``jit`` — no
  explicit collectives (SURVEY §2.10/§5.8).
* ``Trainer.fit`` + callbacks → an explicit epoch loop with tuning eval,
  early stopping on ``tuning_loss`` (``EarlyStopping`` ≡
  ``OptimizationConfig.patience``), LR logging (``LearningRateMonitor``),
  and step-level orbax checkpoints with preemption-safe auto-resume (a
  capability the reference lacks; SURVEY §5.3 calls it out as a must-add).
* ``train()`` keeps the reference contract: seeds, builds train/tuning
  datasets, ``set_to_dataset``, dumps the five config JSONs, fits, calls
  ``save_pretrained``, then runs final tuning/held-out validation with the
  full metrics config and writes ``tuning_metrics.json`` /
  ``held_out_metrics.json``, returning ``tuning_loss``.

W&B is replaced by a local JSONL train log (``train_log.jsonl`` in
``save_dir``) — same information, no external service.
"""

from __future__ import annotations

import dataclasses
import json
import math
import time
from pathlib import Path
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization, struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.config import PytorchDatasetConfig
from ..data.device_dataset import DeviceDataset
from ..data.jax_dataset import JaxDataset
from ..data.prefetch import prefetch_to_device
from ..data.types import EventStreamBatch
from ..models.ci_model import CIPPTForGenerativeSequenceModeling
from ..models.config import (
    MetricsConfig,
    OptimizationConfig,
    Split,
    StructuredEventProcessingMode,
    StructuredTransformerConfig,
)
from ..models.moe import ROUTING_COLLECTION, routing_counters
from ..models.na_model import NAPPTForGenerativeSequenceModeling
from ..utils import config_dataclass
from ..utils import scopes
from ..utils.scopes import host_span, host_spanned, scope
from .checkpoint import TrainCheckpointManager, save_pretrained
from .generative_metrics import GenerativeMetrics
from .optimizer import build_optimizer

SKIP_CFG_PARAMS = {"seq_attention_layers", "dep_graph_attention_layers", "mixer_layers", "ffn_layers"}


# --------------------------------------------------------------------- state
@struct.dataclass
class TrainState:
    """Replicated training state — a pytree moved whole through ``jit``."""

    step: jnp.ndarray  # scalar int32, counts optimizer steps
    params: Any
    opt_state: Any


@host_spanned("startup/build_model", id="startup")
def build_model(config: StructuredTransformerConfig):
    """CI vs NA model choice (reference ``generative_modeling.py:98-106``)."""
    mode = config.structured_event_processing_mode
    if mode == StructuredEventProcessingMode.NESTED_ATTENTION:
        return NAPPTForGenerativeSequenceModeling(config)
    if mode == StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT:
        return CIPPTForGenerativeSequenceModeling(config)
    raise ValueError(f"Unsupported structured event processing mode: {mode}")


# ------------------------------------------------------------------ sharding
def _fit_data_axis(n_data: int, *batch_sizes: int, multiplier: int = 1) -> int:
    """Largest data-axis size ≤ ``n_data`` such that ``n_data·multiplier``
    divides every batch size.

    The shared fallback rule of every mesh builder: shrink the data axis
    (rather than fail) so e.g. a batch of 6 on 4 chips runs 2-way
    data-parallel — `parallel_mesh` prints a WARNING whenever that leaves
    devices unused. ``multiplier`` is the batch-sharding factor the other
    axes contribute (the ``fsdp`` axis shards the batch too).
    """
    while n_data > 1 and any(bs % (n_data * multiplier) != 0 for bs in batch_sizes):
        n_data -= 1
    return max(n_data, 1)


def parallel_mesh(*batch_sizes: int, n_cp: int = 1, n_tp: int = 1, n_fsdp: int = 1) -> Mesh:
    """The training mesh for any ``data × fsdp × context × model`` layout.

    Axes of size 1 are omitted, so the degenerate layouts collapse to the
    1-D ``data`` mesh, ``data × model`` (tensor parallel), ``data × context``
    (ring attention), or ``data × fsdp`` (sharded parameters/optimizer —
    training/sharding.py). Axis order puts ``model`` innermost (the
    highest-bandwidth links carry the per-layer TP all-reduces), ``context``
    next (ring kv rotations), ``fsdp`` next (per-layer weight all-gathers /
    gradient reduce-scatters), ``data`` outermost. The data axis shrinks
    until ``data × fsdp`` divides every batch size (`_fit_data_axis` — the
    batch shards over both axes jointly).
    """
    devices = jax.devices()
    n_devices = len(devices)
    if n_fsdp > 1 and n_cp > 1:
        raise ValueError(
            "fsdp_shards and context_parallel_shards cannot be combined (the "
            "batch's event axis and the parameter shards would contend for the "
            "same links); pick one of the two memory axes."
        )
    per_data = n_cp * n_tp * n_fsdp
    if n_devices % per_data != 0:
        raise ValueError(
            f"fsdp x context x tensor parallel shards ({n_fsdp}x{n_cp}x{n_tp}) must "
            f"divide the device count ({n_devices}); a silent partial mesh would "
            "waste devices."
        )
    if n_fsdp > 1 and any(bs % n_fsdp != 0 for bs in batch_sizes):
        raise ValueError(
            f"every batch size {batch_sizes} must divide by fsdp_shards ({n_fsdp}): "
            "the batch shards over the fsdp axis jointly with data."
        )
    n_data = _fit_data_axis(n_devices // per_data, *batch_sizes, multiplier=n_fsdp)
    # A mesh over fewer devices than the host has is never silent: four
    # chips must not become two without a word.
    if n_data * per_data < n_devices:
        print(
            f"WARNING: batch sizes {batch_sizes} shrink the data axis to {n_data}; "
            f"using {n_data * per_data} of {n_devices} devices."
        )
    dims = [("data", n_data)]
    if n_fsdp > 1:
        dims.append(("fsdp", n_fsdp))
    if n_cp > 1:
        dims.append(("context", n_cp))
    if n_tp > 1:
        dims.append(("model", n_tp))
    return Mesh(
        np.asarray(devices[: n_data * per_data]).reshape([s for _, s in dims]),
        tuple(n for n, _ in dims),
    )


def data_parallel_mesh(*batch_sizes: int) -> Mesh:
    """A 1-D ``data`` mesh over the most devices that divide every batch size.

    Falls back to fewer devices (largest common divisor) rather than failing —
    a batch of 6 on 4 chips runs 2-way data-parallel, with a printed WARNING. Passing both the train
    and validation batch sizes yields one mesh usable for the whole run.
    """
    return parallel_mesh(*batch_sizes)


def shard_batch(batch: EventStreamBatch, mesh: Mesh) -> EventStreamBatch:
    """Device-puts a host batch sharded over the mesh's batch axes —
    ``data``, joined by ``fsdp`` when that axis exists (FSDP is data
    parallelism with sharded parameters, so the batch splits over both)."""
    from .sharding import batch_partition_axes

    axes = batch_partition_axes(mesh)
    dim0 = axes if len(axes) > 1 else axes[0]

    def put(x):
        x = np.asarray(x)
        sharding = NamedSharding(mesh, P(dim0, *([None] * (x.ndim - 1))))
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(put, batch)


def context_parallel_mesh(n_cp: int, *batch_sizes: int) -> Mesh:
    """A ``data × context`` mesh: sequence axis sharded ``n_cp``-way.

    The data axis takes the remaining devices, shrinking (like
    `data_parallel_mesh`) until it divides every batch size.
    """
    return parallel_mesh(*batch_sizes, n_cp=n_cp)


# Batch fields whose dim 1 is the event (sequence) axis; statics, labels,
# and per-subject scalars stay data-sharded only.
_CP_SEQ_FIELDS = frozenset(
    {
        "event_mask",
        "time_delta",
        "time",
        "dynamic_indices",
        "dynamic_measurement_indices",
        "dynamic_values",
        "dynamic_values_mask",
        "segment_ids",
    }
)


def shard_batch_cp(batch: EventStreamBatch, mesh: Mesh) -> EventStreamBatch:
    """Device-puts a batch with the batch dim on ``data`` and the sequence
    (event) dim on ``context`` — the layout ring attention consumes.

    Arrays whose event axis does not divide the ``context`` axis (e.g. padded
    eval batches at the dataset's own cap) fall back to data-only sharding;
    GSPMD reshards them at the first trace-enforced boundary instead.
    """
    n_ctx = int(mesh.shape["context"])

    def put(x, seq_sharded: bool):
        x = np.asarray(x)
        if seq_sharded and x.ndim >= 2 and x.shape[1] % n_ctx == 0:
            spec = P("data", "context", *([None] * (x.ndim - 2)))
        else:
            spec = P("data", *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    updates = {}
    for field in dataclasses.fields(batch):
        val = getattr(batch, field.name)
        if val is None:
            continue
        if isinstance(val, dict):  # stream_labels: per-subject arrays
            updates[field.name] = {k: put(v, False) for k, v in val.items()}
        else:
            updates[field.name] = put(val, field.name in _CP_SEQ_FIELDS)
    return batch.replace(**updates)


@host_spanned("startup/state", id="startup")
def replicate(tree: Any, mesh: Mesh) -> Any:
    return jax.device_put(tree, NamedSharding(mesh, P()))


# ----------------------------------------------------------------- train step
def _train_step_body(model, tx, with_health: bool = False, with_routing: bool = False) -> Callable:
    """The un-jitted ``(state, batch, rng) -> (state, loss)`` step body.

    Shared verbatim by the per-batch step (`make_train_step`) and the
    scanned multi-step program (`make_chunked_train_step`), so both paths
    have identical numerics: same per-step dropout rng (``fold_in`` on the
    step counter), same gradient, same optimizer update.

    ``with_health=True`` switches the output to ``(state, (loss, health))``
    where ``health`` is the divergence sentinel's device-resident flag
    vector ``[loss, grad_global_norm]`` (f32). It is computed from values
    the step already has in registers — no extra host traffic, no change to
    the parameter/loss numerics — and is read back only at the training
    loop's existing flush cadence (``reliability/sentinel.py``).

    ``with_routing=True`` (with ``with_health``) appends the routed layers'
    counters of the step, ``(state, (loss, health, routing))``: an int32
    ``[token-expert pairs computed here, largest load of one held expert]``
    (`models/moe.py::routing_counters`), read back with the health vector and
    never inside the step.
    """
    if with_routing and not with_health:
        raise ValueError("the routing counters are returned beside the health vector: with_health=True")

    def train_step(state: TrainState, batch: EventStreamBatch, rng: jax.Array):
        dropout_rng = jax.random.fold_in(rng, state.step)

        def loss_fn(params):
            if with_routing:
                out, sown = model.apply(
                    params, batch, rngs={"dropout": dropout_rng}, mutable=[ROUTING_COLLECTION]
                )
                return out.loss, routing_counters(sown)
            out = model.apply(params, batch, rngs={"dropout": dropout_rng})
            return out.loss, None

        (loss, routing), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with scope("optimizer"):
            updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt_state)
        if with_health:
            with scope("health"):
                health = jnp.stack([loss, optax.global_norm(grads)]).astype(jnp.float32)
            return new_state, ((loss, health, routing) if with_routing else (loss, health))
        return new_state, loss

    return train_step


@host_spanned("startup/build_step", id="startup")
def make_train_step(
    model, tx, with_health: bool = False, out_state_shardings=None
) -> Callable:
    """A jitted ``(state, batch, rng) -> (state, loss)`` step.

    Gradients reduce across the ``data`` axis automatically (XLA inserts the
    psum for replicated-param/sharded-batch layouts). The state is donated so
    parameters update in place on device. ``with_health`` swaps the output
    for ``(state, (loss, health))`` (see `_train_step_body`).

    ``out_state_shardings`` (a `TrainState` sharding tree, i.e.
    `make_state_shardings` output) pins the output state to the input
    layout. Without the pin, GSPMD's sharding propagation may choose a
    DIFFERENT layout for updated parameters than the caller declared on the
    inputs — on tensor-parallel meshes it reshards the small replicated
    leaves (layer norms, biases) over ``model`` — which silently drops
    their donation (input/output layouts no longer match, so the buffers
    cannot alias: the graftcheck Tier C donation audit caught 48 such
    leaves on dp4_tp2) and makes the second dispatch reshard or recompile.
    Pass it whenever the state carries a parameter-sharding axis (tp/fsdp);
    pure data-parallel layouts propagate P() unchanged and don't need it.
    The loss (and health) outputs replicate — they are cross-replica
    reductions already.
    """
    step = _train_step_body(model, tx, with_health=with_health)
    if out_state_shardings is None:
        return jax.jit(step, donate_argnums=(0,))
    mesh = jax.tree_util.tree_leaves(out_state_shardings)[0].mesh
    replicated = NamedSharding(mesh, P())
    # (state, loss) or (state, (loss, health)): `replicated` is a tree
    # prefix covering the whole auxiliary output.
    return jax.jit(
        step,
        donate_argnums=(0,),
        out_shardings=(out_state_shardings, replicated),
    )


def _flash_pair_counter(config: StructuredTransformerConfig) -> Callable | None:
    """``segment ids [..., L] -> (pairs visited per row, dense pairs a row)`` at
    the chunk widths the flash op takes for this model's heads
    (`ops.pallas_flash.flash_block_sizes`, `visited_pairs`), nothing on rows
    that are not whole chunks; ``None`` where the global layers run no flash op."""
    if config.attention_implementation != "pallas_flash":
        return None
    from ..ops.pallas_flash import flash_block_sizes, visited_pairs

    latent = "latent" in config.mixer_layers
    heads = config.num_attention_heads
    width = config.qk_nope_head_dim + config.qk_rope_head_dim if latent else config.head_dim
    value_width = config.v_head_dim if latent else config.head_dim

    def pairs(segment_ids: np.ndarray):
        L = segment_ids.shape[-1]
        if L % 128:
            return None
        return visited_pairs(segment_ids, *flash_block_sizes(1, L, heads, width, value_dim=value_width)[2:])

    return pairs


@host_spanned("startup/build_step", id="startup")
def make_chunked_train_step(
    model,
    tx,
    device_data,
    packed: bool = False,
    with_health: bool = False,
    out_state_shardings=None,
    with_routing: bool = False,
) -> Callable:
    """A jitted ``(state, arrays, plans, rng) -> (state, losses)`` program
    that runs ``k`` collate+train steps in ONE dispatch.

    The round-5 feed-path redesign (``data/device_dataset.py``): with the
    dataset HBM-resident, a ``lax.scan`` over ``k`` stacked `BatchPlan`s
    collates each batch on device and steps the optimizer, so per-step wire
    traffic is ~100 bytes and per-dispatch host overhead is amortized
    ``k``-fold. Numerics are identical to ``k``
    calls of `make_train_step` on the same plan stream (shared step body,
    same fold-in rng; tested in ``tests/training/test_resident_training.py``).

    ``plans`` comes from `DeviceDataset.plan_chunks` (padded rows) or
    `DeviceDataset.packed_plan_chunks` (``packed=True``); ``arrays`` is
    ``device_data.arrays``. Pretraining ignores per-subject light fields
    (labels, subject ids), which is why the scanned batch carries none.
    ``with_health`` stacks the per-step sentinel health vectors alongside
    the losses: the output becomes ``(state, (losses, healths))``, and with
    ``with_routing`` ``(state, (losses, healths, routings))``, the routed
    layers' counters of every step (`_train_step_body`).

    The feed is told how to count the chunk pairs the model's flash op visits
    (`DeviceDataset.flash_pairs`), so that the ``es.host/plan`` span of every
    chunk of plans carries them beside its events and slots
    (`DeviceDataset.plan_counts`).
    """
    body = _train_step_body(model, tx, with_health=with_health, with_routing=with_routing)
    device_data.flash_pairs = _flash_pair_counter(model.config)

    if packed:
        kern = device_data.packed_kernel()

        def collate(arrays, plan):
            fields = kern(arrays, plan["event_ids"], plan["event_mask"])
            fields["segment_ids"] = plan["segment_ids"]
            fields = device_data.constrain_fields(fields)
            B = plan["event_ids"].shape[0]
            return EventStreamBatch(valid_mask=jnp.ones(B, bool), **fields)

    else:
        kern = device_data.padded_kernel()

        def collate(arrays, plan):
            fields = kern(
                arrays, plan["subject_indices"], plan["starts"], plan["valid_mask"]
            )
            fields = device_data.constrain_fields(fields)
            return EventStreamBatch(valid_mask=plan["valid_mask"], **fields)

    def chunk_step(state: TrainState, arrays: dict, plans: dict, rng: jax.Array):
        def scan_body(st, plan):
            with scope("collate"):
                batch = collate(arrays, plan)
            st, out = body(st, batch, rng)
            return st, out

        return jax.lax.scan(scan_body, state, plans)

    if out_state_shardings is None:
        return jax.jit(chunk_step, donate_argnums=(0,))
    # Same output-layout pin as make_train_step: on parameter-sharding
    # meshes, unpinned GSPMD propagation reshards the small replicated
    # leaves over `model` on output, silently dropping their donation.
    mesh = jax.tree_util.tree_leaves(out_state_shardings)[0].mesh
    replicated = NamedSharding(mesh, P())
    return jax.jit(
        chunk_step,
        donate_argnums=(0,),
        out_shardings=(out_state_shardings, replicated),
    )


def make_eval_step(model) -> Callable:
    def eval_step(params, batch: EventStreamBatch):
        return model.apply(params, batch)

    return jax.jit(eval_step)


# ------------------------------------------------------------------ eval loop
def evaluate(
    eval_step: Callable,
    params: Any,
    dataset: JaxDataset,
    batch_size: int,
    config: StructuredTransformerConfig,
    metrics_config: MetricsConfig,
    split: str,
    mesh: Mesh | None = None,
    key: jax.Array | None = None,
    place_batch: Callable[[EventStreamBatch, Mesh], EventStreamBatch] | None = None,
    device_data: "DeviceDataset | None" = None,
) -> dict[str, float]:
    """Runs one full-split eval pass, returning ``{split}_...`` metrics.

    Fill rows in the final short batch are blanked + flagged by
    ``valid_mask``; loss parts re-weight by the valid count so no subject is
    double-counted. ``place_batch`` overrides the default
    data-sharded placement — context-parallel callers pass ``shard_batch_cp``
    so the event axis lands on the ``context`` mesh axis up front instead of
    being resharded at every ring-attention boundary. ``device_data`` (a
    `DeviceDataset` over the same split) switches to device-resident
    collation — identical batches, no per-batch wire transfer.
    """
    metrics = GenerativeMetrics(config, metrics_config, split=split)
    if key is None:
        key = jax.random.PRNGKey(0)
    # seed=0 pins the (otherwise random) subsequence crops so every eval pass
    # scores identical data — epoch-to-epoch tuning losses must be comparable
    # for early stopping, and the final validation must match the last epoch.
    if device_data is not None:
        # Device-resident eval: batches collate on device from ~100-byte
        # plans (bit-identical to host collation), so no transfer thread is
        # needed; collate and eval dispatches pipeline asynchronously.
        # valid_mask is a host array on device batches — reading it costs no
        # device sync.
        for batch in device_data.batches(
            batch_size, shuffle=False, drop_last=False, seed=0
        ):
            out = eval_step(params, batch)
            key, sub = jax.random.split(key)
            metrics.update(out, key=sub, n_valid=int(np.asarray(batch.valid_mask).sum()))
        return metrics.compute()
    placer = place_batch if place_batch is not None else shard_batch
    place = (lambda b: placer(b, mesh)) if mesh is not None else (lambda b: b)
    batch_iter = prefetch_to_device(
        dataset.batches(batch_size, shuffle=False, drop_last=False, seed=0),
        place,
        host_stats_fn=lambda b: int(np.asarray(b.valid_mask).sum()) if b.valid_mask is not None else None,
    )
    try:
        for batch, n_valid in batch_iter:
            out = eval_step(params, batch)
            key, sub = jax.random.split(key)
            metrics.update(out, key=sub, n_valid=n_valid)
    finally:
        batch_iter.close()
    return metrics.compute()


# --------------------------------------------------------------------- config
@config_dataclass
class PretrainConfig:
    """Pretraining driver config (reference ``PretrainConfig`` :491-552).

    ``config`` holds ``StructuredTransformerConfig`` kwargs as a dict (the
    reference's hydra ``_target_`` pattern; a ``_target_`` key is accepted
    and ignored). ``save_dir`` supports ``${...}`` interpolation via
    ``utils.config_tool``.
    """

    do_overwrite: bool = False
    seed: int = 1
    # Debug mode (reference ``PretrainConfig.do_detect_anomaly`` / Lightning
    # ``detect_anomaly``; SURVEY §5.2): enables ``jax_debug_nans``, which
    # re-runs any jitted computation that produces a NaN in op-by-op mode and
    # raises with the originating primitive — NaN provenance for the whole
    # forward/backward, not just the generation boundary.
    do_detect_anomaly: bool = False

    config: dict[str, Any] = dataclasses.field(default_factory=dict)
    optimization_config: OptimizationConfig = dataclasses.field(default_factory=OptimizationConfig)
    data_config: PytorchDatasetConfig = dataclasses.field(default_factory=PytorchDatasetConfig)
    pretraining_metrics_config: MetricsConfig = dataclasses.field(
        default_factory=lambda: MetricsConfig(do_skip_all_metrics=True)
    )
    final_validation_metrics_config: MetricsConfig = dataclasses.field(
        default_factory=lambda: MetricsConfig(do_skip_all_metrics=False)
    )

    trainer_config: dict[str, Any] = dataclasses.field(
        default_factory=lambda: {
            "log_every_n_steps": 10,
            "checkpoint_every_n_steps": 100,
            "max_checkpoints_to_keep": 2,
            "profile_dir": None,
        }
    )

    experiment_dir: str = "./experiments"
    save_dir: str = "${experiment_dir}/pretrain"

    do_final_validation_on_metrics: bool = True
    do_resume_from_checkpoint: bool = True

    def __post_init__(self):
        if "max_epochs" in self.trainer_config:
            raise ValueError("Max epochs is set in the optimization_config, not the trainer config!")

    def build_model_config(self) -> StructuredTransformerConfig:
        kwargs = {k: v for k, v in self.config.items() if k not in SKIP_CFG_PARAMS and k != "_target_"}
        return StructuredTransformerConfig(**kwargs)


# --------------------------------------------------------------------- driver
def train(
    cfg: PretrainConfig,
    model_config: StructuredTransformerConfig | None = None,
) -> tuple[float | None, dict | None, dict | None]:
    """End-to-end pretraining (reference ``train`` :555-698).

    Returns ``(tuning_loss, tuning_metrics, held_out_metrics)`` when final
    validation runs, else ``(None, None, None)``.

    Fault tolerance (docs/reliability.md): raises
    ``reliability.Preempted`` after a graceful SIGTERM/SIGINT drain (final
    mid-epoch checkpoint written; script entry points convert this to
    ``EXIT_PREEMPTED``), and ``reliability.DivergenceError`` when the
    divergence sentinel exhausts its rollback budget (diagnostic dump in
    ``save_dir/divergence_diagnostics.json``).
    """
    # What the ``startup`` line below accounts for: what the host record holds
    # since the last span of an earlier run's loop in this process (a sweep's
    # earlier trial), which on a process's first run is all of it: its imports
    # and its config lie before this call.
    hot = (s.end for s in scopes.recorded() if s.name.partition("/")[0] not in ("startup", "compile"))
    run_began = max(hot, default=0.0)

    np.random.seed(cfg.seed)
    rng = jax.random.PRNGKey(cfg.seed)

    if getattr(cfg, "do_detect_anomaly", False):
        jax.config.update("jax_debug_nans", True)

    train_pyd = JaxDataset(cfg.data_config, split="train")
    tuning_pyd = JaxDataset(cfg.data_config, split="tuning")

    config = model_config if model_config is not None else cfg.build_model_config()
    optimization_config = cfg.optimization_config
    data_config = cfg.data_config

    # set_to_dataset overwrites max_seq_len with the dataset's per-subject
    # cap; the constructor-set value is the user's intended *model* context
    # length, which packed-row training must honor (packed rows hold several
    # subjects, so their length legitimately exceeds the per-subject cap).
    configured_max_seq_len = config.max_seq_len
    config.set_to_dataset(train_pyd)

    oc = optimization_config
    tc = dict(cfg.trainer_config or {})
    # Optional tensor parallelism: trainer_config.tensor_parallel_shards > 1
    # carves a ``model`` axis out of the device set (vocab-sharded embedding
    # + classification head etc.; see training/sharding.py) with the
    # remaining devices data-parallel. The data axis shrinks until it divides
    # both batch sizes, mirroring data_parallel_mesh's fallback.
    n_tp = int(tc.get("tensor_parallel_shards") or 1)
    # Optional FSDP (r10 scale-up round): trainer_config.fsdp_shards > 1
    # carves an ``fsdp`` mesh axis; every parameter and its Adam moments
    # shard their largest divisible dimension over it and the batch shards
    # over (data, fsdp) jointly, so GSPMD inserts gather-on-use /
    # reduce-scatter-on-grad — the layout that fits widths the replicated
    # path cannot (training/sharding.py, docs/scaling.md).
    # trainer_config.strict_sharding upgrades the replicated-fallback
    # warning to an error when most parameter bytes miss the rules.
    n_fsdp = int(tc.get("fsdp_shards") or 1)
    # Optional sequence (context) parallelism: packed long-context batches
    # shard their event axis over a ``context`` mesh axis and attention runs
    # as a ring (parallel/ring_attention.py). Requires packed batches and the
    # ring attention implementation. ``use_packed_batches`` alone trains on
    # packed rows without sequence sharding; ``packed_seq_len`` overrides the
    # packed row length (default: config.max_seq_len).
    n_cp = int(tc.get("context_parallel_shards") or 1)
    use_packed = bool(tc.get("use_packed_batches")) or n_cp > 1
    # Default packed row length: the larger of the configured model context
    # and the dataset's per-subject cap — a model max_seq_len left at its
    # class default must not shrink packed rows below the data cap, and an
    # explicitly longer model context must be honored. packed_seq_len
    # overrides outright.
    packed_L = int(tc.get("packed_seq_len") or max(configured_max_seq_len, train_pyd.max_seq_len))
    if use_packed:
        # The saved config must reflect the true context length trained at
        # (downstream generation budgets read config.max_seq_len).
        config.max_seq_len = packed_L
    if n_cp > 1:
        if config.attention_implementation != "ring":
            raise ValueError(
                "context_parallel_shards > 1 requires config.attention_implementation='ring' "
                "(otherwise the sharded sequence axis is all-gathered for attention)."
            )
        if float(config.attention_dropout) != 0.0:
            raise ValueError(
                "context_parallel_shards > 1 requires attention_dropout=0 (the ring path, "
                "like the Pallas kernels, has no attention dropout)."
            )
        if packed_L % n_cp != 0:
            raise ValueError(
                f"the packed row length ({packed_L}) must be divisible by "
                f"context_parallel_shards ({n_cp})."
            )

    # Packed rows hold several subjects, so the packed stream has a
    # packing-factor fewer batches per epoch than the padded count — the LR
    # schedule and step budget must see that count, not the padded one.
    # Epoch 0's packing (packing only, no collation) sets the nominal
    # horizon; later epochs repack under a different shuffle and may differ
    # by a row or two, exactly like Lightning's estimated steps when a
    # dataloader's length drifts.
    steps_per_epoch = (
        train_pyd.packed_batch_count(oc.batch_size, seq_len=packed_L, seed=cfg.seed)
        if use_packed
        else None
    )
    optimization_config.set_to_dataset(train_pyd, steps_per_epoch=steps_per_epoch)
    if steps_per_epoch is None:
        steps_per_epoch = len(train_pyd) // oc.batch_size

    save_dir = Path(cfg.save_dir)
    is_main = jax.process_index() == 0
    if is_main:
        save_dir.mkdir(parents=True, exist_ok=True)
        config_fp = save_dir / "config.json"
        # Resume waives the overwrite guard only when there is actually a
        # checkpoint to resume from — resume-enabled-but-fresh reruns into a
        # foreign results dir must still fail loudly instead of clobbering.
        has_resume_target = cfg.do_resume_from_checkpoint and any(
            p.name.isdigit() for p in (save_dir / "model_checkpoints").glob("*")
        )
        if config_fp.exists() and not cfg.do_overwrite and not has_resume_target:
            raise FileExistsError(f"{config_fp} already exists!")
        config.to_json_file(config_fp, do_overwrite=True)
        data_config.to_json_file(save_dir / "data_config.json", do_overwrite=True)
        optimization_config.to_json_file(save_dir / "optimization_config.json", do_overwrite=True)
        cfg.pretraining_metrics_config.to_json_file(
            save_dir / "pretraining_metrics_config.json", do_overwrite=True
        )
        cfg.final_validation_metrics_config.to_json_file(
            save_dir / "final_validation_metrics_config.json", do_overwrite=True
        )

    model = build_model(config)
    tx, lr_schedule = build_optimizer(optimization_config)

    # One mesh for every layout: data-parallel by default; a ``model`` axis
    # for Megatron tensor parallelism; a ``context`` axis for ring-attention
    # sequence parallelism; all three composed when both shard counts are set
    # (the axes are orthogonal — each model shard rings its local heads' kv
    # blocks over ``context``; parallel/ring_attention.py ``head_axis``).
    mesh = parallel_mesh(
        oc.batch_size, oc.validation_batch_size, n_cp=n_cp, n_tp=n_tp, n_fsdp=n_fsdp
    )
    state_shardings = None  # set by the first place_state on tp/fsdp layouts
    if n_tp > 1 or n_fsdp > 1:
        from .sharding import make_state_shardings

        strict_sharding = bool(tc.get("strict_sharding", False))

        @host_spanned("startup/state", id="startup")
        def place_state(s):
            nonlocal state_shardings
            state_shardings = make_state_shardings(s, mesh, strict=strict_sharding)
            return jax.device_put(s, state_shardings)

    else:
        place_state = lambda s: replicate(s, mesh)  # noqa: E731
    place_batch = shard_batch_cp if n_cp > 1 else shard_batch

    def train_batches(epoch: int, skip: int):
        """The epoch's training batch stream (padded or packed)."""
        if not use_packed:
            return train_pyd.batches(
                oc.batch_size, shuffle=True, seed=cfg.seed + epoch, skip_batches=skip
            )
        import itertools

        packed = (
            b
            for b in train_pyd.packed_batches(
                oc.batch_size, seq_len=packed_L, seed=cfg.seed + epoch
            )
            # A short final packed batch would retrigger compilation.
            if b.event_mask.shape[0] == oc.batch_size
        )
        # Packing is deterministic per seed, so mid-epoch resume re-derives
        # and discards the first `skip` batches (collation cost only).
        return itertools.islice(packed, skip, None)

    # Initialize from the first training batch's shapes.
    if len(train_pyd) < oc.batch_size:
        raise ValueError(
            f"Train split has {len(train_pyd)} subjects but batch_size is "
            f"{oc.batch_size}; training batches drop the last short batch, so "
            "no batch can be formed. Lower optimization_config.batch_size."
        )
    init_iter = train_batches(epoch=0, skip=0)
    try:
        init_batch = next(init_iter)
    except StopIteration:
        raise ValueError(
            "No full training batch could be formed; lower optimization_config.batch_size."
        ) from None
    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, init_batch)
    state = TrainState(
        step=jnp.zeros((), dtype=jnp.int32), params=params, opt_state=tx.init(params)
    )
    state = place_state(state)

    log_every = int(tc.get("log_every_n_steps") or 10)
    ckpt_every = int(tc.get("checkpoint_every_n_steps") or 100)
    keep = int(tc.get("max_checkpoints_to_keep") or 2)
    profile_dir = tc.get("profile_dir")

    # Reliability subsystem (eventstreamgpt_tpu/reliability/): hardened
    # checkpoint I/O (retry/backoff + checksum manifests + walk-back),
    # the divergence sentinel with bounded rollback, graceful preemption,
    # and the deterministic fault hooks CI drives all of it with. Imported
    # lazily (like CompileGuard) so the module graph stays cycle-free.
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

    ckpt_mgr = ReliableCheckpointManager(
        save_dir / "model_checkpoints",
        max_to_keep=keep,
        save_interval_steps=1,
        retries=int(tc.get("ckpt_retries", 3)),
        backoff_base=float(tc.get("ckpt_backoff_base", 0.5)),
    )
    start_epoch = 0
    skip_batches = 0
    if cfg.do_resume_from_checkpoint and ckpt_mgr.latest_step() is not None:
        # Shared auto-resume (reliability/integrity.py): walk-back restore of
        # the newest verifiable checkpoint with readable resume metadata — a
        # corrupt or partially-written latest step degrades the relaunch
        # instead of crashing it, and a mid-epoch (preemption) checkpoint
        # re-enters its epoch past the batches already trained on (batch
        # order is deterministic per cfg.seed + epoch: the skip is rng-exact).
        state, _, start_epoch, skip_batches = resume_training_state(
            ckpt_mgr, state, place_state
        )

    # tp/fsdp layouts pin the output state to the input layout (see
    # make_train_step: unpinned propagation reshards replicated leaves over
    # `model`, silently dropping their donation).
    train_step = make_train_step(
        model, tx, with_health=with_health, out_state_shardings=state_shardings
    )
    eval_step = make_eval_step(model)

    # Device-resident data (round-5 feed-path redesign; data/device_dataset.py):
    # keep the dataset in HBM and run k on-device-collate + train steps per
    # dispatch. 'auto' enables it when the tables fit a conservative HBM
    # budget: single-process runs use the replicated layout, multi-process
    # runs the sharded layout (each process uploads its subject-pool shard
    # over the mesh's data axis and the plan stream is dealt shard-major —
    # see DeviceDataset.create). Numerics are bit-identical to host collation
    # of the same plan stream (tested), so this is purely a throughput
    # decision.
    resident_mode = tc.get("device_resident_data", "auto")
    resident_budget = int(
        tc.get("device_resident_max_bytes") or DeviceDataset.DEFAULT_BUDGET_BYTES
    )
    device_train = device_tuning = None
    if n_fsdp > 1:
        # The resident tables shard over the `data` axis and deal plans per
        # data shard; an fsdp axis splits the batch dimension further than
        # the plan stream deals. Host collation + shard_batch handles the
        # (data, fsdp) layout; the resident fast path is an open follow-up.
        if resident_mode is True:
            raise ValueError(
                "device_resident_data: true is not supported with fsdp_shards > 1; "
                "use 'auto' (host collation) for FSDP runs."
            )
        resident_mode = False
    if resident_mode is True:
        # Explicit opt-in: unsupported topologies (and shard-indivisible
        # batch sizes) raise a clear error here instead of a full epoch in.
        device_train = DeviceDataset.create(
            train_pyd, mesh=mesh, context_parallel=n_cp > 1,
            batch_sizes=(oc.batch_size, oc.validation_batch_size),
        )
        device_tuning = DeviceDataset.create(
            tuning_pyd, mesh=mesh, context_parallel=n_cp > 1,
            batch_sizes=(oc.validation_batch_size,),
        )
    elif resident_mode == "auto":
        device_train = DeviceDataset.try_create(
            train_pyd, mesh=mesh, context_parallel=n_cp > 1, max_bytes=resident_budget,
            batch_sizes=(oc.batch_size, oc.validation_batch_size),
        )
        if device_train is not None:
            device_tuning = DeviceDataset.try_create(
                tuning_pyd, mesh=mesh, context_parallel=n_cp > 1, max_bytes=resident_budget,
                batch_sizes=(oc.validation_batch_size,),
            )
    print(
        "feed: device-resident (DeviceDataset, on-device collation)"
        if device_train is not None
        else "feed: host collation (JaxDataset batches + shard_batch per step)"
    )
    chunk_steps = tc.get("steps_per_execution") or "auto"
    if chunk_steps == "auto":
        # Align with the logging cadence so windowed records keep their
        # meaning; 16 steps/dispatch already amortizes dispatch overhead to
        # a few percent.
        chunk_steps = max(min(log_every, ckpt_every, 16), 1)
    chunk_steps = int(chunk_steps)
    # A routed feed-forward's counters ride out beside the health vector and
    # reach train_log.jsonl at the log flush (the scanned path only).
    with_routing = with_health and "routed" in config.ffn_layers
    chunked_step = (
        make_chunked_train_step(
            model,
            tx,
            device_train,
            packed=use_packed,
            with_health=with_health,
            out_state_shardings=state_shardings,
            with_routing=with_routing,
        )
        if device_train is not None
        else None
    )

    # Recompilation sentinel (analysis/compile_guard.py): every steady-state
    # shape is seen during the first in-process epoch, so from the second
    # epoch on the active step function must dispatch cached executables
    # only. Armed per epoch, checked after every full-shape dispatch
    # (handle_window); a mid-epoch recompile — drifting batch shape, weak
    # type — fails the run immediately instead of silently training at
    # compile speed. trainer_config.guard_recompiles=False opts out.
    step_guard = None
    if bool(tc.get("guard_recompiles", True)):
        from ..analysis.compile_guard import CompileGuard

        step_guard = CompileGuard(
            watch=[chunked_step if chunked_step is not None else train_step],
            label="pretrain step (mid-epoch)",
        )

    def train_plan_chunks(epoch: int, skip: int):
        if use_packed:
            return device_train.packed_plan_chunks(
                oc.batch_size,
                chunk_steps,
                seq_len=packed_L,
                seed=cfg.seed + epoch,
                skip_batches=skip,
            )
        return device_train.plan_chunks(
            oc.batch_size, chunk_steps, shuffle=True, seed=cfg.seed + epoch, skip_batches=skip
        )

    log_fp = save_dir / "train_log.jsonl" if is_main else None

    def log_record(rec: dict) -> None:
        if log_fp is not None:
            with open(log_fp, "a") as f:
                f.write(json.dumps(rec) + "\n")

    # The program's own host record (utils/scopes.py) written out: one
    # ``startup`` line once the first full dispatch has returned (the phases'
    # self seconds, the compile spans by program, the persistent cache's hits
    # and misses), and from then on a ``compile`` line for every program a
    # dispatch compiled: which step recompiled. Both wait for the next flush.
    started = False

    def note_dispatch(dispatch: host_span, full: bool, pending: list) -> None:
        nonlocal started
        if started:
            compiled = scopes.since(dispatch.start)  # nothing, dispatch after dispatch
            if compiled:
                for program, row in scopes.summary(compiled, small=0.0)["compile"].items():
                    pending.append({"compile": program, "step": global_step, **row})
        elif full:
            started = True
            spans = [s for s in scopes.recorded() if s.start >= run_began]
            pending.append(
                {
                    "startup": scopes.summary(spans),
                    "step": global_step,
                    "wall_s": time.perf_counter() - spans[0].start,
                    "since_process_start_s": scopes.seconds_since_process_start(),
                    **scopes.compile_totals(),
                }
            )

    best_tuning_loss = float("inf")
    epochs_since_best = 0
    global_step = int(jax.device_get(state.step))
    # max_training_steps counts *optimizer* steps (what the LR schedule sees);
    # with gradient accumulation each optimizer step spans `accum` loop steps.
    accum = oc.gradient_accumulation or 1
    stop = False
    profiling = False

    # Context parallelism: ring attention engages whenever the config asks
    # for it AND a ring context is active during tracing. Activating it for
    # the whole fit (incl. tuning eval) keeps train and eval numerics on the
    # same path; it is tracing-time (thread-local) state only, restored on
    # exit — also on error — so subsequent in-process runs (ASHA rungs)
    # start clean.
    import contextlib

    ring_cm = contextlib.nullcontext()
    if n_cp > 1:
        from ..parallel import ring_context

        ring_cm = ring_context(mesh)
    # Pallas kernels run once per batch shard on a multi-device mesh (GSPMD
    # cannot partition a Mosaic call; parallel/context.py). Like the ring
    # context this is tracing-time state, active for every step this fit
    # traces: the train loop here and the final validation below.
    from ..parallel.context import kernel_mesh

    # The guard arms only after a FULL in-process epoch: a resumed partial
    # epoch (skip_batches) can consist solely of a short tail chunk, which
    # would leave the full-chunk executable uncompiled until the next epoch —
    # a legitimate compile that must not trip the sentinel.
    full_epoch_completed_in_process = False
    shutdown = GracefulShutdown()
    # A while-loop, not a range: divergence rollback rewinds the walker —
    # restoring the last good checkpoint may re-enter the same epoch (or an
    # earlier one) with a fresh skip point past the poisoned window.
    resume_epoch, resume_skip = start_epoch, skip_batches
    epoch = start_epoch
    with ring_cm, kernel_mesh(mesh), shutdown:
        while epoch < oc.max_epochs:
            if step_guard is not None:
                if full_epoch_completed_in_process:
                    step_guard.arm()
                else:
                    step_guard.disarm()  # warm-up: compiles are expected
            epoch_t0 = time.perf_counter()
            window_t0, window_events, window_n = time.perf_counter(), 0, 0
            window_losses: list = []
            window_routing: list = []
            window_visited: list = []
            epoch_skip = resume_skip if epoch == resume_epoch else 0
            if rollback_ctl is not None:
                # Excise any window a previous rollback marked poisoned: the
                # epoch's batch order is deterministic, so a data-caused
                # fault would simply re-fire if these batches were retrained.
                epoch_skip = rollback_ctl.epoch_skip(epoch, epoch_skip)
            epoch_progress = epoch_skip  # epoch-order batches consumed so far
            preempt_requested = False
            # The shared health buffer + inspection gate (reliability/
            # sentinel.py): dispatches `record` their device flags without
            # readback; `inspect` runs only at the existing flush cadence
            # (checkpoint saves, epoch end) where the pipeline drains anyway,
            # so the sentinel adds no host sync to the dispatch loop.
            health_mon = HealthMonitor(sentinel)

            def flush_window() -> dict:
                """Closes the current logging window into a record whose
                losses stay device arrays (`finalize_record` converts)."""
                nonlocal window_t0, window_events, window_n, window_losses, window_routing, window_visited
                dt = time.perf_counter() - window_t0
                rec = {
                    "split": str(Split.TRAIN),
                    "epoch": epoch,
                    "step": global_step,
                    "_losses": [jnp.atleast_1d(l) for l in window_losses],
                    "_routing": window_routing,
                    "_visited": window_visited,
                    "events_per_sec": window_events / dt if dt > 0 else None,
                    "step_time_ms": 1000.0 * dt / max(window_n, 1),
                }
                window_t0, window_events, window_n = time.perf_counter(), 0, 0
                window_losses, window_routing, window_visited = [], [], []
                return rec

            def finalize_record(rec: dict) -> None:
                """Epoch-end flush: the only place window losses (and the lr
                schedule, a tiny eager jnp computation) touch the host."""
                if "_losses" not in rec:  # a startup or compile line: host numbers as they are
                    return log_record(rec)
                rec["train_loss"] = float(jnp.mean(jnp.concatenate(rec.pop("_losses"))))  # graftcheck: allow GC001 -- epoch-end flush, dispatch loop already drained
                rec["lr"] = float(lr_schedule(rec["step"] // accum))  # graftcheck: allow GC001 -- epoch-end flush, dispatch loop already drained
                routing = rec.pop("_routing")
                if routing:
                    routing = np.concatenate([np.asarray(r) for r in routing])  # graftcheck: allow GC001 -- epoch-end flush, dispatch loop already drained
                    rec["moe_pairs_per_step"] = float(routing[:, 0].mean())  # graftcheck: allow GC001 -- a host array, read back on the line above at the flush
                    rec["moe_load_max"] = int(routing[:, 1].max())  # graftcheck: allow GC001 -- a host array, as above
                visited = rec.pop("_visited")
                if visited:
                    rec["attn_blocks_visited_share"] = sum(visited) / len(visited)  # host floats, one a dispatch
                log_record(rec)

            def flush_logs(pending: list) -> None:
                with host_span("log_flush"):
                    for rec in pending:
                        finalize_record(rec)
                    pending.clear()

            def handle_window(step_in_epoch: int, stepped: int, pending: list):
                """Shared per-dispatch bookkeeping: logs, checkpoints, stop.

                ``stepped`` is how many optimizer-loop steps the last dispatch
                advanced (1 for the per-batch path, k for a scanned chunk) —
                cadences fire when the counter crosses a multiple. Window
                records buffer their losses as device arrays in ``pending``
                for an epoch-end flush (a float() here would block the
                dispatch pipeline on a data-plane round trip every window;
                GC001).
                """
                nonlocal stop, preempt_requested
                if global_step % log_every < stepped:
                    pending.append(flush_window())
                if global_step % ckpt_every < stepped:
                    # Shared inspect-then-save gate (HealthMonitor.vetted_save):
                    # sentinel inspection rides the checkpoint cadence and the
                    # save commits only when THIS window vetted healthy — a
                    # bad-but-below-streak window must never become a poisoned
                    # rollback target. Checkpointing IS a host readback; the
                    # cadence (ckpt_every) bounds how often the pipeline
                    # drains.
                    with host_span("checkpoint"):
                        saved = health_mon.vetted_save(
                            ckpt_mgr,
                            global_step,
                            lambda: serialization.to_state_dict(jax.device_get(state)),  # graftcheck: allow GC001 -- checkpoint readback + sentinel inspection, cadence-bounded
                            {
                                "epoch": epoch,
                                "epoch_complete": False,
                                "step_in_epoch": step_in_epoch,
                            },
                            epoch=epoch,
                            progress=step_in_epoch,
                        )
                    if saved:
                        # The device_get above already drained the pipeline, so
                        # persisting the buffered window records here costs no
                        # extra sync — and bounds what a SIGKILL-style preemption
                        # can lose from train_log.jsonl to ckpt_every steps.
                        flush_logs(pending)
                if step_guard is not None and step_guard.armed:
                    if chunked_step is None or stepped == chunk_steps:
                        # Steady state: the watched step function must not
                        # have grown a new executable.
                        step_guard.check()
                    elif step_guard.compiles > 0:
                        # A short tail chunk legitimately owns its shape (and
                        # repacking can shift its length between epochs):
                        # absorb its compile by re-baselining rather than
                        # tripping on the next full-shape dispatch. Clean
                        # short dispatches leave the baseline untouched so
                        # full-shape checks keep their bite.
                        step_guard.arm()
                if (
                    oc.max_training_steps is not None
                    and global_step // accum >= oc.max_training_steps
                ):
                    stop = True
                if shutdown.requested:
                    # Graceful preemption: this chunk boundary is the drain
                    # point; the final checkpoint is written once the
                    # dispatch loops unwind (reliability/preemption.py).
                    preempt_requested = True

            # Window records buffer device losses and flush once the dispatch
            # loop exits — in a finally, so a mid-epoch failure (step error,
            # RecompileError, preemption-triggered teardown) still writes the
            # trajectory leading up to it instead of losing the epoch's log.
            pending_logs: list[dict] = []
            try:
                if chunked_step is not None:
                    # Device-resident scanned training: k collate+step
                    # iterations per dispatch, ~100-byte plans on the wire
                    # (the production fast path; bit-identical numerics to
                    # the branch below).
                    step_in_epoch = epoch_skip
                    for plans, n_events in train_plan_chunks(epoch, epoch_skip):
                        k = int(next(iter(plans.values())).shape[0])
                        chunk, counts = n_events.id, n_events.counts  # the chunk's plan span: events, slots, the flash op's pairs
                        if oc.max_training_steps is not None:
                            remaining = oc.max_training_steps * accum - global_step
                            if remaining < k:
                                plans = {key_: v[:remaining] for key_, v in plans.items()}
                                k = remaining
                                # Recount from the kept plans only — the chunk's
                                # counts include the dropped plans' events.
                                counts = device_train.plan_counts(plans) if k > 0 else {"events": 0}
                                n_events = counts["events"]
                        if k <= 0:
                            break
                        # Profile the dispatch(es) overlapping steps [10, 20),
                        # once — same window as the per-batch path.
                        if (
                            profile_dir and not profiling
                            and global_step < 20 and global_step + k > 10
                        ):
                            jax.profiler.start_trace(str(profile_dir))
                            profiling = True
                        with host_span("dispatch", id=chunk) as dispatch:
                            if with_health:
                                state, (losses, healths, *routings) = chunked_step(state, device_train.arrays, plans, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                                health_mon.record(healths)
                                window_routing.extend(routings)
                            else:
                                state, losses = chunked_step(state, device_train.arrays, plans, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                        if "pairs_dense" in counts:  # what the flash op's walk visits, counted at the plan
                            window_visited.append(counts["pairs_visited"] / counts["pairs_dense"])
                        global_step += k
                        note_dispatch(dispatch, k == chunk_steps, pending_logs)
                        step_in_epoch += k
                        epoch_progress = step_in_epoch
                        faults.maybe_sigterm(global_step, shutdown)
                        window_events += n_events
                        window_losses.append(losses)
                        window_n += k
                        if profiling and global_step >= 20:
                            jax.profiler.stop_trace()
                            profiling = False
                        handle_window(step_in_epoch, k, pending_logs)
                        if stop or health_mon.rollback_requested or preempt_requested:
                            break
                else:
                    # Asynchronous host input pipeline: collation + device_put
                    # run in a background thread with a depth-2 device buffer,
                    # so the host path overlaps the previous step's compute
                    # Event counts are computed host-side in
                    # the worker — reading them here would otherwise force a
                    # device sync every step.
                    batch_iter = prefetch_to_device(
                        # Fault injection (reliability/faults.py): a no-op
                        # pass-through unless a plan scripts a poisoned batch
                        # for this epoch's deterministic order.
                        faults.wrap_batches(
                            train_batches(epoch, epoch_skip),
                            epoch=epoch,
                            first_index=epoch_skip,
                        ),
                        lambda b: place_batch(b, mesh),
                        host_stats_fn=lambda b: int(b.event_mask.sum()),
                    )
                    try:
                        for step_in_epoch, (batch, n_events) in enumerate(
                            batch_iter, start=epoch_skip
                        ):
                            if profile_dir and not profiling and 10 <= global_step < 20:
                                jax.profiler.start_trace(str(profile_dir))
                                profiling = True
                            with host_span("dispatch", id=global_step) as dispatch:
                                if with_health:
                                    state, (loss, health) = train_step(state, batch, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                                    health_mon.record(health)
                                else:
                                    state, loss = train_step(state, batch, rng)  # graftcheck: allow GC003 -- step body folds rng with state.step; constant base key is the dropout-stream contract
                            global_step += 1
                            note_dispatch(dispatch, True, pending_logs)
                            epoch_progress = step_in_epoch + 1
                            faults.maybe_sigterm(global_step, shutdown)
                            window_events += n_events
                            # Keep the loss on device: converting every step
                            # would sync the host with the device and serialize
                            # collation with compute.
                            window_losses.append(loss)
                            window_n += 1
                            if profiling and global_step >= 20:
                                jax.profiler.stop_trace()
                                profiling = False
                            handle_window(step_in_epoch + 1, 1, pending_logs)
                            if stop or health_mon.rollback_requested or preempt_requested:
                                break
                    finally:
                        batch_iter.close()
            finally:
                flush_logs(pending_logs)
            if profiling:
                jax.profiler.stop_trace()
                profiling = False

            # Post-epoch recovery tail (reliability/sentinel.py finish_epoch,
            # shared verbatim with fine-tuning): vets the tail window,
            # executes a pending rollback, or drains a pending preemption
            # (raising Preempted after the tail-gated final checkpoint). The
            # returned verdict gates the epoch-end checkpoint below.
            outcome = finish_epoch(
                health_mon=health_mon,
                rollback_ctl=rollback_ctl,
                ckpt_mgr=ckpt_mgr,
                shutdown=shutdown,
                state=state,
                place_state=place_state,
                log_record=log_record,
                epoch=epoch,
                epoch_progress=epoch_progress,
                global_step=global_step,
                accum=accum,
                max_training_steps=oc.max_training_steps,
                label="pretraining",
            )
            if outcome.action == "rollback":
                state = outcome.state
                global_step = outcome.global_step
                resume_epoch, resume_skip = outcome.resume_epoch, outcome.resume_skip
                stop = outcome.stop
                epoch = resume_epoch
                continue
            tail_healthy = outcome.tail_healthy

            if epoch_skip == 0:
                full_epoch_completed_in_process = True

            # Tuning eval (loss-only under the default pretraining metrics config).
            rng, eval_key = jax.random.split(rng)  # graftcheck: allow GC003 -- train consumptions above only fold_in; this split advances the base stream
            with host_span("eval"):
                tuning_metrics = evaluate(
                    eval_step,
                    state.params,
                    tuning_pyd,
                    oc.validation_batch_size,
                    config,
                    cfg.pretraining_metrics_config,
                    Split.TUNING,
                    mesh=mesh,
                    key=eval_key,
                    place_batch=place_batch,
                    device_data=device_tuning,
                )
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
            print(
                f"epoch {epoch}: opt step {global_step // accum}/"
                f"{oc.max_training_steps or steps_per_epoch * oc.max_epochs}"
                f" tuning_loss={tuning_loss:.4f}"
            )

            if tail_healthy:
                with host_span("checkpoint"):
                    ckpt_mgr.save(
                        global_step,
                        serialization.to_state_dict(jax.device_get(state)),  # graftcheck: allow GC001 -- epoch-end checkpoint readback, pipeline already drained by eval
                        metadata={"epoch": epoch, "epoch_complete": True},
                    )

            # Early stopping (reference EarlyStopping(monitor="tuning_loss")).
            if np.isfinite(tuning_loss) and tuning_loss < best_tuning_loss - 1e-12:
                best_tuning_loss = tuning_loss
                epochs_since_best = 0
            else:
                epochs_since_best += 1
                # Lightning EarlyStopping semantics: stop once the wait count
                # reaches patience (the Nth consecutive non-improving epoch).
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
    device_held_out = (
        DeviceDataset.try_create(
            held_out_pyd, mesh=mesh, context_parallel=n_cp > 1, max_bytes=resident_budget,
            batch_sizes=(oc.validation_batch_size,),
        )
        if device_train is not None
        else None
    )
    rng, k1, k2 = jax.random.split(rng, 3)
    with kernel_mesh(mesh):
        final_tuning = evaluate(
            eval_step,
            state.params,
            tuning_pyd,
            oc.validation_batch_size,
            config,
            cfg.final_validation_metrics_config,
            Split.TUNING,
            mesh=mesh,
            key=k1,
            place_batch=place_batch,
            device_data=device_tuning,
        )
        final_held_out = evaluate(
            eval_step,
            state.params,
            held_out_pyd,
            oc.validation_batch_size,
            config,
            cfg.final_validation_metrics_config,
            Split.HELD_OUT,
            mesh=mesh,
            key=k2,
            place_batch=place_batch,
            device_data=device_held_out,
        )

    if is_main:
        print("Saving final metrics...")
        with open(save_dir / "tuning_metrics.json", "w") as f:
            json.dump(final_tuning, f)
        with open(save_dir / "held_out_metrics.json", "w") as f:
            json.dump(final_held_out, f)

    ckpt_mgr.close()
    return final_tuning.get("tuning_loss"), final_tuning, final_held_out
