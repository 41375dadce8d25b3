"""Output dataclasses and the shared generative output layer.

Rebuild of ``/root/reference/EventStream/transformer/model_output.py`` (the
output dataclasses ``:208-1232`` and ``GenerativeOutputLayerBase`` ``:1234``).
Loss semantics are reproduced exactly — the nested masked macro-averages
(per-label → per-event → per-subject → batch), the is-observed Bernoulli
terms, and the TTE "fake last observation" trick (``:1345-1350``) — because
held-out NLL parity with the reference is judged on them (SURVEY.md §7).

Differences from the reference are representational only:

* Output containers are ``flax.struct`` pytrees, so whole outputs flow
  through ``jit``/``scan`` and slicing a predictions container is a
  ``tree_map`` (replacing ``NestedIndexableMixin``, ``:172``).
* Distributions are the JAX pytree distributions of
  `eventstreamgpt_tpu.distributions`.
* The layer is a flax module; per-measurement heads hang off static config.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from ..data.types import DataModality, EventStreamBatch
from ..distributions import Bernoulli, Categorical
from ..ops import safe_weighted_avg, weighted_loss
from ..ops.impl_select import LANE
from ..ops.pallas_multihot import multihot_any
from ..utils.scopes import scoped
from .config import (
    StructuredTransformerConfig,
    TimeToEventGenerationHeadType,
)
from .generative_layers import (
    ExponentialTTELayer,
    GaussianIndexedRegressionLayer,
    GaussianRegressionLayer,
    LogNormalMixtureTTELayer,
)

Array = Any


@struct.dataclass
class GenerativeSequenceModelLosses:
    """Per-head losses (reference ``model_output.py:228``)."""

    classification: Optional[dict[str, Array]] = None
    regression: Optional[dict[str, Array]] = None
    time_to_event: Optional[Array] = None


@struct.dataclass
class GenerativeSequenceModelPredictions:
    """Predicted distributions per head (reference ``model_output.py:1073``).

    ``classification`` maps measurement → ``(is_observed_dist | None, dist)``;
    ``regression`` maps measurement → ``(is_observed_dist | None, dist)``.
    Slicing the whole container is a tree_map (replaces
    ``NestedIndexableMixin`` + ``idx_distribution``).
    """

    classification: Optional[dict[str, tuple]] = None
    regression: Optional[dict[str, tuple]] = None
    regression_indices: Optional[dict[str, Array]] = None
    time_to_event: Optional[Any] = None

    def slice(self, index) -> "GenerativeSequenceModelPredictions":
        return jax.tree_util.tree_map(lambda x: x[index], self)


@struct.dataclass
class GenerativeSequenceModelLabels:
    """Labels per head (reference ``model_output.py:1168``)."""

    classification: Optional[dict[str, Array]] = None
    regression: Optional[dict[str, Array]] = None
    regression_indices: Optional[dict[str, Array]] = None
    time_to_event: Optional[Array] = None


@struct.dataclass
class GenerativeSequenceModelOutput:
    """Full generative model output (reference ``model_output.py:1189``)."""

    loss: Optional[Array] = None
    losses: Optional[GenerativeSequenceModelLosses] = None
    preds: Optional[GenerativeSequenceModelPredictions] = None
    labels: Optional[GenerativeSequenceModelLabels] = None
    event_mask: Optional[Array] = None
    dynamic_values_mask: Optional[Array] = None
    past_key_values: Optional[tuple] = None
    hidden_states: Optional[tuple] = None
    attentions: Optional[tuple] = None
    # NA: per-layer contextualized event embeddings (the spec-verify history
    # head state; populated only when requested).
    contextualized: Optional[tuple] = None


@struct.dataclass
class StreamClassificationModelOutput:
    """Fine-tuning classification output (reference ``model_output.py:1219``)."""

    loss: Array
    preds: Optional[Array] = None
    labels: Optional[Array] = None


def get_event_types(
    dynamic_measurement_indices,
    dynamic_indices,
    event_type_measurement_idx: int,
    event_type_vocab_offset: int,
):
    """Per-event event-type vocabulary indices (local to the event-type vocab).

    Reference: ``model_output.py:41-105``. Every event carries exactly one
    ``event_type`` data element; this extracts its index and rebases it by the
    measurement's vocab offset. Works on numpy or jnp arrays (the zero-shot
    labeler surface is host numpy).

    Examples:
        >>> import numpy as np
        >>> meas = np.asarray([[[1, 2, 0], [1, 2, 2]]])
        >>> idx = np.asarray([[[3, 7, 0], [4, 8, 9]]])
        >>> get_event_types(meas, idx, event_type_measurement_idx=1,
        ...                 event_type_vocab_offset=1)
        array([[2, 3]])
    """
    is_event_type = dynamic_measurement_indices == event_type_measurement_idx
    event_type_indices = (dynamic_indices * is_event_type).sum(-1)
    return event_type_indices - event_type_vocab_offset


def get_measurement_vocab_slice(config: StructuredTransformerConfig, measurement: str) -> tuple[int, int]:
    """[vocab_start, vocab_end) of a measurement in the unified vocabulary.

    Reference: ``model_output.py:1460-1466``.
    """
    vocab_start = config.vocab_offsets_by_measurement[measurement]
    vocab_end = min(
        o for o in list(config.vocab_offsets_by_measurement.values()) + [config.vocab_size] if o > vocab_start
    )
    return vocab_start, vocab_end


class VocabProjection(nn.Module):
    """The unified-vocabulary classification head, column-sliceable.

    A drop-in replacement for the ``nn.Dense`` classification layer with an
    identical parameter tree (``kernel``/``bias``, same shapes, same
    lecun-normal/zeros initializers — existing checkpoints load unchanged)
    whose ``__call__`` can project just a ``[start, end)`` span of output
    columns. Each output column ``y[v] = x · kernel[:, v] + bias[v]`` is
    independent of every other column, so a narrow projection computes
    exactly the columns the caller would otherwise slice from the full
    plane — without paying the full ``(hidden, vocab)`` matmul. The NA
    output layer's per-level walk uses this (head-stack lever, r06 MFU
    round): a level predicting one small measurement (e.g. ``event_type``,
    ~1% of the unified vocabulary) no longer projects and discards the
    other ~99% of the plane. Parameters are declared in ``setup`` so they
    exist even when every call in a trace is narrow.

    Note for tensor-parallel layouts: ``training/sharding.py`` shards
    ``kernel`` column-wise over the ``model`` axis; narrow projections
    slice that axis, which GSPMD handles but may pay a gather — the
    audited TP layouts (CI models) never take the narrow path, and
    ``head_narrow_projections=False`` restores full-plane projection.
    """

    features: int
    in_features: int
    dtype: Any = jnp.float32

    def setup(self):
        self.kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.in_features, self.features)
        )
        self.bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))

    def __call__(self, x: Array, vocab_slice: tuple[int, int] | None = None) -> Array:
        kernel, bias = self.kernel, self.bias
        if vocab_slice is not None:
            start, end = vocab_slice
            kernel = kernel[:, start:end]
            bias = bias[start:end]
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        return x @ kernel + bias


class GenerativeOutputLayerBase(nn.Module):
    """Shared output layer: TTE head + is-observed head + unified
    classification head + per-measurement regression heads.

    Reference: ``model_output.py:1234-1721``. Subclasses (CI/NA) decide which
    encoded representations feed which prediction.
    """

    config: StructuredTransformerConfig

    def setup(self):
        cfg = self.config
        if cfg.TTE_generation_layer_type == TimeToEventGenerationHeadType.LOG_NORMAL_MIXTURE:
            self.TTE_layer = LogNormalMixtureTTELayer(
                num_components=cfg.TTE_lognormal_generation_num_components,
                mean_log_inter_time=cfg.mean_log_inter_event_time_min,
                std_log_inter_time=cfg.std_log_inter_event_time_min,
            )
        elif cfg.TTE_generation_layer_type == TimeToEventGenerationHeadType.EXPONENTIAL:
            self.TTE_layer = ExponentialTTELayer()
        else:
            raise ValueError(
                f"Invalid option for `config.TTE_generation_layer_type`. Must be "
                f"a member of the `TimeToEventGenerationHeadType` enum: "
                f"({TimeToEventGenerationHeadType.values()}). got {cfg.TTE_generation_layer_type}."
            )

        # Head matmuls run in the compute dtype (the vocab-size classification
        # projection is the largest matmul in the model); logits are upcast to
        # fp32 before any log-prob/loss math below.
        dt = cfg.compute_dtype
        self.IsObservedLayer = nn.Dense(len(cfg.measurements_idxmap), dtype=dt, name="IsObservedLayer")
        # Column-sliceable unified classification head (same param tree as
        # the nn.Dense it replaces): per-level NA calls project only their
        # measurements' vocabulary span instead of the full plane.
        self.ClassificationLayer = VocabProjection(
            features=cfg.vocab_size,
            in_features=cfg.hidden_size,
            dtype=dt,
            name="ClassificationLayer",
        )

        regression_layers = {}
        for measurement in cfg.measurements_for(DataModality.MULTIVARIATE_REGRESSION):
            regression_layers[measurement] = GaussianIndexedRegressionLayer(
                n_regression_targets=cfg.vocab_sizes_by_measurement[measurement],
                dtype=dt,
                name=f"regression_layer_{measurement}",
            )
        for measurement in cfg.measurements_for(DataModality.UNIVARIATE_REGRESSION):
            if measurement in regression_layers:
                raise ValueError(f"{measurement} duplicated!")
            regression_layers[measurement] = GaussianRegressionLayer(
                dtype=dt, name=f"regression_layer_{measurement}"
            )
        self.regression_layers = regression_layers

        classification_mode_per_measurement = {}
        for generative_mode, measurements in cfg.measurements_per_generative_mode.items():
            if generative_mode not in (
                DataModality.SINGLE_LABEL_CLASSIFICATION,
                DataModality.MULTI_LABEL_CLASSIFICATION,
            ):
                continue
            for measurement in measurements:
                assert measurement not in classification_mode_per_measurement
                classification_mode_per_measurement[measurement] = generative_mode
        self.classification_mode_per_measurement = classification_mode_per_measurement

    # ------------------------------------------------------------------ TTE
    @scoped("heads_tte")
    def get_TTE_outputs(self, batch: EventStreamBatch, encoded: Array, is_generation: bool = False):
        """TTE distribution + average log-likelihood (**not** NLL).

        Reference: ``model_output.py:1311-1372``, including the fake last
        observation appended so the returned distribution covers the final
        event for generation.
        """
        TTE_dist = self.TTE_layer(encoded)

        if is_generation:
            return None, TTE_dist, None

        TTE_obs_mask = batch.event_mask[:, 1:] & batch.event_mask[:, :-1]
        if batch.segment_ids is not None:
            # Packed rows: the gap between one subject's last event and the
            # next subject's first is not a real inter-event time.
            TTE_obs_mask = TTE_obs_mask & (batch.segment_ids[:, 1:] == batch.segment_ids[:, :-1])
        TTE_delta = batch.time_delta[:, :-1]
        TTE_true = jnp.where(TTE_obs_mask, TTE_delta, 1.0)

        TTE_true_exp = jnp.concatenate((TTE_true, jnp.ones_like(TTE_true[:, -1:])), axis=-1)
        TTE_obs_mask_exp = jnp.concatenate(
            (TTE_obs_mask, jnp.zeros_like(TTE_obs_mask[:, -1:])), axis=-1
        )

        TTE_LL = TTE_dist.log_prob(TTE_true_exp)

        obs = TTE_obs_mask_exp.astype(jnp.float32)
        # Parity note: the reference divides by the raw count and would produce
        # inf/NaN for an event-free subject (it raises instead); we guard the
        # denominator so jit-compiled training never NaNs, matching results
        # whenever the reference's own validity precondition holds.
        denom = jnp.maximum(obs.sum(-1), 1.0)
        TTE_LL_per_patient = (TTE_LL * obs).sum(-1) / denom
        TTE_LL_overall = TTE_LL_per_patient.mean()

        return TTE_LL_overall, TTE_dist, TTE_true

    # -------------------------------------------------------- classification
    @scoped("heads_cls")
    def get_classification_outputs(
        self, batch: EventStreamBatch, encoded: Array, valid_measurements: set[str]
    ):
        """Classification losses/distributions/labels per measurement.

        Reference: ``model_output.py:1374-1549``; see that docstring for the
        averaging contracts (label → event → subject → batch macro-averages).
        """
        if not valid_measurements:
            return {}, {}, {}

        is_observed_score = self.IsObservedLayer(encoded).astype(jnp.float32)

        # Head-stack lever (r06 MFU round): when this call covers only a
        # narrow span of the unified vocabulary — the NA per-level walk,
        # where e.g. the event_type level needs ~1% of the columns — project
        # just those spans of the head kernel (column-exact; see
        # `VocabProjection`). Calls covering most of the vocabulary (every
        # CI call, the wide NA levels) keep the single full-plane matmul,
        # which is the efficient shape there.
        todo = [
            m for m in self.classification_mode_per_measurement if m in valid_measurements
        ]
        spans = {m: get_measurement_vocab_slice(self.config, m) for m in todo}
        narrow = (
            getattr(self.config, "head_narrow_projections", True)
            and 2 * sum(end - start for start, end in spans.values()) <= self.config.vocab_size
        )
        classification_scores = (
            None if narrow else self.ClassificationLayer(encoded).astype(jnp.float32)
        )

        losses, dists, labels_out = {}, {}, {}

        for measurement, classification_mode in self.classification_mode_per_measurement.items():
            if measurement not in valid_measurements:
                continue

            event_mask = batch.event_mask
            measurement_idx = self.config.measurements_idxmap[measurement]
            vocab_start, vocab_end = spans[measurement]

            scores = (
                self.ClassificationLayer(
                    encoded, vocab_slice=(vocab_start, vocab_end)
                ).astype(jnp.float32)
                if narrow
                else classification_scores[:, :, vocab_start:vocab_end]
            )
            # measurement_idx 0 is withheld for missing data, hence the -1.
            is_obs_score = is_observed_score[:, :, measurement_idx - 1]

            dynamic_indices = batch.dynamic_indices
            tensor_idx = batch.dynamic_measurement_indices == measurement_idx

            if classification_mode == DataModality.SINGLE_LABEL_CLASSIFICATION:
                events_with_label = tensor_idx.any(axis=-1)
                # BCE-with-logits, unreduced.
                is_obs_loss = -Bernoulli(logits=is_obs_score).log_prob(events_with_label)

                labels = (
                    (dynamic_indices.astype(jnp.int32) * tensor_idx.astype(jnp.int32)).sum(axis=-1)
                    - vocab_start
                ) * events_with_label.astype(jnp.int32)

                loss_per_event = -Categorical(logits=scores).log_prob(labels)

                event_mask = event_mask & events_with_label

                is_obs_dist = Bernoulli(logits=is_obs_score)
                measurement_dists = Categorical(logits=scores)

            elif classification_mode == DataModality.MULTI_LABEL_CLASSIFICATION:
                # The dense 0/1 label plane in one pass of a kernel
                # (`ops.pallas_multihot.multihot_any`; the broadcast
                # compare-any off the TPU). Padding and other measurements'
                # slots go to -1, which names no column. The plane is written
                # with the axis on the lanes that XLA gives the scores: the
                # head's [hidden, vocab] kernel is laid vocabulary-major, and
                # its product events-minor, where the unified vocabulary is
                # no multiple of 128 (sandbox compiles for the v5e at five
                # cells' shapes, PR 37; `tests/test_chip_compile.py` holds two).
                labels = multihot_any(
                    jnp.where(tensor_idx, dynamic_indices - vocab_start, -1),
                    scores.shape[-1],
                    scores.dtype,
                    events_minor=self.config.vocab_size % LANE != 0,
                )

                loss_per_label = -Bernoulli(logits=scores).log_prob(labels)
                loss_per_event = loss_per_label.mean(axis=-1)

                is_obs_loss = None
                is_obs_dist = None
                measurement_dists = Bernoulli(logits=scores)
            else:
                raise ValueError(f"Classification mode {classification_mode} Invalid!")

            if is_obs_loss is not None:
                loss_per_event = loss_per_event + is_obs_loss
            losses[measurement] = weighted_loss(loss_per_event, event_mask)
            dists[measurement] = (is_obs_dist, measurement_dists)
            labels_out[measurement] = labels

        return losses, dists, labels_out

    # ------------------------------------------------------------ regression
    @scoped("heads_reg")
    def get_regression_outputs(
        self,
        batch: EventStreamBatch,
        encoded: Array,
        valid_measurements: set[str],
        is_generation: bool = False,
    ):
        """Regression losses/distributions/labels/indices per measurement.

        Reference: ``model_output.py:1551-1721``.
        """
        if not valid_measurements:
            return {}, {}, {}, {}

        is_observed_score = self.IsObservedLayer(encoded).astype(jnp.float32)

        loss_values, dists, labels_out, indices_out = {}, {}, {}, {}

        for measurement in self.config.measurements_for(DataModality.MULTIVARIATE_REGRESSION):
            if measurement not in valid_measurements:
                continue

            event_mask = batch.event_mask
            measurement_idx = self.config.measurements_idxmap[measurement]
            vocab_start = self.config.vocab_offsets_by_measurement[measurement]

            tensor_idx = (
                batch.dynamic_measurement_indices == measurement_idx
            ) & batch.dynamic_values_mask

            indices_measured_or_zero = jnp.where(
                tensor_idx, batch.dynamic_indices - vocab_start, 0
            ).astype(jnp.int32)

            regr_dist = self.regression_layers[measurement](
                X=encoded, idx=(None if is_generation else indices_measured_or_zero)
            )

            values_observed_or_zero = jnp.where(tensor_idx, batch.dynamic_values, 0.0).astype(
                jnp.float32
            )

            if is_generation:
                loss_overall = None
            else:
                loss_per_label = -regr_dist.log_prob(values_observed_or_zero)
                loss_per_event, _ = safe_weighted_avg(loss_per_label, tensor_idx)
                events_with_label = event_mask & tensor_idx.any(axis=-1)
                loss_overall = weighted_loss(loss_per_event, events_with_label)

            loss_values[measurement] = loss_overall
            dists[measurement] = (None, regr_dist)
            labels_out[measurement] = values_observed_or_zero
            indices_out[measurement] = indices_measured_or_zero

        for measurement in self.config.measurements_for(DataModality.UNIVARIATE_REGRESSION):
            if measurement not in valid_measurements:
                continue

            event_mask = batch.event_mask
            measurement_idx = self.config.measurements_idxmap[measurement]

            is_obs_score = is_observed_score[:, :, measurement_idx - 1]
            tensor_idx = batch.dynamic_measurement_indices == measurement_idx
            is_obs_loss = -Bernoulli(logits=is_obs_score).log_prob(tensor_idx.any(axis=-1))

            tensor_with_labels_idx = tensor_idx & batch.dynamic_values_mask
            events_with_label = tensor_with_labels_idx.any(axis=-1)

            event_mask = event_mask & events_with_label

            is_obs_dist = Bernoulli(logits=is_obs_score)
            regr_dist = self.regression_layers[measurement](X=encoded)

            values_observed_or_zero = (
                jnp.where(tensor_with_labels_idx, batch.dynamic_values, 0.0).astype(jnp.float32).sum(axis=-1)
                * events_with_label.astype(jnp.float32)
            )[..., None]

            if is_generation:
                loss_overall = None
            else:
                loss_per_event = -regr_dist.log_prob(values_observed_or_zero)[..., 0]
                loss_overall = weighted_loss(loss_per_event + is_obs_loss, event_mask)

            loss_values[measurement] = loss_overall
            dists[measurement] = (is_obs_dist, regr_dist)
            labels_out[measurement] = values_observed_or_zero
            indices_out[measurement] = None

        return (
            loss_values,
            dists,
            None if is_generation else labels_out,
            None if is_generation else indices_out,
        )
