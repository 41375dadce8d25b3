"""The nested-attention event stream model, end to end.

Rebuild of ``/root/reference/EventStream/transformer/nested_attention_model.py``:
the NA output layer walks dependency-graph levels — the encoding of level
``i-1`` predicts the measurements of level ``i`` (``:118-185``), and
time-to-event is predicted from the whole-event (last) element (``:187-195``).
No sequence shifting is needed: the structured attention data flow already
guarantees level ``i-1`` outputs only see history plus levels ``< i``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..data.types import DataModality, EventStreamBatch
from ..utils.scopes import scope
from .config import StructuredEventProcessingMode, StructuredTransformerConfig
from .embedding import MeasIndexGroupOptions
from .model_output import (
    GenerativeOutputLayerBase,
    GenerativeSequenceModelLabels,
    GenerativeSequenceModelLosses,
    GenerativeSequenceModelOutput,
    GenerativeSequenceModelPredictions,
)
from .transformer import NAPast, NestedAttentionPointProcessTransformer


class NestedAttentionGenerativeOutputLayer(GenerativeOutputLayerBase):
    """NA output layer (reference ``nested_attention_model.py:25``)."""

    def __call__(
        self,
        batch: EventStreamBatch,
        encoded: jnp.ndarray,  # (B, L, G, H)
        is_generation: bool = False,
        dep_graph_el_generation_target: int | None = None,
    ) -> GenerativeSequenceModelOutput:
        cfg = self.config
        if cfg.structured_event_processing_mode != StructuredEventProcessingMode.NESTED_ATTENTION:
            raise ValueError(f"{cfg.structured_event_processing_mode} invalid for this model!")
        if dep_graph_el_generation_target is not None and not is_generation:
            raise ValueError(
                f"If dep_graph_el_generation_target ({dep_graph_el_generation_target}) is not None, "
                f"is_generation ({is_generation}) must be True!"
            )

        classification_dists_by_measurement = {}
        classification_losses_by_measurement = None if is_generation else {}
        classification_labels_by_measurement = None if is_generation else {}
        regression_dists = {}
        regression_loss_values = None if is_generation else {}
        regression_labels = None if is_generation else {}
        regression_indices = None if is_generation else {}

        classification_measurements = set(self.classification_mode_per_measurement.keys())
        regression_measurements = set(
            cfg.measurements_for(DataModality.MULTIVARIATE_REGRESSION)
            + cfg.measurements_for(DataModality.UNIVARIATE_REGRESSION)
        )

        bsz, seq_len, dep_graph_len, _ = encoded.shape

        if is_generation:
            if dep_graph_el_generation_target is None:
                # Full structured forward: every level's predictions are
                # available from the graph outputs, so expose them all (the
                # uncached generation path samples from these; the reference
                # instead re-runs per-level with sliced inputs —
                # ``transformer.py:918-927`` — which changes the attention
                # pattern relative to training; see generation_utils docstring).
                dep_graph_loop = range(1, dep_graph_len) if dep_graph_len > 1 else None
                do_TTE = True
            elif dep_graph_el_generation_target == 0:
                dep_graph_loop = None
                do_TTE = True
            else:
                if dep_graph_len == 1:
                    # Triggered when use_cache trims the graph to one element.
                    dep_graph_loop = range(1, 2)
                else:
                    dep_graph_loop = range(
                        dep_graph_el_generation_target, dep_graph_el_generation_target + 1
                    )
                do_TTE = False
        else:
            dep_graph_loop = range(1, dep_graph_len)
            do_TTE = True

        if dep_graph_loop is not None:
            for i in dep_graph_loop:
                dep_graph_level_encoded = encoded[:, :, i - 1, :]
                target_idx = (
                    dep_graph_el_generation_target if dep_graph_el_generation_target is not None else i
                )

                categorical_in_level = set()
                numerical_in_level = set()
                for measurement in cfg.measurements_per_dep_graph_level[target_idx]:
                    if isinstance(measurement, (tuple, list)):
                        measurement, mode = measurement
                    else:
                        mode = MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL
                    if mode == MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL:
                        categorical_in_level.add(measurement)
                        numerical_in_level.add(measurement)
                    elif mode == MeasIndexGroupOptions.CATEGORICAL_ONLY:
                        categorical_in_level.add(measurement)
                    elif mode == MeasIndexGroupOptions.NUMERICAL_ONLY:
                        numerical_in_level.add(measurement)
                    else:
                        raise ValueError(f"Unknown mode {mode}")

                classification_out = self.get_classification_outputs(
                    batch,
                    dep_graph_level_encoded,
                    categorical_in_level.intersection(classification_measurements),
                )
                classification_dists_by_measurement.update(classification_out[1])
                if not is_generation:
                    classification_losses_by_measurement.update(classification_out[0])
                    classification_labels_by_measurement.update(classification_out[2])

                regression_out = self.get_regression_outputs(
                    batch,
                    dep_graph_level_encoded,
                    numerical_in_level.intersection(regression_measurements),
                    is_generation=is_generation,
                )
                regression_dists.update(regression_out[1])
                if not is_generation:
                    regression_loss_values.update(regression_out[0])
                    regression_labels.update(regression_out[2])
                    regression_indices.update(regression_out[3])

        if do_TTE:
            whole_event_encoded = encoded[:, :, -1, :]
            TTE_LL_overall, TTE_dist, TTE_true = self.get_TTE_outputs(
                batch, whole_event_encoded, is_generation=is_generation
            )
        else:
            TTE_LL_overall, TTE_dist, TTE_true = None, None, None

        if is_generation:
            loss = None
            losses = GenerativeSequenceModelLosses()
            labels = GenerativeSequenceModelLabels()
        else:
            with scope("loss"):
                loss = (
                    sum(classification_losses_by_measurement.values())
                    + sum(regression_loss_values.values())
                    - TTE_LL_overall
                )
            losses = GenerativeSequenceModelLosses(
                classification=classification_losses_by_measurement,
                regression=regression_loss_values,
                time_to_event=-TTE_LL_overall,
            )
            labels = GenerativeSequenceModelLabels(
                classification=classification_labels_by_measurement,
                regression=regression_labels,
                regression_indices=regression_indices,
                time_to_event=TTE_true,
            )

        return GenerativeSequenceModelOutput(
            loss=loss,
            losses=losses,
            preds=GenerativeSequenceModelPredictions(
                classification=classification_dists_by_measurement,
                regression=regression_dists,
                regression_indices=None if is_generation else regression_indices,
                time_to_event=TTE_dist,
            ),
            labels=labels,
            event_mask=batch.event_mask,
            dynamic_values_mask=batch.dynamic_values_mask,
        )


class NAPPTForGenerativeSequenceModeling(nn.Module):
    """End-to-end NA generative model (reference ``:231``)."""

    config: StructuredTransformerConfig
    use_gradient_checkpointing: bool = False

    def setup(self):
        if (
            self.config.structured_event_processing_mode
            != StructuredEventProcessingMode.NESTED_ATTENTION
        ):
            raise ValueError(f"{self.config.structured_event_processing_mode} invalid!")
        self.encoder = NestedAttentionPointProcessTransformer(
            self.config, use_gradient_checkpointing=self.use_gradient_checkpointing
        )
        self.output_layer = NestedAttentionGenerativeOutputLayer(self.config)

    def __call__(
        self,
        batch: EventStreamBatch,
        past: Optional[NAPast] = None,
        use_cache: bool = False,
        output_attentions: bool = False,
        output_hidden_states: bool = False,
        is_generation: bool = False,
        dep_graph_el_generation_target: int | None = None,
        last_event_index: Optional[jnp.ndarray] = None,
        partial_content_levels: bool = False,
        history_head: tuple | None = None,
        return_contextualized: bool = False,
    ) -> GenerativeSequenceModelOutput:
        encoded = self.encoder(
            batch,
            past=past,
            use_cache=use_cache,
            output_attentions=output_attentions,
            output_hidden_states=output_hidden_states,
            dep_graph_el_generation_target=dep_graph_el_generation_target,
            last_event_index=last_event_index,
            partial_content_levels=partial_content_levels,
            history_head=history_head,
            return_contextualized=return_contextualized,
        )
        output = self.output_layer(
            batch,
            encoded.last_hidden_state,
            is_generation=is_generation,
            dep_graph_el_generation_target=dep_graph_el_generation_target,
        )
        return output.replace(
            past_key_values=encoded.past_key_values,
            hidden_states=encoded.hidden_states,
            attentions=encoded.attentions,
            contextualized=encoded.contextualized,
        )
