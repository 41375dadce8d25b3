"""The conditionally-independent event stream model, end to end.

Rebuild of ``/root/reference/EventStream/transformer/conditionally_independent_model.py``:
the CI output layer predicts all next-event content from the whole-event
encoding, shifting encodings right by one event during training so position
``j`` predictions align with event ``j``'s labels (``:91-100``); generation
keeps the unshifted encodings (``is_generation=True``).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..data.types import DataModality, EventStreamBatch
from ..ops import segment_starts
from ..utils.scopes import scope
from .config import StructuredEventProcessingMode, StructuredTransformerConfig
from .model_output import (
    GenerativeOutputLayerBase,
    GenerativeSequenceModelLabels,
    GenerativeSequenceModelLosses,
    GenerativeSequenceModelOutput,
    GenerativeSequenceModelPredictions,
)
from .transformer import ConditionallyIndependentPointProcessTransformer, KVCache


class ConditionallyIndependentGenerativeOutputLayer(GenerativeOutputLayerBase):
    """CI output layer (reference ``conditionally_independent_model.py:24``)."""

    def __call__(
        self, batch: EventStreamBatch, encoded: jnp.ndarray, is_generation: bool = False
    ) -> GenerativeSequenceModelOutput:
        cfg = self.config
        if cfg.structured_event_processing_mode != StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT:
            raise ValueError(f"{cfg.structured_event_processing_mode} invalid!")

        classification_measurements = set(self.classification_mode_per_measurement.keys())
        regression_measurements = set(
            cfg.measurements_for(DataModality.MULTIVARIATE_REGRESSION)
            + cfg.measurements_for(DataModality.UNIVARIATE_REGRESSION)
        )

        whole_event_encoded = encoded

        # Training alignment: position j's content predictions come from the
        # encoding of event j-1 (zeros for j=0); generation keeps unshifted
        # encodings since the last event predicts the next one.
        if is_generation:
            for_event_contents_prediction = whole_event_encoded
        else:
            for_event_contents_prediction = jnp.concatenate(
                (jnp.zeros_like(whole_event_encoded[:, :1, :]), whole_event_encoded[:, :-1, :]),
                axis=1,
            )
            if batch.segment_ids is not None:
                # Packed rows: a segment's first event is predicted from zeros
                # (like position 0), never from the previous subject's last
                # event encoding.
                for_event_contents_prediction = jnp.where(
                    segment_starts(batch.segment_ids)[..., None],
                    0.0,
                    for_event_contents_prediction,
                )

        classification_out = self.get_classification_outputs(
            batch, for_event_contents_prediction, classification_measurements
        )
        regression_out = self.get_regression_outputs(
            batch, for_event_contents_prediction, regression_measurements, is_generation=is_generation
        )
        TTE_LL_overall, TTE_dist, TTE_true = self.get_TTE_outputs(
            batch, whole_event_encoded, is_generation=is_generation
        )

        if is_generation:
            loss = None
            losses = GenerativeSequenceModelLosses(
                classification=None, regression=None, time_to_event=None
            )
            labels = GenerativeSequenceModelLabels()
        else:
            with scope("loss"):
                loss = (
                    sum(classification_out[0].values())
                    + sum(regression_out[0].values())
                    - TTE_LL_overall
                )
            losses = GenerativeSequenceModelLosses(
                classification=classification_out[0],
                regression=regression_out[0],
                time_to_event=-TTE_LL_overall,
            )
            labels = GenerativeSequenceModelLabels(
                classification=classification_out[2],
                regression=regression_out[2],
                regression_indices=regression_out[3],
                time_to_event=TTE_true,
            )

        return GenerativeSequenceModelOutput(
            loss=loss,
            losses=losses,
            preds=GenerativeSequenceModelPredictions(
                classification=classification_out[1],
                regression=regression_out[1],
                regression_indices=None if is_generation else regression_out[3],
                time_to_event=TTE_dist,
            ),
            labels=labels,
            event_mask=batch.event_mask,
            dynamic_values_mask=batch.dynamic_values_mask,
        )


class CIPPTForGenerativeSequenceModeling(nn.Module):
    """End-to-end CI generative model (reference ``:164``)."""

    config: StructuredTransformerConfig
    use_gradient_checkpointing: bool = False

    def setup(self):
        self.encoder = ConditionallyIndependentPointProcessTransformer(
            self.config, use_gradient_checkpointing=self.use_gradient_checkpointing
        )
        self.output_layer = ConditionallyIndependentGenerativeOutputLayer(self.config)

    def __call__(
        self,
        batch: EventStreamBatch,
        past: Optional[tuple[KVCache, ...]] = None,
        use_cache: bool = False,
        output_attentions: bool = False,
        output_hidden_states: bool = False,
        is_generation: bool = False,
    ) -> GenerativeSequenceModelOutput:
        encoded = self.encoder(
            batch,
            past=past,
            use_cache=use_cache,
            output_attentions=output_attentions,
            output_hidden_states=output_hidden_states,
        )
        output = self.output_layer(batch, encoded.last_hidden_state, is_generation=is_generation)
        return output.replace(
            past_key_values=encoded.past_key_values,
            hidden_states=encoded.hidden_states,
            attentions=encoded.attentions,
        )
