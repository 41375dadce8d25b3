"""Model layer: configs, embedding, transformer encoders, heads, full models."""

from ..utils.misc import ImportClock as _ImportClock

_import = _ImportClock()  # `startup/import` of the host record, from here to the last line

from .config import (  # noqa: F401
    AttentionLayerType,
    StructuredEventProcessingMode,
    StructuredTransformerConfig,
    TimeToEventGenerationHeadType,
)
from .config import (  # noqa: F401
    Averaging,
    MetricCategories,
    Metrics,
    MetricsConfig,
    OptimizationConfig,
    Split,
)
from .embedding import (  # noqa: F401
    DataEmbeddingLayer,
    EmbeddingMode,
    MeasIndexGroupOptions,
    StaticEmbeddingMode,
)
from .fine_tuning_model import ESTForStreamClassification  # noqa: F401
from .model_output import get_event_types  # noqa: F401

_import.done()
