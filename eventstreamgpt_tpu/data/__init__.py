from ..utils.misc import ImportClock as _ImportClock

_import = _ImportClock()  # `startup/import` of the host record, from here to the last line

from .config import (
    DatasetConfig,
    DatasetSchema,
    InputDFSchema,
    MeasurementConfig,
    PytorchDatasetConfig,
    SeqPaddingSide,
    SubsequenceSamplingStrategy,
    VocabularyConfig,
)
from .dataset_base import DatasetBase
from .dataset_pandas import Dataset, Query
from .device_dataset import DeviceDataset
from .jax_dataset import BatchPlan, JaxDataset
from .prefetch import DevicePrefetcher, prefetch_to_device
from .time_dependent_functor import AgeFunctor, TimeDependentFunctor, TimeOfDayFunctor
from .types import (
    DataModality,
    EventStreamBatch,
    InputDataType,
    InputDFType,
    NumericDataModalitySubtype,
    TemporalityType,
    de_pad,
)
from .vocabulary import Vocabulary

__all__ = [
    "AgeFunctor",
    "DataModality",
    "Dataset",
    "DatasetBase",
    "BatchPlan",
    "DatasetConfig",
    "DatasetSchema",
    "DeviceDataset",
    "DevicePrefetcher",
    "prefetch_to_device",
    "Query",
    "EventStreamBatch",
    "InputDataType",
    "InputDFSchema",
    "InputDFType",
    "JaxDataset",
    "MeasurementConfig",
    "NumericDataModalitySubtype",
    "PytorchDatasetConfig",
    "SeqPaddingSide",
    "SubsequenceSamplingStrategy",
    "TemporalityType",
    "TimeDependentFunctor",
    "TimeOfDayFunctor",
    "Vocabulary",
    "VocabularyConfig",
    "de_pad",
]

_import.done()
