"""Training harnesses: pretraining driver, optimizer, checkpointing, metrics.

TPU-native replacement for the reference's Lightning modules
(``/root/reference/EventStream/transformer/lightning_modules/``).
"""

from ..utils.misc import ImportClock as _ImportClock

_import = _ImportClock()  # `startup/import` of the host record, from here to the last line

from .checkpoint import TrainCheckpointManager, load_pretrained, save_pretrained
from .fine_tuning import (
    FinetuneConfig,
    StreamClassificationMetrics,
    init_from_pretrained_encoder,
)
from .fine_tuning import train as finetune
from .generative_metrics import GenerativeMetrics
from .optimizer import build_optimizer, polynomial_decay_with_warmup
from .sharding import (
    batch_partition_axes,
    make_mesh,
    make_param_shardings,
    make_state_shardings,
    shard_params,
    shard_state,
    train_state_bytes,
)
from .pretrain import (
    PretrainConfig,
    TrainState,
    build_model,
    data_parallel_mesh,
    evaluate,
    make_chunked_train_step,
    make_eval_step,
    make_train_step,
    parallel_mesh,
    replicate,
    shard_batch,
    train,
)

__all__ = [
    "FinetuneConfig",
    "GenerativeMetrics",
    "PretrainConfig",
    "StreamClassificationMetrics",
    "finetune",
    "init_from_pretrained_encoder",
    "TrainCheckpointManager",
    "TrainState",
    "batch_partition_axes",
    "build_model",
    "build_optimizer",
    "data_parallel_mesh",
    "evaluate",
    "load_pretrained",
    "make_chunked_train_step",
    "make_eval_step",
    "make_mesh",
    "make_param_shardings",
    "make_state_shardings",
    "make_train_step",
    "parallel_mesh",
    "polynomial_decay_with_warmup",
    "replicate",
    "shard_params",
    "shard_state",
    "save_pretrained",
    "shard_batch",
    "train",
    "train_state_bytes",
]

_import.done()
