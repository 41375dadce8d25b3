"""Model architecture configuration for structured event-stream transformers.

TPU-native rebuild of ``/root/reference/EventStream/transformer/config.py:355``
(``StructuredTransformerConfig``). Field names, defaults, and validation match
the reference so existing YAML/JSON configs keep working (BASELINE
requirement), but the class is a plain ``JSONableMixin`` python object — no
HuggingFace ``PretrainedConfig`` coupling. HF-inherited task fields the
codebase actually uses (``finetuning_task``, ``id2label``, ``label2id``,
``num_labels``, ``problem_type``, ``task_specific_params``) are first-class
fields here.
"""

from __future__ import annotations

import dataclasses
import enum
import inspect
import itertools
import math
from typing import Any, Hashable, Union

from ..data.config import MeasurementConfig
from ..data.types import DataModality
from ..utils import JSONableMixin, StrEnum, config_dataclass
from .embedding import MEAS_INDEX_GROUP_T, MeasIndexGroupOptions, StaticEmbeddingMode


class Split(StrEnum):
    """What data split is being used (reference ``config.py:25``)."""

    TRAIN = enum.auto()
    TUNING = enum.auto()
    HELD_OUT = enum.auto()


class MetricCategories(StrEnum):
    """Categories of metrics, for configuring what to track (reference ``config.py:44``)."""

    LOSS_PARTS = enum.auto()
    TTE = "TTE"
    CLASSIFICATION = enum.auto()
    REGRESSION = enum.auto()


class Metrics(StrEnum):
    """Supported metric functions (reference ``config.py:63``)."""

    AUROC = "AUROC"
    AUPRC = "AUPRC"
    ACCURACY = enum.auto()
    EXPLAINED_VARIANCE = enum.auto()
    MSE = "MSE"
    MSLE = "MSLE"


class Averaging(StrEnum):
    """Metric averaging modes in multi-class/multi-label settings (reference ``config.py:91``)."""

    MACRO = enum.auto()
    MICRO = enum.auto()
    WEIGHTED = enum.auto()


def _default_include_metrics() -> dict:
    # Built per split so the nested dicts are never aliased between splits.
    def eval_metrics() -> dict:
        return {
            MetricCategories.LOSS_PARTS: True,
            MetricCategories.TTE: {Metrics.MSE: True, Metrics.MSLE: True},
            MetricCategories.CLASSIFICATION: {
                Metrics.AUROC: [Averaging.WEIGHTED],
                Metrics.ACCURACY: True,
            },
            MetricCategories.REGRESSION: {Metrics.MSE: True},
        }

    return {Split.TUNING: eval_metrics(), Split.HELD_OUT: eval_metrics()}


@config_dataclass
class MetricsConfig(JSONableMixin):
    """What metrics should be tracked, over which splits, with which averagings.

    Reference: ``transformer/config.py:104-206`` (``MetricsConfig``). The
    ``include_metrics`` format is ``{split: {category: True | {metric: True |
    [averagings]}}}``; ``do_skip_all_metrics`` clears it entirely.
    """

    n_auc_thresholds: int | None = 50
    do_skip_all_metrics: bool = False
    do_validate_args: bool = False
    include_metrics: dict[str, Any] = dataclasses.field(default_factory=_default_include_metrics)

    def __post_init__(self):
        if self.do_skip_all_metrics:
            self.include_metrics = {}

    def do_log_only_loss(self, split: str) -> bool:
        """True if only the loss (no other metrics) should be logged for ``split``."""
        if (
            self.do_skip_all_metrics
            or split not in self.include_metrics
            or not self.include_metrics[split]
            or (
                len(self.include_metrics[split]) == 1
                and MetricCategories.LOSS_PARTS in self.include_metrics[split]
            )
        ):
            return True
        return False

    def do_log(self, split: str, cat: str, metric_name: str | None = None) -> bool:
        """True if ``metric_name`` should be tracked for ``split`` and ``cat``.

        Reference: ``transformer/config.py:176-199``. Metric names may carry an
        averaging prefix (e.g. ``weighted_AUROC``); ``explained_variance`` is
        the one un-prefixed metric containing an underscore.
        """
        if self.do_log_only_loss(split):
            return False

        inc_dict = self.include_metrics[split].get(cat, False)
        if not inc_dict:
            return False
        if metric_name is None or inc_dict is True:
            return True

        has_averaging = "_" in metric_name.replace("explained_variance", "")
        if not has_averaging:
            return metric_name in inc_dict

        parts = metric_name.split("_")
        averaging = parts[0]
        metric = "_".join(parts[1:])

        permissible_averagings = inc_dict.get(metric, [])
        return (permissible_averagings is True) or (averaging in permissible_averagings)

    def do_log_any(self, cat: str, metric_name: str | None = None) -> bool:
        """True if ``metric_name`` should be tracked for ``cat`` on any split."""
        return any(self.do_log(split, cat, metric_name) for split in Split.values())


class StructuredEventProcessingMode(StrEnum):
    """Structured event sequence processing modes (reference ``config.py:314``)."""

    CONDITIONALLY_INDEPENDENT = enum.auto()
    NESTED_ATTENTION = enum.auto()


class TimeToEventGenerationHeadType(StrEnum):
    """Options for model TTE generation heads (reference ``config.py:324``)."""

    EXPONENTIAL = enum.auto()
    LOG_NORMAL_MIXTURE = enum.auto()


class AttentionLayerType(StrEnum):
    """Attention layer type options (reference ``config.py:334``)."""

    GLOBAL = enum.auto()
    LOCAL = enum.auto()


ATTENTION_TYPES_LIST_T = Union[str, list]


class StructuredTransformerConfig(JSONableMixin):
    """Configuration for event-stream transformer models.

    See the reference docstring (``transformer/config.py:356-478``) for the
    full field semantics; this class reproduces them. Constructor signature and
    validation behavior are parity-tested against the reference.
    """

    def __init__(
        self,
        # Data configuration
        vocab_sizes_by_measurement: dict[str, int] | None = None,
        vocab_offsets_by_measurement: dict[str, int] | None = None,
        measurement_configs: dict[str, MeasurementConfig] | None = None,
        measurements_idxmap: dict[str, dict[Hashable, int]] | None = None,
        measurements_per_generative_mode: dict[str, list[str]] | None = None,
        event_types_idxmap: dict[str, int] | None = None,
        measurements_per_dep_graph_level: list[list[MEAS_INDEX_GROUP_T]] | None = None,
        max_seq_len: int = 256,
        do_split_embeddings: bool = False,
        categorical_embedding_dim: int | None = None,
        numerical_embedding_dim: int | None = None,
        static_embedding_mode: str = StaticEmbeddingMode.SUM_ALL,
        static_embedding_weight: float = 0.5,
        dynamic_embedding_weight: float = 0.5,
        categorical_embedding_weight: float = 0.5,
        numerical_embedding_weight: float = 0.5,
        do_normalize_by_measurement_index: bool = False,
        # Model configuration
        structured_event_processing_mode: str = StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT,
        hidden_size: int | None = None,
        head_dim: int | None = 64,
        num_hidden_layers: int = 2,
        num_attention_heads: int = 4,
        seq_attention_types: ATTENTION_TYPES_LIST_T | None = None,
        seq_window_size: int = 32,
        attention_implementation: str = "einsum",
        gradient_checkpointing: str = "none",
        scan_layers: bool = False,
        precision: str = "fp32",
        dep_graph_attention_types: ATTENTION_TYPES_LIST_T | None = None,
        dep_graph_window_size: int | None = 2,
        dep_graph_fused_attention: bool | None = True,
        dep_graph_attention_impl: str | None = None,
        head_narrow_projections: bool = True,
        intermediate_size: int = 32,
        activation_function: str = "gelu",
        attention_dropout: float = 0.1,
        input_dropout: float = 0.1,
        resid_dropout: float = 0.1,
        init_std: float = 0.02,
        layer_norm_epsilon: float = 1e-5,
        do_full_block_in_dep_graph_attention: bool | None = True,
        do_full_block_in_seq_attention: bool | None = False,
        # Layer kinds (docs/layer_kinds.md): a mixer and a feed-forward per
        # layer, one norm for the stack. The defaults are the classic block.
        mixer_types: ATTENTION_TYPES_LIST_T = "mha",
        ffn_types: ATTENTION_TYPES_LIST_T = "mlp",
        norm_type: str = "layer_norm",
        q_lora_rank: int | None = None,
        kv_lora_rank: int | None = None,
        qk_nope_head_dim: int | None = None,
        qk_rope_head_dim: int | None = None,
        v_head_dim: int | None = None,
        rope_theta: float = 10000.0,
        rope_scaling: dict | None = None,
        hc_mult: int = 1,
        hc_sinkhorn_iters: int = 20,
        hc_eps: float = 1e-6,
        hc_res_clamp: float = 30.0,
        moe_intermediate_size: int | None = None,
        moe_router_width: int | None = None,
        n_routed_experts: int | None = None,
        moe_expert_offset: int = 0,
        n_shared_experts: int = 0,
        num_experts_per_tok: int | None = None,
        routed_scaling_factor: float = 1.0,
        norm_topk_prob: bool = True,
        moe_expert_form: str = "swiglu",
        moe_shared_expert_intermediate_size: int | None = None,
        num_key_value_heads: int | None = None,
        mamba_num_heads: int | None = None,
        mamba_head_dim: int | None = None,
        mamba_n_groups: int | None = None,
        ssm_state_size: int | None = None,
        mamba_conv_kernel: int = 4,
        mamba_chunk_size: int = 128,
        conv_L_cache: int = 3,
        conv_bias: bool = False,
        mha_qk_norm: bool = False,
        mha_rope: bool = False,
        moe_topk_norm_eps: float = 0.0,
        # Model output configuration
        TTE_generation_layer_type: str = TimeToEventGenerationHeadType.EXPONENTIAL,
        TTE_lognormal_generation_num_components: int | None = None,
        mean_log_inter_event_time_min: float | None = None,
        std_log_inter_event_time_min: float | None = None,
        # For decoding
        use_cache: bool = True,
        # Task (HF-PretrainedConfig-inherited in the reference)
        finetuning_task: str | None = None,
        id2label: dict[int, str] | None = None,
        label2id: dict[str, int] | None = None,
        num_labels: int | None = None,
        problem_type: str | None = None,
        task_specific_params: dict[str, Any] | None = None,
        **kwargs,
    ):
        if vocab_sizes_by_measurement is None:
            vocab_sizes_by_measurement = {}
        if vocab_offsets_by_measurement is None:
            vocab_offsets_by_measurement = {}
        if measurements_idxmap is None:
            measurements_idxmap = {}
        if measurements_per_generative_mode is None:
            measurements_per_generative_mode = {}
        if event_types_idxmap is None:
            event_types_idxmap = {}
        if measurement_configs is None:
            measurement_configs = {}

        self.event_types_idxmap = event_types_idxmap

        if measurement_configs:
            measurement_configs = {
                k: (MeasurementConfig.from_dict(v) if type(v) is dict else v)
                for k, v in measurement_configs.items()
            }
        self.measurement_configs = measurement_configs

        if do_split_embeddings:
            for nm, v in (
                ("categorical_embedding_dim", categorical_embedding_dim),
                ("numerical_embedding_dim", numerical_embedding_dim),
            ):
                if type(v) is not int or v <= 0:
                    raise ValueError(
                        f"When do_split_embeddings={do_split_embeddings}, {nm} must be "
                        f"a positive integer. Got {v}."
                    )
        else:
            if categorical_embedding_dim is not None:
                print(
                    f"WARNING: categorical_embedding_dim is set to {categorical_embedding_dim} but "
                    f"do_split_embeddings={do_split_embeddings}. Setting categorical_embedding_dim to None."
                )
                categorical_embedding_dim = None
            if numerical_embedding_dim is not None:
                print(
                    f"WARNING: numerical_embedding_dim is set to {numerical_embedding_dim} but "
                    f"do_split_embeddings={do_split_embeddings}. Setting numerical_embedding_dim to None."
                )
                numerical_embedding_dim = None
        self.do_split_embeddings = do_split_embeddings

        self.categorical_embedding_dim = categorical_embedding_dim
        self.numerical_embedding_dim = numerical_embedding_dim
        self.static_embedding_mode = StaticEmbeddingMode(static_embedding_mode)
        self.static_embedding_weight = static_embedding_weight
        self.dynamic_embedding_weight = dynamic_embedding_weight
        self.categorical_embedding_weight = categorical_embedding_weight
        self.numerical_embedding_weight = numerical_embedding_weight
        self.do_normalize_by_measurement_index = do_normalize_by_measurement_index

        missing_param_err_tmpl = f"For a {structured_event_processing_mode} model, {{}} should not be None"
        extra_param_err_tmpl = (
            f"WARNING: For a {structured_event_processing_mode} model, {{}} is not used; got {{}}. Setting "
            "to None."
        )
        if structured_event_processing_mode == StructuredEventProcessingMode.NESTED_ATTENTION:
            if do_full_block_in_seq_attention is None:
                raise ValueError(missing_param_err_tmpl.format("do_full_block_in_seq_attention"))
            if do_full_block_in_dep_graph_attention is None:
                raise ValueError(missing_param_err_tmpl.format("do_full_block_in_dep_graph_attention"))
            if measurements_per_dep_graph_level is None:
                raise ValueError(missing_param_err_tmpl.format("measurements_per_dep_graph_level"))

            proc_levels = []
            for group in measurements_per_dep_graph_level:
                proc_group = []
                for meas_index in group:
                    if isinstance(meas_index, str):
                        proc_group.append(meas_index)
                    elif (
                        isinstance(meas_index, (list, tuple))
                        and len(meas_index) == 2
                        and isinstance(meas_index[0], str)
                    ):
                        assert meas_index[1] in MeasIndexGroupOptions.values()
                        proc_group.append((meas_index[0], meas_index[1]))
                    else:
                        raise ValueError(f"Invalid `measurements_per_dep_graph_level` entry {meas_index}.")
                proc_levels.append(proc_group)
            measurements_per_dep_graph_level = proc_levels
        elif structured_event_processing_mode == StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT:
            # NA-only knobs are nulled for CI models. Unlike the reference
            # (which warns even when the value is just the constructor
            # default, polluting every CI run's logs), only explicitly-set
            # non-default values warn; untouched defaults are nulled silently.
            # Defaults are read from the signature so they cannot drift.
            _sig = inspect.signature(StructuredTransformerConfig.__init__)
            _na_only_defaults = {
                name: _sig.parameters[name].default
                for name in (
                    "do_full_block_in_seq_attention",
                    "do_full_block_in_dep_graph_attention",
                    "dep_graph_window_size",
                    "dep_graph_fused_attention",
                )
            }
            if measurements_per_dep_graph_level is not None:
                print(
                    extra_param_err_tmpl.format(
                        "measurements_per_dep_graph_level", measurements_per_dep_graph_level
                    )
                )
                measurements_per_dep_graph_level = None
            if do_full_block_in_seq_attention is not None:
                if do_full_block_in_seq_attention != _na_only_defaults["do_full_block_in_seq_attention"]:
                    print(
                        extra_param_err_tmpl.format(
                            "do_full_block_in_seq_attention", do_full_block_in_seq_attention
                        )
                    )
                do_full_block_in_seq_attention = None
            if do_full_block_in_dep_graph_attention is not None:
                if (
                    do_full_block_in_dep_graph_attention
                    != _na_only_defaults["do_full_block_in_dep_graph_attention"]
                ):
                    print(
                        extra_param_err_tmpl.format(
                            "do_full_block_in_dep_graph_attention", do_full_block_in_dep_graph_attention
                        )
                    )
                do_full_block_in_dep_graph_attention = None
            if dep_graph_attention_types is not None:
                print(extra_param_err_tmpl.format("dep_graph_attention_types", dep_graph_attention_types))
                dep_graph_attention_types = None
            if dep_graph_window_size is not None:
                if dep_graph_window_size != _na_only_defaults["dep_graph_window_size"]:
                    print(extra_param_err_tmpl.format("dep_graph_window_size", dep_graph_window_size))
                dep_graph_window_size = None
            if dep_graph_fused_attention is not None:
                if dep_graph_fused_attention != _na_only_defaults["dep_graph_fused_attention"]:
                    print(
                        extra_param_err_tmpl.format(
                            "dep_graph_fused_attention", dep_graph_fused_attention
                        )
                    )
                dep_graph_fused_attention = None
        else:
            raise ValueError(
                "`structured_event_processing_mode` must be a valid `StructuredEventProcessingMode` "
                f"enum member ({StructuredEventProcessingMode.values()}). Got "
                f"{structured_event_processing_mode}."
            )

        self.structured_event_processing_mode = structured_event_processing_mode

        if (head_dim is None) and (hidden_size is None):
            raise ValueError("Must specify at least one of hidden size or head dim!")
        latent_dims = (q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
        latent = any(d is not None for d in latent_dims)
        if latent:
            # Latent attention's heads are no split of the hidden size: the
            # core's head width is what the up-projections give it, and a
            # `head_dim` passed beside them is not used.
            if None in latent_dims or hidden_size is None:
                raise ValueError(
                    "latent attention needs hidden_size, q_lora_rank, kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
                )
            head_dim = qk_nope_head_dim + qk_rope_head_dim
        elif hidden_size is None:
            hidden_size = head_dim * num_attention_heads
        elif head_dim is None:
            head_dim = hidden_size // num_attention_heads
        # (The kinds block's attention projects to `head_dim x heads` of its own, as its
        # configuration publishes them: 128 x 32 over a hidden size of 2,688.)
        if not latent and norm_type != "rms_norm" and head_dim * num_attention_heads != hidden_size:
            raise ValueError(
                f"hidden_size must be divisible by num_attention_heads (got `hidden_size`: {hidden_size} "
                f"and `num_attention_heads`: {num_attention_heads})."
            )

        if type(num_hidden_layers) is not int:
            raise TypeError(f"num_hidden_layers must be an int! Got {type(num_hidden_layers)}.")
        elif num_hidden_layers <= 0:
            raise ValueError(f"num_hidden_layers must be > 0! Got {num_hidden_layers}.")
        self.num_hidden_layers = num_hidden_layers

        if seq_attention_types is None:
            seq_attention_types = ["local", "global"]
        self.seq_attention_types = seq_attention_types
        self.seq_attention_layers = self.expand_attention_types_params(seq_attention_types)
        if len(self.seq_attention_layers) != num_hidden_layers:
            raise ValueError(
                "Configuration for module is incorrect. "
                "It is required that `len(config.seq_attention_layers)` == `config.num_hidden_layers` "
                f"but is `len(config.seq_attention_layers) = {len(self.seq_attention_layers)}`, "
                f"`config.num_layers = {num_hidden_layers}`. "
                "`config.seq_attention_layers` is prepared using `config.seq_attention_types`. "
                "Please verify the value of `config.seq_attention_types` argument."
            )

        if structured_event_processing_mode != StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT:
            if dep_graph_attention_types is None:
                dep_graph_attention_types = "global"
            dep_graph_attention_layers = self.expand_attention_types_params(dep_graph_attention_types)
            if len(dep_graph_attention_layers) != num_hidden_layers:
                raise ValueError(
                    "Configuration for module is incorrect. It is required that "
                    "`len(config.dep_graph_attention_layers)` == `config.num_hidden_layers` "
                    f"but is `len(config.dep_graph_attention_layers) = {len(dep_graph_attention_layers)}`, "
                    f"`config.num_layers = {num_hidden_layers}`. "
                    "`config.dep_graph_attention_layers` is prepared using "
                    "`config.dep_graph_attention_types`. Please verify the value of "
                    "`config.dep_graph_attention_types` argument."
                )
        else:
            dep_graph_attention_layers = None
        self.dep_graph_attention_types = dep_graph_attention_types
        self.dep_graph_attention_layers = dep_graph_attention_layers

        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps, self.hc_res_clamp = (
            hc_mult, hc_sinkhorn_iters, hc_eps, hc_res_clamp
        )
        self.num_key_value_heads = num_key_value_heads
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.mamba_n_groups = mamba_n_groups
        self.ssm_state_size = ssm_state_size
        self.mamba_conv_kernel = mamba_conv_kernel
        self.mamba_chunk_size = mamba_chunk_size
        self.conv_L_cache, self.conv_bias = conv_L_cache, conv_bias
        self.mha_qk_norm, self.mha_rope = mha_qk_norm, mha_rope
        self.num_attention_heads = num_attention_heads
        self._set_layer_kinds(mixer_types, ffn_types, norm_type)
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_router_width = moe_router_width
        self.n_routed_experts = n_routed_experts
        self.moe_expert_offset = moe_expert_offset
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.moe_topk_norm_eps = moe_topk_norm_eps
        self.moe_expert_form = moe_expert_form
        self.moe_shared_expert_intermediate_size = moe_shared_expert_intermediate_size
        if moe_expert_form not in self.EXPERT_FORMS:
            raise ValueError(f"moe_expert_form must be of {self.EXPERT_FORMS}; got {moe_expert_form}")
        if "routed" in self.ffn_layers:
            if None in (moe_intermediate_size, moe_router_width, n_routed_experts, num_experts_per_tok):
                raise ValueError(
                    "feed-forward 'routed' needs moe_intermediate_size, moe_router_width, "
                    "n_routed_experts (the experts held here) and num_experts_per_tok"
                )
            if not 0 < n_routed_experts <= moe_router_width or not (
                0 <= moe_expert_offset <= moe_router_width - n_routed_experts
            ):
                raise ValueError(
                    f"the experts held, {moe_expert_offset}..{moe_expert_offset + n_routed_experts - 1}, "
                    f"are not among the router's {moe_router_width}"
                )
            if not 0 < num_experts_per_tok <= moe_router_width:
                raise ValueError(f"num_experts_per_tok {num_experts_per_tok} of {moe_router_width} experts")

        self.seq_window_size = seq_window_size
        if attention_implementation not in ("einsum", "pallas_flash", "ring"):
            raise ValueError(
                f"attention_implementation must be 'einsum', 'pallas_flash', or 'ring'; got "
                f"{attention_implementation}"
            )
        # Cross-backend note: under 'pallas_flash', narrow-window
        # local layers use the backend-independent band einsum on CPU too, so
        # off-TPU evals of pallas_flash checkpoints are fp32-rounding-close to
        # TPU, not bit-exact; 'einsum' remains the bit-exact-everywhere path.
        self.attention_implementation = attention_implementation
        # Rematerialization policy for the encoder blocks
        # (r06 MFU round). "none" saves all activations (fastest when they fit HBM;
        # at toy shapes every policy only adds recompute), "block" re-runs
        # each block's forward in its backward (nn.remat, minimum memory),
        # "dots" / "dots_no_batch" are jax.checkpoint selective policies
        # that save matmul outputs and recompute only elementwise work,
        # and "save_attention" composes dots_no_batch with
        # save_only_these_names on the checkpoint-named attention outputs
        # so the backward never re-executes the flash/splash/band attention
        # custom-calls (docs/performance.md). The benchmark's width-1024
        # configuration names dots_no_batch.
        if gradient_checkpointing not in (
            "none", "block", "dots", "dots_no_batch", "save_attention"
        ):
            raise ValueError(
                "gradient_checkpointing must be one of 'none', 'block', 'dots', "
                f"'dots_no_batch', 'save_attention'; got {gradient_checkpointing}"
            )
        self.gradient_checkpointing = gradient_checkpointing
        # Depth as a first-class scaling axis (r10 scale-up round): compile
        # ONE layer body regardless of num_hidden_layers by running the
        # encoder stack as ``nn.scan`` over the (remat-wrapped) block with
        # stacked ``(L/p, ...)`` parameters, where p is the attention-type
        # pattern period (models/transformer.py `scan_period`). False keeps
        # the historical unrolled loop — the parity reference whose
        # loss/grads the scanned path must reproduce (tests/models/
        # test_scan_layers.py); checkpoints migrate between the two layouts
        # with `models.transformer.stack_layer_params` / `unstack_layer_params`.
        self.scan_layers = bool(scan_layers)
        if self.scan_layers and self.hc_mult > 1:
            raise ValueError("hc_mult > 1 with scan_layers: the kinds block, which carries the streams, is not scanned")
        if precision not in ("fp32", "bf16"):
            raise ValueError(f"precision must be 'fp32' or 'bf16'; got {precision}")
        self.precision = precision
        self.dep_graph_window_size = dep_graph_window_size
        # NA-only: route the per-event dep-graph walk through the fused
        # broadcast-reduce attention (ops/band_attention.dep_graph_attention)
        # instead of batched tiny dot_generals. Numerics-parity gated in
        # tests (tests/models/test_dep_graph_fused.py); False restores the
        # einsum path for A/Bs.
        self.dep_graph_fused_attention = dep_graph_fused_attention
        # Which implementation the fused dep-graph walk runs on: None/"auto"
        # resolves per backend (the hand-tiled Pallas kernel on TPU, the
        # fused-XLA formulation elsewhere; $ESGPT_PALLAS_IMPL overrides —
        # ops/impl_select.py). Explicit "pallas" / "pallas_interpret" / "xla"
        # pin it — the bench A/B (`dep_graph_pallas_ab_ms`) drives both arms
        # through this knob.
        if dep_graph_attention_impl not in (None, "auto", "pallas", "pallas_interpret", "xla"):
            raise ValueError(
                "dep_graph_attention_impl must be None/'auto'/'pallas'/"
                f"'pallas_interpret'/'xla'; got {dep_graph_attention_impl}"
            )
        self.dep_graph_attention_impl = dep_graph_attention_impl
        # Output-head classification projections: when a call needs only a
        # narrow vocabulary span (the NA per-level walk), project just those
        # columns of the ClassificationLayer kernel instead of the full
        # (hidden, vocab) plane — column-exact, checkpoint-compatible
        # (models/model_output.py `VocabProjection`).
        self.head_narrow_projections = head_narrow_projections

        missing_param_err_tmpl = f"For a {TTE_generation_layer_type} model, {{}} should not be None"
        extra_param_err_tmpl = (
            f"WARNING: For a {TTE_generation_layer_type} model, {{}} is not used; got {{}}. "
            "Setting to None."
        )
        if TTE_generation_layer_type == TimeToEventGenerationHeadType.LOG_NORMAL_MIXTURE:
            if TTE_lognormal_generation_num_components is None:
                raise ValueError(missing_param_err_tmpl.format("TTE_lognormal_generation_num_components"))
            if type(TTE_lognormal_generation_num_components) is not int:
                raise TypeError(
                    f"`TTE_lognormal_generation_num_components` must be an int! "
                    f"Got: {type(TTE_lognormal_generation_num_components)}."
                )
            elif TTE_lognormal_generation_num_components <= 0:
                raise ValueError(
                    "`TTE_lognormal_generation_num_components` should be >0 "
                    f"got {TTE_lognormal_generation_num_components}."
                )
            if mean_log_inter_event_time_min is None:
                mean_log_inter_event_time_min = 0.0
            if std_log_inter_event_time_min is None:
                std_log_inter_event_time_min = 1.0
        elif TTE_generation_layer_type == TimeToEventGenerationHeadType.EXPONENTIAL:
            if TTE_lognormal_generation_num_components is not None:
                print(
                    extra_param_err_tmpl.format(
                        "TTE_lognormal_generation_num_components", TTE_lognormal_generation_num_components
                    )
                )
                TTE_lognormal_generation_num_components = None
            if mean_log_inter_event_time_min is not None:
                print(
                    extra_param_err_tmpl.format(
                        "mean_log_inter_event_time_min", mean_log_inter_event_time_min
                    )
                )
                mean_log_inter_event_time_min = None
            if std_log_inter_event_time_min is not None:
                print(
                    extra_param_err_tmpl.format("std_log_inter_event_time_min", std_log_inter_event_time_min)
                )
                std_log_inter_event_time_min = None
        else:
            raise ValueError(
                f"Invalid option for `TTE_generation_layer_type`. Must be in "
                f"({TimeToEventGenerationHeadType.values()}). Got {TTE_generation_layer_type}."
            )

        self.TTE_generation_layer_type = TTE_generation_layer_type
        self.TTE_lognormal_generation_num_components = TTE_lognormal_generation_num_components
        self.mean_log_inter_event_time_min = mean_log_inter_event_time_min
        self.std_log_inter_event_time_min = std_log_inter_event_time_min

        self.init_std = init_std

        self.max_seq_len = max_seq_len
        self.vocab_sizes_by_measurement = vocab_sizes_by_measurement
        self.vocab_offsets_by_measurement = vocab_offsets_by_measurement
        self.measurements_idxmap = measurements_idxmap
        self.measurements_per_generative_mode = measurements_per_generative_mode
        self.measurements_per_dep_graph_level = measurements_per_dep_graph_level

        # The reference constructor uses ``max(sum(sizes), 1)`` here
        # (``config.py:804``), which under-counts the padding offset; the real
        # value is always overwritten by ``set_to_dataset`` with
        # ``VocabularyConfig.total_vocab_size`` (``data/config.py:583``). We
        # apply that formula directly whenever offsets are known so
        # standalone-constructed configs are consistent too.
        if self.vocab_offsets_by_measurement:
            self.vocab_size = (
                sum(self.vocab_sizes_by_measurement.values())
                + min(self.vocab_offsets_by_measurement.values())
                + (
                    len(self.vocab_offsets_by_measurement)
                    - len(self.vocab_sizes_by_measurement)
                )
            )
        else:
            self.vocab_size = max(sum(self.vocab_sizes_by_measurement.values()), 1)

        self.head_dim = head_dim
        self.hidden_size = hidden_size
        self.attention_dropout = attention_dropout
        self.input_dropout = input_dropout
        self.resid_dropout = resid_dropout
        self.intermediate_size = intermediate_size
        self.layer_norm_epsilon = layer_norm_epsilon
        self.activation_function = activation_function
        self.do_full_block_in_seq_attention = do_full_block_in_seq_attention
        self.do_full_block_in_dep_graph_attention = do_full_block_in_dep_graph_attention

        self.use_cache = use_cache

        self.finetuning_task = finetuning_task
        self.id2label = id2label
        self.label2id = label2id
        self.num_labels = num_labels
        self.problem_type = problem_type
        self.task_specific_params = task_specific_params

        # Accept-and-store unknown kwargs for forward compatibility, as
        # PretrainedConfig does.
        for k, v in kwargs.items():
            setattr(self, k, v)
        self._extra_kwargs = sorted(kwargs.keys())

    @property
    def compute_dtype(self):
        """The activation/matmul dtype implied by ``precision``.

        Mixed-precision discipline: bf16 activations and
        matmuls, fp32 parameters, fp32 softmax and losses. The reference's
        closest analog is ``torch.set_float32_matmul_precision("high")``
        (``/root/reference/scripts/pretrain.py:24``).
        """
        import jax.numpy as jnp

        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    MIXER_KINDS = ("mha", "latent", "ssm", "conv", "none")
    FFN_KINDS = ("mlp", "swiglu", "routed", "none")
    NORM_KINDS = ("layer_norm", "rms_norm")
    EXPERT_FORMS = ("swiglu", "relu2")

    def _set_layer_kinds(self, mixer_types, ffn_types, norm_type) -> None:
        """Each layer's mixer and feed-forward kind, in the attention types'
        mini-language. The classic block (`InnerBlock`) is every default. Under
        ``rms_norm`` the stack is built of the kinds block (`models/blocks.py`):
        a layer names a mixer (``latent``, ``ssm``, ``conv``, ``mha`` with grouped
        key/value heads, or ``none``) and a feed-forward (``swiglu``,
        ``routed`` or ``none``), at least one of the two, and each mixer kind
        in use has its sizes (docs/layer_kinds.md)."""
        self.mixer_types, self.ffn_types, self.norm_type = mixer_types, ffn_types, norm_type
        self.mixer_layers = self.expand_attention_types_params(mixer_types)
        self.ffn_layers = self.expand_attention_types_params(ffn_types)
        for name, layers, known in (
            ("mixer_types", self.mixer_layers, self.MIXER_KINDS),
            ("ffn_types", self.ffn_layers, self.FFN_KINDS),
        ):
            if len(layers) != self.num_hidden_layers:
                raise ValueError(f"{name} gives {len(layers)} layers for {self.num_hidden_layers}")
            if not set(layers) <= set(known):
                raise ValueError(f"{name} must be of {known}; got {layers}")
        if norm_type not in self.NORM_KINDS:
            raise ValueError(f"norm_type must be of {self.NORM_KINDS}; got {norm_type}")
        mixers, ffns = set(self.mixer_layers), set(self.ffn_layers)
        classic = mixers == {"mha"} and ffns == {"mlp"} and norm_type == "layer_norm"
        kinds = norm_type == "rms_norm" and "mlp" not in ffns
        if ("latent" in mixers) != (self.qk_nope_head_dim is not None) or not (classic or kinds):
            raise ValueError(
                "layer kinds: either every default (mha, mlp, layer_norm) or, under rms_norm, mixers of "
                "latent (with the latent ranks and head dims) / ssm / conv / mha / none and feed-forwards of "
                f"swiglu / routed / none; got {mixer_types}, {ffn_types}, {norm_type}, "
                f"qk_nope_head_dim {self.qk_nope_head_dim}"
            )
        if self.rope_scaling is not None and (
            not isinstance(self.rope_scaling, dict) or self.rope_scaling.get("type") != "yarn" or "latent" not in mixers
        ):
            raise ValueError(f"rope_scaling is null or a 'yarn' group of latent attention's; got {self.rope_scaling}")
        if type(self.hc_mult) is not int or self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError(f"hc_mult and hc_sinkhorn_iters are whole numbers of at least 1; got {self.hc_mult}, {self.hc_sinkhorn_iters}")
        if self.hc_mult > 1 and not kinds:
            raise ValueError("hc_mult > 1: only the kinds block (norm_type rms_norm) carries residual streams")
        if not kinds:
            if self.num_key_value_heads not in (None, self.num_attention_heads):
                raise ValueError("the classic block has as many key/value heads as query heads")
            if self.mha_qk_norm or self.mha_rope:
                raise ValueError("mha_qk_norm and mha_rope are the kinds block's grouped-query attention's")
            return
        if self.structured_event_processing_mode != StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT:
            raise ValueError("the kinds block serves the conditionally-independent model only")
        bare = [i for i, parts in enumerate(zip(self.mixer_layers, self.ffn_layers)) if set(parts) == {"none"}]
        if bare:
            raise ValueError(f"layer kinds: layers {bare} have neither a mixer nor a feed-forward")
        if "mha" in mixers:
            kv = self.num_key_value_heads or self.num_attention_heads
            if kv <= 0 or self.num_attention_heads % kv:
                raise ValueError(
                    f"num_attention_heads {self.num_attention_heads} is not a multiple of num_key_value_heads {kv}"
                )
        if "ssm" in mixers:
            sizes = (self.mamba_num_heads, self.mamba_head_dim, self.mamba_n_groups, self.ssm_state_size)
            if None in sizes:
                raise ValueError(
                    "mixer 'ssm' needs mamba_num_heads, mamba_head_dim, mamba_n_groups and ssm_state_size"
                )
            if self.mamba_num_heads % self.mamba_n_groups:
                raise ValueError(
                    f"mamba_num_heads {self.mamba_num_heads} is not a multiple of mamba_n_groups {self.mamba_n_groups}"
                )
            if self.mamba_conv_kernel < 1 or self.mamba_chunk_size < 1:
                raise ValueError("mamba_conv_kernel and mamba_chunk_size are at least 1")
        if "conv" in mixers and (type(self.conv_L_cache) is not int or self.conv_L_cache < 1 or self.conv_bias):
            raise ValueError(
                f"mixer 'conv' needs conv_L_cache, its taps, of at least 1, and no bias (conv_bias true is not built); "
                f"got {self.conv_L_cache}, {self.conv_bias}"
            )

    @property
    def uses_layer_kinds(self) -> bool:
        """True where the stack is built of `models/blocks.py::KindsBlock`."""
        return self.norm_type != "layer_norm"

    def measurements_for(self, modality: DataModality) -> list[str]:
        return self.measurements_per_generative_mode.get(modality, [])

    def expand_attention_types_params(self, attention_types: ATTENTION_TYPES_LIST_T) -> list[str]:
        """Expands the attention-type mini-language into a per-layer list.

        Reference: ``transformer/config.py:818-837``.

        Examples:
            >>> cfg = StructuredTransformerConfig(num_hidden_layers=4)
            >>> cfg.expand_attention_types_params("global")
            ['global', 'global', 'global', 'global']
            >>> cfg.expand_attention_types_params(["local", "global"])
            ['local', 'global', 'local', 'global']
            >>> cfg.expand_attention_types_params([(["global", "local"], 1), (["global"], 2)])
            ['global', 'local', 'global', 'global']
        """
        if isinstance(attention_types, str):
            return [attention_types] * self.num_hidden_layers
        if not isinstance(attention_types, list):
            raise TypeError(f"Config Invalid {attention_types} ({type(attention_types)}) is wrong type!")
        if isinstance(attention_types[0], str):
            return (attention_types * self.num_hidden_layers)[: self.num_hidden_layers]
        if isinstance(attention_types[0], (list, tuple)):
            attentions = []
            for sub_list, n_layers in attention_types:
                attentions.extend(list(sub_list) * n_layers)
            return attentions[: self.num_hidden_layers]
        raise TypeError(f"Config Invalid {attention_types} El 0 ({type(attention_types[0])}) is wrong type!")

    def set_to_dataset(self, dataset) -> None:
        """Copies vocabulary/idxmap/task information from a dataset.

        Reference: ``transformer/config.py:839-899``. ``dataset`` is any
        object with the `JaxDataset` attribute surface (``measurement_configs``,
        ``vocabulary_config``, ``max_seq_len``, TTE stats, task fields).
        """
        self.measurement_configs = dataset.measurement_configs
        self.measurements_idxmap = dataset.vocabulary_config.measurements_idxmap
        self.measurements_per_generative_mode = dict(
            dataset.vocabulary_config.measurements_per_generative_mode
        )
        for k in DataModality.values():
            if k not in self.measurements_per_generative_mode:
                self.measurements_per_generative_mode[k] = []

        if self.structured_event_processing_mode == StructuredEventProcessingMode.NESTED_ATTENTION:
            in_dep = {
                x[0] if isinstance(x, (list, tuple)) and len(x) == 2 else x
                for x in itertools.chain.from_iterable(self.measurements_per_dep_graph_level)
            }
            in_generative_mode = set(
                itertools.chain.from_iterable(self.measurements_per_generative_mode.values())
            )
            if not in_generative_mode.issubset(in_dep):
                raise ValueError(
                    "Config is attempting to generate something outside the dependency graph:\n"
                    f"{in_generative_mode - in_dep}"
                )

        self.event_types_idxmap = dataset.vocabulary_config.event_types_idxmap
        self.vocab_offsets_by_measurement = dataset.vocabulary_config.vocab_offsets_by_measurement
        self.vocab_sizes_by_measurement = dict(dataset.vocabulary_config.vocab_sizes_by_measurement)
        for k in set(self.vocab_offsets_by_measurement.keys()) - set(self.vocab_sizes_by_measurement.keys()):
            self.vocab_sizes_by_measurement[k] = 1

        self.vocab_size = dataset.vocabulary_config.total_vocab_size
        self.max_seq_len = dataset.max_seq_len

        if self.TTE_generation_layer_type == TimeToEventGenerationHeadType.LOG_NORMAL_MIXTURE:
            self.mean_log_inter_event_time_min = dataset.mean_log_inter_event_time_min
            self.std_log_inter_event_time_min = dataset.std_log_inter_event_time_min

        if getattr(dataset, "has_task", False):
            if len(dataset.tasks) == 1:
                self.finetuning_task = dataset.tasks[0]
                task_type = dataset.task_types[self.finetuning_task]
                if task_type in ("binary_classification", "multi_class_classification"):
                    self.id2label = {i: v for i, v in enumerate(dataset.task_vocabs[self.finetuning_task])}
                    self.label2id = {v: i for i, v in self.id2label.items()}
                    self.num_labels = len(self.id2label)
                    self.problem_type = "single_label_classification"
                elif task_type == "regression":
                    self.num_labels = 1
                    self.problem_type = "regression"
            elif all(t == "binary_classification" for t in dataset.task_types.values()):
                self.problem_type = "multi_label_classification"
                self.num_labels = len(dataset.tasks)
            elif all(t == "regression" for t in dataset.task_types.values()):
                self.num_labels = len(dataset.tasks)
                self.problem_type = "regression"

    def to_dict(self) -> dict[str, Any]:
        """Serializes to a plain dict, recursing into measurement configs."""
        as_dict = {
            k: v
            for k, v in self.__dict__.items()
            if k not in ("seq_attention_layers", "mixer_layers", "ffn_layers", "_extra_kwargs")
        }
        as_dict.pop("dep_graph_attention_layers", None)
        if as_dict.get("measurement_configs"):
            as_dict["measurement_configs"] = {
                k: (v if isinstance(v, dict) else v.to_dict())
                for k, v in as_dict["measurement_configs"].items()
            }
        if as_dict.get("id2label") is not None:
            as_dict["id2label"] = {int(k): v for k, v in as_dict["id2label"].items()}
        return as_dict

    @classmethod
    def from_dict(cls, as_dict: dict) -> "StructuredTransformerConfig":
        as_dict = dict(as_dict)
        if as_dict.get("id2label") is not None:
            as_dict["id2label"] = {int(k): v for k, v in as_dict["id2label"].items()}
        return cls(**as_dict)

    def __eq__(self, other) -> bool:
        if not isinstance(other, StructuredTransformerConfig):
            return False
        return self.to_dict() == other.to_dict()


@config_dataclass
class OptimizationConfig(JSONableMixin):
    """Optimization settings: AdamW + polynomial decay with linear warmup.

    Reference: ``transformer/config.py:209-311`` (``OptimizationConfig``).
    ``set_to_dataset`` derives step counts from dataset length.
    """

    init_lr: float = 1e-2
    end_lr: float | None = None
    end_lr_frac_of_init_lr: float | None = 1e-3
    max_epochs: int = 100
    batch_size: int = 32
    validation_batch_size: int = 32
    lr_frac_warmup_steps: float | None = 0.01
    lr_num_warmup_steps: int | None = None
    max_training_steps: int | None = None
    lr_decay_power: float = 1.0
    weight_decay: float = 0.01
    patience: int | None = None
    gradient_accumulation: int | None = None
    num_dataloader_workers: int = 0

    def __post_init__(self):
        if self.end_lr_frac_of_init_lr is not None:
            if self.end_lr_frac_of_init_lr <= 0.0 or self.end_lr_frac_of_init_lr >= 1.0:
                raise ValueError("`end_lr_frac_of_init_lr` must be between 0.0 and 1.0!")
            if self.end_lr is not None:
                prod = self.end_lr_frac_of_init_lr * self.init_lr
                if not math.isclose(self.end_lr, prod):
                    raise ValueError(
                        "If both set, `end_lr` must be equal to `end_lr_frac_of_init_lr * init_lr`! Got "
                        f"end_lr={self.end_lr}, end_lr_frac_of_init_lr * init_lr = {prod}!"
                    )
            self.end_lr = self.end_lr_frac_of_init_lr * self.init_lr
        else:
            if self.end_lr is None:
                raise ValueError("Must set either end_lr or end_lr_frac_of_init_lr!")
            self.end_lr_frac_of_init_lr = self.end_lr / self.init_lr

    def set_to_dataset(self, dataset, steps_per_epoch: int | None = None) -> None:
        """Derives ``max_training_steps`` / warmup steps from dataset length.

        Reference: ``transformer/config.py:277-311``. ``steps_per_epoch``
        overrides the padded-batch count — packed-batch training fits several
        subjects per row, so its per-epoch step count (and therefore the LR
        schedule horizon) is a packing-factor smaller.
        """
        if steps_per_epoch is None:
            steps_per_epoch = int(math.ceil(len(dataset) / self.batch_size))
        if self.max_training_steps is None:
            self.max_training_steps = steps_per_epoch * self.max_epochs
        if self.lr_num_warmup_steps is None:
            assert self.lr_frac_warmup_steps is not None
            self.lr_num_warmup_steps = int(round(self.lr_frac_warmup_steps * self.max_training_steps))
        elif self.lr_frac_warmup_steps is None:
            self.lr_frac_warmup_steps = self.lr_num_warmup_steps / self.max_training_steps
        # Unlike the reference (``transformer/config.py:303-305``, where an
        # operator-precedence slip makes the check unreachable), this really
        # validates that warmup fraction and step count agree.
        if not (
            math.floor(self.lr_frac_warmup_steps * self.max_training_steps) <= self.lr_num_warmup_steps
            <= math.ceil(self.lr_frac_warmup_steps * self.max_training_steps)
        ):
            raise ValueError(
                "`self.lr_frac_warmup_steps`, `self.max_training_steps`, and `self.lr_num_warmup_steps` "
                "should be consistent, but they aren't! Got\n"
                f"\tself.max_training_steps = {self.max_training_steps}\n"
                f"\tself.lr_frac_warmup_steps = {self.lr_frac_warmup_steps}\n"
                f"\tself.lr_num_warmup_steps = {self.lr_num_warmup_steps}"
            )
