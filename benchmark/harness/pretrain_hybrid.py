"""Job kind ``pretrain_hybrid``: job kind ``pretrain`` for a ``nemotron_h``
backbone, one part a layer: Mamba-2, routed relu^2 experts, grouped-query
attention (`configs/nemotron_twotower_ep16.json`).

In `harness/pretrain_routed.py`'s manner and with its pieces, by import: the
window, the clock, the plan stream, `compare` and the planted faults are
`harness/pretrain.py`'s; the `Program` whose step returns the routing counters,
`follow` with the seed's parameters on the host and the key statistics of a
stack with no local layer are `pretrain_routed`'s, substituted into the base
while it runs. What this file adds: the sizes this model has
(`reference_model`), its FLOP count (`harness/flops_hybrid.py`), and the
routing counters in the record under names of its own (``relu2_*``): the
benchmark's readers of the gated experts (`metrics/moe_experts_roofline.py`,
`metrics/moe_load_max_over_mean.py`) count three products an expert and read
nothing here.
"""

from __future__ import annotations

import types

from benchmark.harness import cohort as cohort_lib
from benchmark.harness import flops_hybrid
from benchmark.harness import pretrain as base
from benchmark.harness import pretrain_routed as routed
from benchmark.harness.pretrain import FAULTS, compare  # noqa: F401  (a job module's surface)
from benchmark.harness.pretrain_routed import attention_key_stats, follow  # noqa: F401

_LETTER = {("ssm", "none"): "M", ("none", "routed"): "E", ("mha", "none"): "*"}
_SIZES = (
    "mamba_num_heads", "mamba_head_dim", "mamba_n_groups", "ssm_state_size", "mamba_conv_kernel", "mamba_chunk_size",
    "num_key_value_heads", "moe_intermediate_size", "moe_shared_expert_intermediate_size", "moe_router_width",
    "n_routed_experts", "moe_expert_offset", "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
)


def model_config(cell: dict):
    """The program's configuration object of the cell. A program without
    these layer kinds refuses here, before anything is built."""
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    return StructuredTransformerConfig(**cell["model"]["config"])


def reference_model(cell: dict, cohort: cohort_lib.Cohort) -> dict:
    """The sizes the plain reference and the readers need."""
    c = cell["model"]["config"]
    if not cell["feed"]["packed"]:
        raise ValueError("pretrain_hybrid counts events from packed plans")
    config = model_config(cell)
    pattern = "".join(_LETTER[kinds] for kinds in zip(config.mixer_layers, config.ffn_layers))
    return {
        "mode": "ci",
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_hidden_layers": c["num_hidden_layers"],
        "intermediate_size": c["intermediate_size"],
        "pattern": pattern,
        "published_layers": cell["model"]["published"]["num_hidden_layers"],
        # What the readers of the classic cells index: `global` at the attention layers' indices only.
        "seq_attention_types": ["global" if letter == "*" else "none" for letter in pattern],
        "head_dim": c["head_dim"],
        **{k: c[k] for k in _SIZES},
        "rms_norm_eps": c["layer_norm_epsilon"],
        "tte_components": c["TTE_lognormal_generation_num_components"],
        "init_std": c["init_std"],
        "mean_log_inter_event_time": cohort.mean_log_inter_event_time,
        "std_log_inter_event_time": cohort.std_log_inter_event_time,
    }


class Program(routed.Program):
    """`pretrain_routed.Program` with this model's sizes."""

    def __init__(self, *args, **kwargs):
        with routed._substituted(routed, reference_model=reference_model):
            super().__init__(*args, **kwargs)


def window_routing(dispatched: list, model_sizes: dict) -> dict:
    """`pretrain_routed.window_routing` under this job's names."""
    layers = model_sizes["pattern"].count("E")
    read = routed.window_routing(dispatched, dict(model_sizes, ffn_layers=["routed"] * layers))
    return {
        "relu2_pairs": read["moe_pairs"],
        "relu2_load_max_sum": read["moe_load_max_sum"],
        "relu2_routed_layers": layers,
        "relu2_experts_held": read["moe_experts_held"],
        "relu2_pairs_per_event_layer": read["moe_pairs_per_event_layer"],
    }


def run(cell: dict, seed: int, seconds: float, trace: bool, env) -> dict:
    """`base.run` over this file's `Program`, key statistics and FLOP count."""
    model_config(cell)  # a program that cannot build the configuration fails here, at once
    programs = []

    class Kept(Program):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            programs.append(self.dispatched)

    routing = {}

    def forward_flops_per_event(model_sizes, vocab, global_keys, _local_keys):
        routing.update(window_routing(programs[0], model_sizes))
        return flops_hybrid.forward_flops_per_event(
            model_sizes, vocab, global_keys, routing["relu2_pairs_per_event_layer"]
        )

    flops = types.SimpleNamespace(forward_flops_per_event=forward_flops_per_event)
    with routed._substituted(
        base, Program=Kept, follow=follow, attention_key_stats=attention_key_stats, flops_lib=flops
    ):
        record = base.run(cell, seed, seconds, trace, env)
    record["counters"].update(routing)
    env.log(f"routing counters of the window: {routing}")
    return record
