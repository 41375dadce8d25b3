"""Job kind ``pretrain_routed_hc``: job kind ``pretrain_routed`` for a backbone
whose blocks carry hyper-connected residual streams and whose latent attention
has a key width beside a value width and YaRN's frequencies
(`configs/xing40_a4b_ep8.json`).

In `harness/pretrain_hybrid.py`'s manner and with `harness/pretrain_routed.py`'s
pieces, by import: the window, the clock, the plan stream, `compare` and the
planted faults are `harness/pretrain.py`'s; the `Program` whose step returns
the routing counters, `follow` with the seed's parameters on the host, the key
statistics of a stack with no local layer and the counters' names
(``moe_pairs``, ``moe_load_max_sum``, ``moe_routed_layers``: the experts are
the gated three-product form that `metrics/moe_experts_roofline.py` and
`metrics/moe_load_max_over_mean.py` count) are `pretrain_routed`'s, and so is
`run`, with this file's `Program` and FLOP module substituted while it runs.
What this file adds: the sizes this model has (`reference_model`) and its FLOP
count (`harness/flops_hc.py`).
"""

from __future__ import annotations

from benchmark.harness import cohort as cohort_lib
from benchmark.harness import flops_hc
from benchmark.harness import pretrain_routed as routed
from benchmark.harness.pretrain import FAULTS, compare  # noqa: F401  (a job module's surface)
from benchmark.harness.pretrain_routed import follow  # noqa: F401

_routed_sizes = routed.reference_model  # `Program` substitutes the name while it builds
_STREAMS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp", "rope_scaling")


def model_config(cell: dict):
    """The program's configuration object of the cell. A program that keeps
    ``hc_mult`` or ``rope_scaling`` as a key it does not know (it stores such
    keys and builds the plain block) is refused here, before anything is built."""
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    config = StructuredTransformerConfig(**cell["model"]["config"])
    unknown = sorted(set(_STREAMS) & set(getattr(config, "_extra_kwargs", ())))
    if unknown:
        raise ValueError(f"this program does not know {unknown} of {cell['config']}: it carries no residual streams")
    return config


def reference_model(cell: dict, cohort: cohort_lib.Cohort) -> dict:
    """`pretrain_routed.reference_model` plus the streams' sizes and YaRN's group."""
    c = cell["model"]["config"]
    model_config(cell)
    sizes = _routed_sizes(cell, cohort)
    # `metrics/flash_attn_roofline.py` reads one head width, and `flops.attention_needs` is linear in it:
    # at the mean of the key width and the value width, (192 + 128) / 2 = 160, it counts exactly QK^T, dQ
    # and dK at the key width, PV, dV and dP at the value width, and the planes q, k, dq, dk at the one and
    # v, o, do, dv at the other (tests/benchmark/test_routed_hc.py proves the equality). Zero lanes that
    # pad a key to whole tiles are not needs and are not counted.
    sizes["head_dim"] = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]) / 2
    return sizes | {k: c[k] for k in _STREAMS}


class Program(routed.Program):
    """`pretrain_routed.Program` with this model's sizes."""

    def __init__(self, *args, **kwargs):
        with routed._substituted(routed, reference_model=reference_model):
            super().__init__(*args, **kwargs)


def run(cell: dict, seed: int, seconds: float, trace: bool, env) -> dict:
    """`pretrain_routed.run` over this file's `Program` and FLOP count."""
    model_config(cell)  # a program that cannot build the configuration fails here, at once
    with routed._substituted(routed, Program=Program, flops_routed=flops_hc):
        return routed.run(cell, seed, seconds, trace, env)
