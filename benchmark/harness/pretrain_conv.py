"""Job kind ``pretrain_conv``: job kind ``pretrain_routed`` for a backbone of
gated short convolutions and grouped-query attention with QK-norm and RoPE,
a dense gated layer, then routed gated experts (`configs/lfm2_8b_a1b_ep4.json`).

In `harness/pretrain_routed_hc.py`'s manner and with `harness/pretrain_routed.py`'s
pieces, by import: the window, the clock, the plan stream, `compare` and the
planted faults are `harness/pretrain.py`'s; the `Program` whose step returns
the routing counters, `follow` with the seed's parameters on the host and the
counters' names (``moe_pairs``, ``moe_load_max_sum``, ``moe_routed_layers``:
the experts are the gated three-product form that
`metrics/moe_experts_roofline.py` and `metrics/moe_load_max_over_mean.py`
count) and the key statistics of a stack with no local layer are
`pretrain_routed`'s, and so is `run`, with this file's `Program` and FLOP
module substituted while it runs. What this file adds:
the sizes this model has (`reference_model`), its FLOP count
(`harness/flops_conv.py`), the refusal of a program that has no ``conv``
mixer, and the seed's selection biases (`selection_biases`).

The selection bias: at a bias of zero the seed's router sends this chip's
eight experts of 32 anywhere from 0.96 to 1.05 times their even share, and
the held experts' products, a fifth of the step, follow them, so the rate
would follow the seed. A trained LFM2 keeps its loads even by the published
balancing rule. So the seed's parameters carry the biases that rule would
hold them to on a dispatch's worth of the seed's histories
(`reference.balance_selection_bias` over `balancing_plans`), in the
program's state and in the reference's alike, and every seed gives this
chip its even share of the work.
"""

from __future__ import annotations

import copy
import itertools

import numpy as np

from benchmark.harness import cohort as cohort_lib
from benchmark.harness import flops_conv
from benchmark.harness import pretrain as base
from benchmark.harness import pretrain_routed as routed
from benchmark.harness.pretrain import FAULTS, compare  # noqa: F401  (a job module's surface)

_routed_follow = routed.follow  # `run` substitutes the name while it runs
_SIZES = (
    "num_key_value_heads", "head_dim", "conv_L_cache", "rope_theta", "moe_intermediate_size", "moe_router_width",
    "n_routed_experts", "moe_expert_offset", "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
)


def program_cell(cell: dict) -> dict:
    """The cell as the program is built from it: itself, unless its query
    heads were cut to no multiple of its key/value heads (the CPU rehearsal,
    `tests/benchmark/tiny.py`, cuts the heads to 4 and leaves the rest); then
    the key/value heads are cut in the published ratio, at least one."""
    c, published = cell["model"]["config"], cell["model"]
    if c["num_attention_heads"] % c["num_key_value_heads"] == 0:
        return cell
    per_group = published["num_attention_heads"] // published["num_key_value_heads"]
    cell = copy.deepcopy(cell)
    cell["model"]["config"]["num_key_value_heads"] = max(1, c["num_attention_heads"] // per_group)
    return cell


def model_config(cell: dict):
    """The program's configuration object of the cell. A program that has no
    ``conv`` mixer refuses here, before anything is built: its configuration
    knows no such kind."""
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    return StructuredTransformerConfig(**program_cell(cell)["model"]["config"])


def reference_model(cell: dict, cohort: cohort_lib.Cohort) -> dict:
    """The sizes the plain reference and the readers need."""
    c = program_cell(cell)["model"]["config"]
    if not cell["feed"]["packed"]:
        raise ValueError("pretrain_conv counts events from packed plans")
    config = model_config(cell)
    return {
        "mode": "ci",
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_hidden_layers": c["num_hidden_layers"],
        "intermediate_size": c["intermediate_size"],
        "mixer_layers": list(config.mixer_layers),
        "ffn_layers": list(config.ffn_layers),
        # What the readers of the classic cells index: `global` at the attention layers' indices only.
        "seq_attention_types": ["global" if kind == "mha" else "none" for kind in config.mixer_layers],
        **{k: c[k] for k in _SIZES},
        "rms_norm_eps": c["layer_norm_epsilon"],
        "tte_components": c["TTE_lognormal_generation_num_components"],
        "init_std": c["init_std"],
        "mean_log_inter_event_time": cohort.mean_log_inter_event_time,
        "std_log_inter_event_time": cohort.std_log_inter_event_time,
    }


def balancing_plans(cell: dict, cohort, plans: dict, seed: int) -> dict:
    """A dispatch's worth of packed rows, the feed's shape, of whole histories
    in the seed's order, none of them in ``plans``, the dispatch the check
    compares: a bias balanced on a row's own scores leaves that row at a
    tie, which rounding would decide otherwise in the program and the
    reference."""
    feed = cell["feed"]
    k, rows, L = feed["steps_per_dispatch"], feed["batch_size"], feed["seq_len"]
    compared = np.searchsorted(cohort.offsets, plans["event_ids"][plans["event_mask"]], side="right") - 1
    order = np.random.default_rng(seed).permutation(len(cohort.offsets) - 1)
    order = order[~np.isin(order, compared)]
    ids, mask = np.zeros((k * rows, L), np.int64), np.zeros((k * rows, L), bool)
    row, at = 0, 0
    for subject in order:
        lo = int(cohort.offsets[subject])
        n = min(int(cohort.offsets[subject + 1]) - lo, L)
        if at + n > L:
            row, at = row + 1, 0
        if row == k * rows:
            break
        ids[row, at : at + n], mask[row, at : at + n] = np.arange(lo, lo + n), True
        at += n
    return {"event_ids": ids.reshape(k, rows, L), "event_mask": mask.reshape(k, rows, L)}


def selection_biases(cell: dict, cohort, reference, model_sizes: dict, plans: dict, seed: int, params) -> dict:
    """The routed layers' selection biases that even out the loads of
    `balancing_plans` under the seed's ``params``."""
    batches = base.reference_batches(cell, cohort, balancing_plans(cell, cohort, plans, seed))
    return reference.balance_selection_bias(params, batches, model_sizes, cell["check"]["rows_per_block"])


class Program(routed.Program):
    """`pretrain_routed.Program` with this model's sizes, built from
    `program_cell`, and the seed's selection biases in its state."""

    def __init__(self, cell: dict, cohort, reference, seed: int, work_dir, log=lambda what: None):
        import jax

        with routed._substituted(routed, reference_model=reference_model):
            super().__init__(program_cell(cell), cohort, reference, seed, work_dir, log)
        first = next(self._plans)
        self._plans = itertools.chain([first], self._plans)
        # On the host: the state's copies are donated to the step.
        self.biases = jax.device_get(
            selection_biases(self.cell, cohort, reference, self.model_sizes, first[0], seed, self.state.params)
        )
        params = reference.with_selection_bias(self.state.params, self.biases)
        params = jax.tree_util.tree_map(lambda new, old: jax.device_put(new, old.sharding), params, self.state.params)
        self.state = self.state.replace(params=params)
        log("selection biases balanced")

    def observed_norms(self) -> dict:
        """`base.Program.observed_norms`, the selection biases' change counted
        from the biases set-up gave them (the seed's init, which the base
        counts from, has them at zero; the init is left as it is, so that its
        compiled norms are the same for every seed)."""
        out = super().observed_norms()
        encoder = self.state.params["params"]["encoder"]
        for name, bias in self.biases.items():
            now = np.asarray(encoder[name]["mlp"]["e_score_correction_bias"])
            out["delta"][f"['params']['encoder']['{name}']['mlp']['e_score_correction_bias']"] = float(
                np.linalg.norm(now - bias)
            )
        return out


class _Biased:
    """The reference module, its `init_params` giving the seed's selection biases."""

    def __init__(self, reference, biases: dict):
        self._reference, self._biases = reference, biases

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def init_params(self, model: dict, vocab: dict, key):
        return self._reference.with_selection_bias(self._reference.init_params(model, vocab, key), self._biases)


def follow(cell: dict, cohort, reference, model_sizes: dict, plans: dict, seed: int, quant=None, fault=None):
    """`pretrain_routed.follow` from the seed's parameters with the selection
    biases the program's set-up gave them, found again from the same histories."""
    import jax

    p0 = jax.jit(lambda key: reference.init_params(model_sizes, cohort.vocab, key))(jax.random.PRNGKey(seed % (2**31)))
    biases = selection_biases(cell, cohort, reference, model_sizes, plans, seed, p0)
    del p0
    return _routed_follow(cell, cohort, _Biased(reference, biases), model_sizes, plans, seed, quant, fault)


def run(cell: dict, seed: int, seconds: float, trace: bool, env) -> dict:
    """`pretrain_routed.run` over this file's `Program`, `follow` and FLOP count."""
    model_config(cell)  # a program that cannot build the configuration fails here, at once
    with routed._substituted(routed, Program=Program, follow=follow, flops_routed=flops_conv):
        return routed.run(program_cell(cell), seed, seconds, trace, env)
