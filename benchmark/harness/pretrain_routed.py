"""Job kind ``pretrain_routed``: job kind ``pretrain`` for a backbone of
latent attention and routed feed-forwards (`configs/glm47flash_ep8.json`).

The window, the clock, the plan stream, `compare` and the planted faults are
`harness/pretrain.py`'s, by import: `run` here is that module's `run` with
four of its names substituted while it runs (`Program`, `follow`,
`attention_key_stats`, the FLOP module). What this file adds: the sizes this
model has (`reference_model`), a `Program` whose step returns the routed
layers' counters beside the health vector (kept on the device until the window
has closed), and the counters in the record. Two pieces repeat the base's
line for line but for what is said at each: `Program.__init__` (the base
builds its step and its sizes from names this file cannot reach into) and
`follow` (the base keeps a second copy of the seed's parameters on the device,
2.4 GB here beside the reference's 12 GB of AdamW state and gradients).
"""

from __future__ import annotations

import contextlib
import shutil
import types
from pathlib import Path

import numpy as np

from benchmark.harness import cohort as cohort_lib
from benchmark.harness import flops_routed
from benchmark.harness import pretrain as base
from benchmark.harness.pretrain import FAULTS, compare  # noqa: F401  (a job module's surface)

_LATENT = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")
_ROUTED = (
    "moe_intermediate_size", "moe_router_width", "n_routed_experts", "moe_expert_offset",
    "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
)


def model_config(cell: dict):
    """The program's configuration object of the cell. A program without the
    layer kinds refuses here, before anything is built."""
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    config = StructuredTransformerConfig(**cell["model"]["config"])
    if not getattr(config, "uses_layer_kinds", False):
        raise ValueError("this program does not know the layer kinds of " + cell["config"])
    return config


def reference_model(cell: dict, cohort: cohort_lib.Cohort) -> dict:
    """The sizes the plain reference and the readers need."""
    c = cell["model"]["config"]
    if not cell["feed"]["packed"]:
        raise ValueError("pretrain_routed counts events from packed plans")
    config = model_config(cell)
    return {
        "mode": "ci",
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_hidden_layers": c["num_hidden_layers"],
        "intermediate_size": c["intermediate_size"],
        # What the readers of the classic cells index: every layer is global,
        # and the flash core's head width is nope + rope.
        "seq_attention_types": ["global"],
        "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        "ffn_layers": list(config.ffn_layers),
        **{k: c[k] for k in _LATENT + _ROUTED},
        "rms_norm_eps": c["layer_norm_epsilon"],
        "tte_components": c["TTE_lognormal_generation_num_components"],
        "init_std": c["init_std"],
        "mean_log_inter_event_time": cohort.mean_log_inter_event_time,
        "std_log_inter_event_time": cohort.std_log_inter_event_time,
    }


class Program(base.Program):
    """`base.Program` with this model's sizes and a step that returns the
    routing counters; everything but `__init__` and `dispatch` is inherited."""

    def __init__(self, cell: dict, cohort, reference, seed: int, work_dir: Path, log=lambda what: None):
        import jax
        import jax.numpy as jnp

        from eventstreamgpt_tpu.data import JaxDataset
        from eventstreamgpt_tpu.data.device_dataset import DeviceDataset
        from eventstreamgpt_tpu.training import PretrainConfig, TrainState, build_model, build_optimizer
        from eventstreamgpt_tpu.training.pretrain import make_chunked_train_step, parallel_mesh, replicate
        from eventstreamgpt_tpu.utils.config_tool import load_config

        feed = cell["feed"]
        self.cell, self.feed, self.seed = cell, feed, seed
        self.model_sizes = reference_model(cell, cohort)
        data_dir = cohort_lib.write_dl_cache(cohort, cell["cohort"], work_dir / "data")
        cfg = load_config(
            PretrainConfig, overrides=base.pretrain_overrides(cell, data_dir, work_dir / "run", seed)
        )
        log("cache file written")
        self.train_ds = JaxDataset(cfg.data_config, split="train")
        log("JaxDataset read")
        shutil.rmtree(data_dir)
        config = cfg.build_model_config()
        config.set_to_dataset(self.train_ds)
        config.mean_log_inter_event_time_min = cohort.mean_log_inter_event_time
        config.std_log_inter_event_time_min = cohort.std_log_inter_event_time
        config.max_seq_len = feed["seq_len"]
        oc = cfg.optimization_config
        oc.set_to_dataset(self.train_ds, steps_per_epoch=1)
        self.model = build_model(config)
        tx, _ = build_optimizer(oc)
        self.mesh = parallel_mesh(oc.batch_size, oc.validation_batch_size)
        self.device_data = DeviceDataset.create(self.train_ds, mesh=self.mesh, batch_sizes=(oc.batch_size,))
        log("DeviceDataset built")
        self.step = make_chunked_train_step(
            self.model, tx, self.device_data, packed=True, with_health=True, with_routing=True
        )
        self.rng = jax.random.PRNGKey(seed % (2**31))
        self._plans = self._plan_stream()
        self._init = jax.jit(lambda key: reference.init_params(self.model_sizes, cohort.vocab, key))
        params = self._init(self.rng)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
        self.state = replicate(state, self.mesh)
        log("state made on the device")
        # Per dispatch: its real events (host) and its (steps, 2) counters (device).
        self.dispatched: list[tuple[int, object]] = []

    def dispatch(self, plans):
        from eventstreamgpt_tpu.parallel.context import kernel_mesh

        with kernel_mesh(self.mesh):
            self.state, (losses, _health, routing) = self.step(
                self.state, self.device_data.arrays, plans, self.rng
            )
        self.dispatched.append((int(np.asarray(plans["event_mask"]).sum()), routing))
        return losses


def follow(cell: dict, cohort, reference, model_sizes: dict, plans: dict, seed: int, quant=None, fault=None):
    """`base.follow` with the seed's parameters kept on the host while the
    reference trains: losses, per-leaf norms of the parameters' change and of
    the first moment, and the first moment itself."""
    import jax
    import jax.numpy as jnp

    opt, rows = cell["optimizer"], cell["check"]["rows_per_block"]
    batches = base.reference_batches(cell, cohort, plans)
    if fault == "half_batch":
        half = cell["feed"]["batch_size"] // 2
        batches = [{k: (None if v is None else v[:half]) for k, v in b.items()} for b in batches]
    batches = [{k: (None if v is None else jnp.asarray(v)) for k, v in b.items()} for b in batches]
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda key: reference.init_params(model_sizes, cohort.vocab, key))(
            jax.random.PRNGKey(seed % (2**31))
        )
        if fault == "state_unchanged":
            loss = jax.jit(lambda p, b: reference.batch_loss_and_grad(p, b, model_sizes, cohort.vocab, rows, quant)[0])
            losses = [float(loss(p0, b)) for b in batches]
            zero = jax.tree_util.tree_map(lambda a: jnp.zeros((), jnp.float32), p0)
            still = base._flat_arrays(jax.tree_util.tree_map(np.zeros_like, jax.device_get(p0)))
            return losses, base._flat(zero), base._flat(zero), still
        keep = jax.device_get(p0)
        losses, params, mu = reference.train_steps(p0, batches, model_sizes, cohort.vocab, opt, rows, quant)
        leaf = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: leaf(x - y), a, b))(params, keep)
        mu_n = jax.jit(lambda m: jax.tree_util.tree_map(leaf, m))(mu)
    return [float(l) for l in losses], base._flat(delta), base._flat(mu_n), base._flat_arrays(jax.device_get(mu))


def attention_key_stats(plans_seen: list[dict], cell: dict, cohort) -> dict:
    """`base.attention_key_stats` for a stack whose every layer is global:
    the window it reads for local layers is not in this configuration."""
    model = dict(cell["model"], config=dict(cell["model"]["config"], seq_window_size=1))
    return _base_key_stats(plans_seen, dict(cell, model=model), cohort)


_base_key_stats = base.attention_key_stats


@contextlib.contextmanager
def _substituted(module, **names):
    kept = {name: getattr(module, name) for name in names}
    for name, value in names.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


def window_routing(dispatched: list, model_sizes: dict) -> dict:
    """The record's routing counters, from the dispatches after the first
    (which is set-up's): read from the device here, after the window."""
    events = sum(n for n, _ in dispatched[1:])
    routing = np.concatenate([np.asarray(r) for _, r in dispatched[1:]]).astype(np.int64)
    return {
        "moe_pairs": int(routing[:, 0].sum()),
        "moe_load_max_sum": int(routing[:, 1].sum()),
        "moe_routed_layers": model_sizes["ffn_layers"].count("routed"),
        "moe_experts_held": model_sizes["n_routed_experts"],
        "moe_pairs_per_event_layer": routing[:, 0].sum() / max(events, 1) / model_sizes["ffn_layers"].count("routed"),
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
        return flops_routed.forward_flops_per_event(
            model_sizes, vocab, global_keys, routing["moe_pairs_per_event_layer"]
        )

    flops = types.SimpleNamespace(forward_flops_per_event=forward_flops_per_event)
    with _substituted(
        base, Program=Kept, follow=follow, attention_key_stats=attention_key_stats, flops_lib=flops
    ):
        record = base.run(cell, seed, seconds, trace, env)
    record["counters"].update(routing)
    env.log(f"routing counters of the window: {routing}")
    return record
