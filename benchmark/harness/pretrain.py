"""Job kind ``pretrain``: the chunked train step that ``train()`` dispatches,
fed from a device-resident cohort, timed over a window.

Set-up makes the cohort and the parameters from the seed, builds the program's
own objects the way ``training.pretrain.train`` does (``JaxDataset`` ->
``DeviceDataset`` plans -> ``make_chunked_train_step``), and drives ONE
dispatch from the fresh state: that dispatch compiles the window's program and
is what `correct` is decided on. The same state and the same compiled step go
on into the window.

After the window the program's state is freed and the plain reference follows
the first dispatch's optimizer steps from the same seed, on batches it
collates itself from the cohort's arrays.
"""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import numpy as np

from benchmark.harness import cohort as cohort_lib
from benchmark.harness import flops as flops_lib


# ----------------------------------------------------------- the program side
def _override(value) -> str:
    if isinstance(value, bool):
        return "true" if value else "false"
    if value is None:
        return "null"
    if isinstance(value, (list, dict)):
        return json.dumps(value)
    return str(value)


def pretrain_overrides(cell: dict, data_dir: Path, save_dir: Path, seed: int) -> list[str]:
    """The ``scripts.pretrain`` argument list of the cell."""
    feed, opt = cell["feed"], cell["optimizer"]
    trainer = {
        "device_resident_data": True,
        "steps_per_execution": feed["steps_per_dispatch"],
    }
    if feed["packed"]:
        trainer.update(use_packed_batches=True, packed_seq_len=feed["seq_len"])
    return [
        f"data_config.save_dir={data_dir}",
        f"data_config.max_seq_len={feed['data_max_seq_len']}",
        f"data_config.min_seq_len={cell['cohort']['min_seq_len']}",
        f"save_dir={save_dir}",
        f"seed={seed % (2**31)}",
        f"optimization_config.init_lr={opt['init_lr']}",
        f"optimization_config.end_lr_frac_of_init_lr={opt['end_lr'] / opt['init_lr']}",
        f"optimization_config.batch_size={feed['batch_size']}",
        f"optimization_config.validation_batch_size={feed['batch_size']}",
        f"optimization_config.max_training_steps={opt['max_training_steps']}",
        f"optimization_config.lr_num_warmup_steps={opt['lr_num_warmup_steps']}",
        "optimization_config.lr_frac_warmup_steps=null",
        f"optimization_config.lr_decay_power={opt['lr_decay_power']}",
        f"optimization_config.weight_decay={opt['weight_decay']}",
        *[f"trainer_config.{k}={_override(v)}" for k, v in trainer.items()],
        *[f"config.{k}={_override(v)}" for k, v in cell["model"]["config"].items()],
    ]


def reference_model(cell: dict, cohort: cohort_lib.Cohort) -> dict:
    """The sizes the plain reference needs, from the configuration's file."""
    c = cell["model"]["config"]
    nested = c.get("structured_event_processing_mode") == "nested_attention"
    return {
        "mode": "na" if nested else "ci",
        "hidden_size": c["hidden_size"],
        "head_dim": c["head_dim"],
        "num_attention_heads": c["num_attention_heads"],
        "num_hidden_layers": c["num_hidden_layers"],
        "intermediate_size": c["intermediate_size"],
        "seq_attention_types": c["seq_attention_types"],
        "seq_window_size": c["seq_window_size"],
        "tte_components": c["TTE_lognormal_generation_num_components"],
        "init_std": c["init_std"],
        "measurements_per_dep_graph_level": c.get("measurements_per_dep_graph_level"),
        "mean_log_inter_event_time": cohort.mean_log_inter_event_time,
        "std_log_inter_event_time": cohort.std_log_inter_event_time,
    }


class Program:
    """The program's objects of one run, built as ``train()`` builds them."""

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
        data_dir = cohort_lib.write_dl_cache(cohort, cell["cohort"], work_dir / "data")
        cfg = load_config(
            PretrainConfig, overrides=pretrain_overrides(cell, data_dir, work_dir / "run", seed)
        )
        log("cache file written")
        self.train_ds = JaxDataset(cfg.data_config, split="train")
        log("JaxDataset read")
        shutil.rmtree(data_dir)  # the cohort lives in memory from here on
        config = cfg.build_model_config()
        config.set_to_dataset(self.train_ds)
        # train() takes the TTE head's normalisation from the cohort at hand;
        # here it is the recipe's population value, the same for every seed,
        # so that one compiled program serves every seed.
        config.mean_log_inter_event_time_min = cohort.mean_log_inter_event_time
        config.std_log_inter_event_time_min = cohort.std_log_inter_event_time
        if feed["packed"]:
            config.max_seq_len = feed["seq_len"]
        oc = cfg.optimization_config
        oc.set_to_dataset(self.train_ds, steps_per_epoch=1)
        self.model = build_model(config)
        tx, _ = build_optimizer(oc)
        self.mesh = parallel_mesh(oc.batch_size, oc.validation_batch_size)
        self.device_data = DeviceDataset.create(
            self.train_ds, mesh=self.mesh, batch_sizes=(oc.batch_size,)
        )
        log("DeviceDataset built")
        self.step = make_chunked_train_step(
            self.model, tx, self.device_data, packed=feed["packed"], with_health=True
        )
        self.model_sizes = reference_model(cell, cohort)
        self.rng = jax.random.PRNGKey(seed % (2**31))
        self._init = jax.jit(lambda key: reference.init_params(self.model_sizes, cohort.vocab, key))
        params = self._init(self.rng)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
        self.state = replicate(state, self.mesh)
        log("state made on the device")
        self._plans = self._plan_stream()

    def _plan_stream(self):
        """Full dispatches of plans, epoch after epoch: a short last chunk of
        an epoch would compile anew, so it is dropped (as a short last batch
        is) and the stream runs on into the next epoch."""
        feed, k = self.feed, self.feed["steps_per_dispatch"]
        epoch = 0
        while True:
            seed = self.seed % (2**31) + epoch
            if feed["packed"]:
                chunks = self.device_data.packed_plan_chunks(
                    feed["batch_size"], k, seq_len=feed["seq_len"], seed=seed
                )
            else:
                chunks = self.device_data.plan_chunks(feed["batch_size"], k, shuffle=True, seed=seed)
            got = 0
            for plans, n_events in chunks:
                if next(iter(plans.values())).shape[0] == k:
                    got += 1
                    yield plans, n_events
            if got == 0:
                raise ValueError("the cohort is too small for one full dispatch of plans")
            epoch += 1

    def next_plans(self):
        return next(self._plans)

    def dispatch(self, plans):
        """One call of the chunked step; returns the losses (device array)."""
        from eventstreamgpt_tpu.parallel.context import kernel_mesh

        with kernel_mesh(self.mesh):
            self.state, (losses, _health) = self.step(self.state, self.device_data.arrays, plans, self.rng)
        return losses

    def observed_norms(self) -> dict:
        """Per-leaf norms of the parameters' change since the seed and of
        AdamW's first moment, worked out on the device from the live state."""
        import jax
        import jax.numpy as jnp

        init = self._init

        @jax.jit
        def norms(params, mu, key):
            p0 = init(key)
            leaf = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))  # noqa: E731
            return (
                jax.tree_util.tree_map(lambda a, b: leaf(a - b), params, p0),
                jax.tree_util.tree_map(leaf, mu),
            )

        live_mu = self.state.opt_state[0].mu
        delta, mu = norms(self.state.params, live_mu, self.rng)
        # The first moment itself goes to the host (0.67 GB for CI): the
        # direction gap needs the tensors side by side with the reference's,
        # and the device has to be free of them during the window.
        return {"delta": _flat(delta), "mu": _flat(mu), "mu_tensors": _flat_arrays(jax.device_get(live_mu))}

    def free(self):
        self.state = self.device_data = self.step = self._plans = None


def _flat(tree) -> dict:
    import jax

    return {
        jax.tree_util.keystr(path): float(v)
        for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _flat_arrays(tree) -> dict:
    import jax

    return {
        jax.tree_util.keystr(path): np.asarray(v, np.float32)
        for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


# ------------------------------------------------------------- the comparison
def reference_batches(cell: dict, cohort, plans: dict) -> list[dict]:
    """The batches of one dispatch of plans, collated by the benchmark."""
    k = next(iter(plans.values())).shape[0]
    out = []
    for i in range(k):
        if cell["feed"]["packed"]:
            b = cohort_lib.packed_batch(cohort, plans["event_ids"][i], plans["event_mask"][i])
        else:
            if not plans["valid_mask"][i].all():
                raise ValueError("a padded plan holds a fill row; the cohort is too small")
            b = cohort_lib.padded_batch(
                cohort, plans["subject_indices"][i], plans["starts"][i], cell["feed"]["data_max_seq_len"]
            )
        out.append(b)
    return out


def follow(cell: dict, cohort, reference, model_sizes: dict, plans: dict, seed: int, quant=None, fault=None):
    """The reference's own run of one dispatch: losses, per-leaf norms of the
    parameters' change and of the first moment. ``fault`` plants one of the
    faults a training cell can have into the reference put in the program's
    place (`FAULTS`)."""
    import jax
    import jax.numpy as jnp

    opt = cell["optimizer"]
    batches = reference_batches(cell, cohort, plans)
    if fault == "half_batch":
        half = cell["feed"]["batch_size"] // 2
        batches = [{k: (None if v is None else v[:half]) for k, v in b.items()} for b in batches]
    batches = [{k: (None if v is None else jnp.asarray(v)) for k, v in b.items()} for b in batches]
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda key: reference.init_params(model_sizes, cohort.vocab, key))(
            jax.random.PRNGKey(seed % (2**31))
        )
        if fault == "state_unchanged":
            losses = [
                jax.jit(
                    lambda p, b: reference.batch_loss_and_grad(
                        p, b, model_sizes, cohort.vocab, cell["check"]["rows_per_block"], quant
                    )[0]
                )(p0, b)
                for b in batches
            ]
            zero = jax.tree_util.tree_map(lambda a: jnp.zeros((), jnp.float32), p0)
            still = _flat_arrays(jax.tree_util.tree_map(np.zeros_like, jax.device_get(p0)))
            return [float(l) for l in losses], _flat(zero), _flat(zero), still
        keep = jax.tree_util.tree_map(jnp.copy, p0)
        losses, params, mu = reference.train_steps(
            p0, batches, model_sizes, cohort.vocab, opt, cell["check"]["rows_per_block"], quant
        )
        leaf = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: leaf(x - y), a, b))(params, keep)
        mu_n = jax.jit(lambda m: jax.tree_util.tree_map(leaf, m))(mu)
    return [float(l) for l in losses], _flat(delta), _flat(mu_n), _flat_arrays(jax.device_get(mu))


FAULTS = ("state_unchanged", "half_batch")


def worst_leaf_gap(got: dict, want: dict, skip: set = frozenset()) -> tuple[float, str]:
    """The widest gap between a leaf's norm here and in the reference, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' gradients are all but nought)."""
    names = [n for n in want if n not in skip]
    median = float(np.median([want[n] for n in names]))
    worst, at = 0.0, ""
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], median, 1e-30)
        if gap > worst:
            worst, at = gap, n
    return worst, at


def leaf_differences(got: dict, want: dict) -> dict:
    """Per leaf, the norm of its difference from the reference's leaf against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger. Unlike a gap of norms it sees unbiased rounding noise, which
    leaves a norm all but unchanged."""
    norms = {n: float(np.linalg.norm(w)) for n, w in want.items()}
    median = float(np.median(list(norms.values())))
    return {n: float(np.linalg.norm(got[n] - w)) / max(norms[n], median, 1e-30) for n, w in want.items()}


def compare(cell: dict, observed: dict, ref_losses, ref_delta, ref_mu, ref_mu_tensors) -> dict:
    """Each number compared, beside its limit."""
    limits = cell["check"]["limits"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(observed["losses"], ref_losses))
    # A leaf whose gradient is nought to rounding in the reference moves under
    # Adam by round-off alone: left out of the change by a rule on the
    # reference's first moment (under a thousandth of the median leaf's).
    median_mu = float(np.median(list(ref_mu.values())))
    dead = {n for n, v in ref_mu.items() if v < 1e-3 * median_mu}
    grad_gap, grad_at = worst_leaf_gap(observed["mu"], ref_mu)
    delta_gap, delta_at = worst_leaf_gap(observed["delta"], ref_delta, skip=dead)
    diffs = leaf_differences(observed["mu_tensors"], ref_mu_tensors)
    diff_at = max(diffs, key=diffs.get)
    numbers = {
        "loss_gap": {"value": loss_gap, "limit": limits.get("loss_gap")},
        "grad_norm_gap": {"value": grad_gap, "limit": limits.get("grad_norm_gap"), "leaf": grad_at},
        "param_change_gap": {"value": delta_gap, "limit": limits.get("param_change_gap"), "leaf": delta_at},
        "grad_diff_gap": {"value": diffs[diff_at], "limit": limits.get("grad_diff_gap"), "leaf": diff_at},
        "grad_diff_median": {"value": float(np.median(list(diffs.values()))), "limit": limits.get("grad_diff_median")},
    }
    finite = all(np.isfinite(v["value"]) for v in numbers.values())
    ok = finite and all(v["limit"] is None or v["value"] <= v["limit"] for v in numbers.values())
    return {"ok": ok, "numbers": numbers, "dead_leaves": sorted(dead), "leaf_differences": diffs}


# --------------------------------------------------------------------- the job
def attention_key_stats(plans_seen: list[dict], cell: dict, cohort) -> dict:
    """Real events and the keys their queries attend to, over the plans the
    window ran (for the FLOP count): causal within the segment, and within the
    window in a local layer."""
    window = cell["model"]["config"]["seq_window_size"]
    n, g_keys, l_keys = 0, 0, 0
    for plans in plans_seen:
        if cell["feed"]["packed"]:
            mask, seg = plans["event_mask"], plans["segment_ids"]
            start = np.concatenate(
                [np.ones_like(seg[..., :1], bool), seg[..., 1:] != seg[..., :-1]], axis=-1
            )
            pos = np.arange(seg.shape[-1])
            first = np.maximum.accumulate(np.where(start, pos, 0), axis=-1)
            depth = (pos - first + 1)[mask]
        else:
            off = cohort.offsets
            lens = np.minimum(
                off[plans["subject_indices"] + 1] - off[plans["subject_indices"]],
                cell["feed"]["data_max_seq_len"],
            )[plans["valid_mask"]]
            depth = np.concatenate([np.arange(1, n_ + 1) for n_ in lens.ravel()])
        n += depth.size
        g_keys += int(depth.sum())
        l_keys += int(np.minimum(depth, window).sum())
    return {"events": n, "global_keys": g_keys / max(n, 1), "local_keys": l_keys / max(n, 1)}


def run(cell: dict, seed: int, seconds: float, trace: bool, env) -> dict:
    """One run of the cell. ``env`` is the harness's `RunEnv` (clock, spans,
    compile meter, work directory, tracing); the result is the run's record,
    which the per-layer metric readers and the result line are made from."""
    import jax

    reference = env.reference
    env.log("imports done; making the cohort")
    cohort = cohort_lib.make_cohort(cell["cohort"], seed)
    env.log(f"cohort made: {cohort.n_events} events")
    prog = Program(cell, cohort, reference, seed, env.work_dir, log=env.log)
    model_sizes = prog.model_sizes
    first_plans, _ = prog.next_plans()
    first_losses = prog.dispatch(first_plans)
    observed = {"losses": [float(x) for x in np.asarray(first_losses)]}
    env.log(f"first dispatch done: losses {observed['losses']}")
    observed.update(prog.observed_norms())
    env.log(f"norms read; compile so far {env.meter.seconds:.1f}s, cache hits {env.meter.hits} misses {env.meter.misses}")

    k, in_flight = cell["feed"]["steps_per_dispatch"], cell["feed"]["in_flight"]
    spans = env.spans
    plans_seen, pending, events, dispatches = [], [], 0, 0
    compiles_before = env.meter.compiles
    if trace:
        env.start_trace()
        seconds = min(seconds, cell["trace_seconds"])
    t0 = time.perf_counter()
    env.window_start = t0
    while time.perf_counter() - t0 < seconds:
        with spans.span("feed_plan"):
            plans, n_events = prog.next_plans()
        with spans.span("dispatch"):
            pending.append(prog.dispatch(plans))
        plans_seen.append(plans)
        events += n_events
        dispatches += 1
        if len(pending) > in_flight:
            with spans.span("wait_result"):
                jax.block_until_ready(pending.pop(0))
    with spans.span("wait_result"):
        jax.block_until_ready(pending)
    t1 = time.perf_counter()
    if trace:
        env.stop_trace()
    compiled_in_window = env.meter.compiles - compiles_before
    last_losses = np.asarray(pending[-1])
    memory_peak = env.memory_peak()
    env.log(f"window closed: {dispatches} dispatches; memory {jax.devices()[0].memory_stats()}")
    keys = attention_key_stats(plans_seen, cell, cohort)
    prog.free()
    del prog, pending

    with spans.span("reference"):
        ref = follow(cell, cohort, reference, model_sizes, first_plans, seed)
    env.log(f"reference done; compile in all {env.meter.seconds:.1f}s, cache hits {env.meter.hits} misses {env.meter.misses}")
    verdict = compare(cell, observed, *ref)
    env.log(f"leaves left out of the change (no gradient in the reference): {verdict['dead_leaves']}")
    verdict["numbers"]["compiles_in_window"] = {"value": compiled_in_window, "limit": 0}
    verdict["numbers"]["last_loss_finite"] = {"value": int(np.isfinite(last_losses).all()), "limit": 1}
    verdict["ok"] = bool(verdict["ok"] and compiled_in_window == 0 and np.isfinite(last_losses).all())
    fwd = flops_lib.forward_flops_per_event(model_sizes, cohort.vocab, keys["global_keys"], keys["local_keys"])
    return {
        "correct": verdict["ok"],
        "compared": verdict["numbers"],
        "attempted": dispatches * k,
        "failed": 0,
        "window": (t0, t1),
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_events_per_s": events / (t1 - t0)},
        "end_to_end_units": {"train_events_per_s": "events/s"},
        "counters": {
            "events": events,
            "steps": dispatches * k,
            "dispatches": dispatches,
            "flops_per_event": 3 * fwd,
            "global_keys": keys["global_keys"],
            "local_keys": keys["local_keys"],
            "rows_per_step": cell["feed"]["batch_size"],
            "row_len": cell["feed"]["seq_len"] if cell["feed"]["packed"] else cell["feed"]["data_max_seq_len"],
        },
        "model_sizes": model_sizes,
    }
