"""chip_smoke.py -- the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, on
ONE TPU chip, in ONE process, at the full width of the largest model this
repository has run (hidden 1024, 12 layers, 8 heads of 128; 166.6M
parameters; weights random, from a seed):

* **data** -- a synthetic cohort (``data/synthetic.py``, fixed seed) written
  as a DL cache under ``chip_smoke_out/`` and read back through `JaxDataset`;
* **train_ci** -- ``scripts.pretrain.main`` on packed sequences of 1,024
  events (bf16, the Pallas flash kernel, ``dots_no_batch`` remat,
  ``device_resident_data: true``): a scripted preemption writes a mid-epoch
  checkpoint, a second identical call restores it and finishes (its
  compiles hit the persistent cache); loss finite and decreasing; the
  lowered step must contain ``tpu_custom_call``;
* **serve** -- a `GenerationEngine` behind `ServingService` on those
  parameters with the chip's defaults (Pallas sampling tail), a few
  requests of mixed prompt length and budget; one is replayed through
  ``generate()``;
* **train_na** -- the nested-attention model at the tutorial shape, so the
  Pallas dep-graph kernel runs forward and backward;
* **kernels** -- one-shot Pallas-vs-XLA comparisons (dep-graph attention,
  ``fused_categorical``, ``vocab_gather``), outside any timing;
* **timing** -- printed, not asserted: wall time to ``block_until_ready``
  vs to a host readback for one jitted matmul of known size.

``--chips 4`` (the script's only option) runs ONLY the multi-chip phase:
the same CI width-1024 train steps on a ``data x fsdp`` mesh over the four
chips of one host (``fsdp_shards=4``, then pure data-parallel), compared
with the same steps from the same seed on one device of the four.

Exits non-zero and prints no ``ok`` line when JAX finds no TPU or when any
phase raises; no phase is wrapped in a ``try`` that lets the run go on.
The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the phases use. `FULL` is what the chip runs; the CPU
    rehearsal test (tests/test_chip_smoke.py) passes a tiny instance."""

    # data (~4k unified vocabulary)
    n_train: int = 512
    n_tuning: int = 64
    n_event_types: int = 40
    n_labs: int = 3500
    n_meds: int = 500
    mean_seq_len: int = 200
    data_max_seq_len: int = 256
    # CI at full width (the benchmark's ci_w1024: 166.6M parameters)
    hidden: int = 1024
    layers: int = 12
    heads: int = 8
    packed_seq_len: int = 1024
    batch: int = 8
    chunk: int = 4  # optimizer steps per scanned dispatch
    steps: int = 12
    preempt_at: int = 4  # scripted SIGTERM -> mid-epoch checkpoint -> resume
    # serve (same width, same parameters)
    n_slots: int = 8
    serve_max_len: int = 128
    decode_chunk: int = 8
    # (prompt_len, budget); the LAST one fills max_len and is the request
    # replayed through generate() (the engine's attention-width parity
    # condition, tests/test_engine.py `mixed_requests`).
    requests: tuple = ((24, 16), (40, 8), (64, 24), (100, 12), (17, 20), (96, 32))
    # NA at the tutorial shape
    na_hidden: int = 256
    na_heads: int = 4
    na_layers: int = 2
    na_batch: int = 32
    na_steps: int = 8
    # kernel comparisons
    gather_rows: int = 8192
    sample_rows: int = 64


FULL = Sizes()


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A phase's check did not hold (raised, never asserted: ``python -O``
    must not turn the smoke into a no-op)."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ------------------------------------------------------------ instrumentation
def compile_state() -> tuple:
    """The clock and the persistent cache's hits and misses so far, from the
    program's host record (`eventstreamgpt_tpu/utils/scopes.py`, whose
    listener on JAX's compile events is the process's one)."""
    from eventstreamgpt_tpu.utils import scopes

    totals = scopes.compile_totals()
    return time.perf_counter(), totals["hits"], totals["misses"]


def compile_since(before: tuple) -> str:
    """Backend-compile seconds and cache hits and misses since `compile_state`."""
    from eventstreamgpt_tpu.utils import scopes

    began, hits, misses = before
    totals = scopes.compile_totals()
    seconds = sum(s.end - s.start for s in scopes.since(began) if s.name == "compile/backend")
    return f"compile {seconds:.1f}s, cache hits {totals['hits'] - hits} misses {totals['misses'] - misses}"


@contextlib.contextmanager
def phase(name: str):
    before, t0 = compile_state(), time.perf_counter()
    say(f"[{name}] start")
    yield
    say(f"[{name}] done: wall {time.perf_counter() - t0:.1f}s, {compile_since(before)}, peak HBM {peak_hbm_gb()}")


def peak_hbm_gb() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# ----------------------------------------------------------------- the phases
def phase_data(out: Path, sz: Sizes):
    """Synthetic cohort -> DL cache on disk -> JaxDataset. No workers: the
    parent holds the chip, so nothing here may fork (`_fork_map`)."""
    from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
    from eventstreamgpt_tpu.data.synthetic import write_synthetic_dataset

    data_dir = write_synthetic_dataset(
        out / "data",
        n_subjects_per_split={
            "train": sz.n_train, "tuning": sz.n_tuning, "held_out": sz.n_tuning
        },
        n_event_types=sz.n_event_types,
        n_labs=sz.n_labs,
        n_meds=sz.n_meds,
        mean_seq_len=sz.mean_seq_len,
        max_seq_len=2 * sz.data_max_seq_len,
        seed=0,
    )
    train_ds = JaxDataset(
        PytorchDatasetConfig(save_dir=data_dir, max_seq_len=sz.data_max_seq_len, min_seq_len=4),
        "train",
    )
    n_events = int(train_ds.data.subject_event_offsets[-1])
    say(
        f"[data] {len(train_ds)} train subjects, {n_events} events, "
        f"vocab {train_ds.vocabulary_config.total_vocab_size} at {data_dir}"
    )
    check(len(train_ds) == sz.n_train and n_events > 0, "the cohort read back is not the one written")
    return data_dir, train_ds


def _model_overrides(sz: Sizes, kind: str) -> list[str]:
    """``config.*`` overrides of `scripts.pretrain` for the CI / NA model."""
    if kind == "ci":
        hidden, heads, layers = sz.hidden, sz.heads, sz.layers
        extra = [
            "config.attention_implementation=pallas_flash",
            "config.attention_dropout=0.0",
            "config.gradient_checkpointing=dots_no_batch",
            f"config.max_seq_len={sz.packed_seq_len}",
        ]
    else:
        hidden, heads, layers = sz.na_hidden, sz.na_heads, sz.na_layers
        extra = [
            "config.structured_event_processing_mode=nested_attention",
            "config.measurements_per_dep_graph_level=[[],[event_type],[lab,med]]",
            "config.dep_graph_attention_types=global",
            "config.do_full_block_in_seq_attention=false",
            "config.do_full_block_in_dep_graph_attention=true",
        ]
    return [
        f"config.hidden_size={hidden}",
        f"config.head_dim={hidden // heads}",
        f"config.num_attention_heads={heads}",
        f"config.num_hidden_layers={layers}",
        "config.seq_attention_types=[local,global]",
        "config.seq_window_size=32",
        f"config.intermediate_size={hidden * 4}",
        "config.TTE_generation_layer_type=log_normal_mixture",
        "config.TTE_lognormal_generation_num_components=3",
        "config.precision=bf16",
        *extra,
    ]


def pretrain_args(
    data_dir: Path, save_dir: Path, sz: Sizes, kind: str, trainer: dict | None = None
) -> list[str]:
    """The ``scripts.pretrain.main`` argument list of one training phase."""
    batch = sz.batch if kind == "ci" else sz.na_batch
    steps = sz.steps if kind == "ci" else sz.na_steps
    tc = {
        "device_resident_data": "true",
        "steps_per_execution": sz.chunk,
        "log_every_n_steps": sz.chunk,
        "checkpoint_every_n_steps": max(steps, sz.chunk),
        "max_checkpoints_to_keep": 1,
    }
    if kind == "ci":
        tc.update(use_packed_batches="true", packed_seq_len=sz.packed_seq_len)
    tc.update(trainer or {})
    return [
        f"data_config.save_dir={data_dir}",
        f"data_config.max_seq_len={sz.data_max_seq_len}",
        "data_config.min_seq_len=4",
        f"save_dir={save_dir}",
        "seed=1",
        "do_overwrite=true",
        "do_final_validation_on_metrics=false",
        "optimization_config.init_lr=1e-3",
        f"optimization_config.batch_size={batch}",
        f"optimization_config.validation_batch_size={batch}",
        "optimization_config.max_epochs=100",
        f"optimization_config.max_training_steps={steps}",
        "optimization_config.lr_frac_warmup_steps=0.25",
        *[f"trainer_config.{k}={v}" for k, v in tc.items()],
        *_model_overrides(sz, kind),
    ]


def read_train_losses(save_dir: Path) -> list[tuple[int, float]]:
    """(step, mean window loss) of every train record in train_log.jsonl."""
    recs = [json.loads(l) for l in (save_dir / "train_log.jsonl").read_text().splitlines()]
    return [(r["step"], r["train_loss"]) for r in recs if "train_loss" in r]


def check_losses(name: str, save_dir: Path, steps: int) -> None:
    import math

    losses = read_train_losses(save_dir)
    say(f"[{name}] window losses (step, loss): {losses}")
    check(losses and losses[-1][0] == steps, f"expected the log to end at step {steps}")
    check(all(math.isfinite(l) for _, l in losses), "non-finite training loss")
    check(losses[-1][1] < losses[0][1], "training loss did not decrease")


def lowered_train_step_text(save_dir: Path, train_ds, sz: Sizes, kind: str) -> str:
    """StableHLO text of the train step of the configuration the run saved."""
    import jax
    import jax.numpy as jnp

    from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
    from eventstreamgpt_tpu.training import TrainState, build_model, build_optimizer, make_train_step

    config = StructuredTransformerConfig.from_json_file(save_dir / "config.json")
    oc = OptimizationConfig.from_json_file(save_dir / "optimization_config.json")
    model = build_model(config)
    tx, _ = build_optimizer(oc)
    if kind == "ci":
        batch = next(
            iter(train_ds.packed_batches(sz.batch, seq_len=sz.packed_seq_len, seed=1))
        )
    else:
        batch = next(train_ds.batches(sz.na_batch, shuffle=True, seed=1))

    def abstract_state():
        params = model.init(jax.random.PRNGKey(0), batch)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
        )

    state = jax.eval_shape(abstract_state)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    say(f"[train_{kind}] {n_params / 1e6:.1f}M parameters")
    return make_train_step(model, tx).lower(state, batch, jax.random.PRNGKey(0)).as_text()


def phase_train_ci(data_dir: Path, train_ds, out: Path, sz: Sizes, expect_kernels: bool) -> Path:
    """CI at full width through ``scripts.pretrain.main``: preempt, resume.
    Prints each call's compile seconds, so the second call's persistent-cache
    hits are visible."""
    from eventstreamgpt_tpu.reliability import Preempted
    from eventstreamgpt_tpu.reliability.faults import Fault, FaultPlan, fault_plan
    from scripts.pretrain import main as pretrain_main

    save_dir = out / "pretrain_ci"
    args = pretrain_args(data_dir, save_dir, sz, "ci")
    say(f"[train_ci] scripts.pretrain.main({' '.join(args)})")

    preempted = False
    before = compile_state()
    with fault_plan(FaultPlan([Fault("sigterm", step=sz.preempt_at)])):
        try:
            pretrain_main(args)
        except Preempted as e:  # the scripted drain -- anything else propagates
            preempted = True
            say(f"[train_ci] preempted at step {e.step}; mid-epoch checkpoint written")
    check(preempted, "the scripted SIGTERM did not preempt the run")
    ckpts = sorted(p.name for p in (save_dir / "model_checkpoints").iterdir() if p.name.isdigit())
    check(ckpts, "preemption wrote no checkpoint")
    say(f"[train_ci] first call (to the preemption): {compile_since(before)}")
    say(f"[train_ci] checkpoints on disk: {ckpts}; resuming with the same arguments")

    before = compile_state()
    pretrain_main(args)  # restores the checkpoint, finishes the step budget
    say(f"[train_ci] second call (resume; same programs): {compile_since(before)}")
    check_losses("train_ci", save_dir, sz.steps)
    resumed_from = [s for s, _ in read_train_losses(save_dir)]
    check(resumed_from == sorted(set(resumed_from)), "resume retrained logged steps")

    text = lowered_train_step_text(save_dir, train_ds, sz, "ci")
    n_calls = text.count("tpu_custom_call")
    say(f"[train_ci] tpu_custom_call sites in the lowered step: {n_calls}")
    if expect_kernels:
        check(
            n_calls > 0,
            "attention_implementation=pallas_flash lowered WITHOUT a Pallas "
            "kernel: the models/transformer.py kernel gate chose einsum",
        )
    return save_dir


def _prompt_rows(train_ds, sz: Sizes):
    """One-row prompts trimmed to the requested lengths, from real subjects
    with at least that many events."""
    import numpy as np

    need = max(lp for lp, _ in sz.requests)
    rows = []
    for batch in train_ds.batches(sz.n_slots, shuffle=False, seed=0):
        lens = np.asarray(batch.event_mask).sum(axis=1)
        for r in range(batch.batch_size):
            if lens[r] >= need and len(rows) < len(sz.requests):
                lp = sz.requests[len(rows)][0]
                rows.append(batch.slice((slice(r, r + 1), slice(0, lp))))
        if len(rows) == len(sz.requests):
            return rows, batch
    raise AssertionError(f"fewer than {len(sz.requests)} subjects with >= {need} events")


def phase_serve(save_dir: Path, train_ds, sz: Sizes) -> None:
    """`ServingService` over one `GenerationEngine` on the trained params."""
    import jax
    import numpy as np

    from eventstreamgpt_tpu.generation import generate
    from eventstreamgpt_tpu.serving import GenerationEngine, Request, ServingService
    from eventstreamgpt_tpu.training import build_model
    from eventstreamgpt_tpu.training.checkpoint import load_pretrained

    params, config = load_pretrained(save_dir)
    model = build_model(config)
    prompts, template = _prompt_rows(train_ds, sz)
    engine = GenerationEngine(
        model,
        params,
        config,
        template=template,
        n_slots=sz.n_slots,
        max_len=sz.serve_max_len,
        decode_chunk=sz.decode_chunk,
        base_key=jax.random.PRNGKey(11),
    )
    say(
        f"[serve] engine: {sz.n_slots} slots, max_len {sz.serve_max_len}, "
        f"sampling_impl={engine.stats().get('sampling_impl')}"
    )
    reqs = [
        Request(
            prompt=p,
            max_new_events=budget,
            key=jax.random.fold_in(jax.random.PRNGKey(42), i),
            request_id=i,
        )
        for i, (p, (_, budget)) in enumerate(zip(prompts, sz.requests))
    ]
    t0 = time.perf_counter()
    results = ServingService([engine], base_key=jax.random.PRNGKey(11)).run(reqs)
    say(f"[serve] {len(results)} requests answered in {time.perf_counter() - t0:.1f}s (compile included)")
    check(len(results) == len(reqs), f"{len(results)} of {len(reqs)} requests answered")
    by_id = {r.request_id: r for r in results}
    for i, (lp, budget) in enumerate(sz.requests):
        r = by_id[i]
        check(r.ok, f"request {i} failed: {r.error}")
        say(
            f"[serve] request {i}: prompt {r.prompt_len} budget {budget} "
            f"n_generated {r.n_generated} n_events {r.n_events}"
        )
        check(
            r.prompt_len == lp and r.n_events == r.prompt_len + r.n_generated,
            f"request {i}: n_events != prompt_len + n_generated",
        )
        check(1 <= r.n_generated <= budget, f"request {i}: n_generated outside [1, budget]")
        for f in ("time_delta", "dynamic_values"):
            check(
                np.isfinite(np.asarray(getattr(r.batch, f), np.float32)).all(),
                f"request {i}: non-finite {f}",
            )

    # Replay the max_len-filling request through generate() (B=1, same key).
    # tests/test_engine.py pins this pair bit-exact in fp32 on the CPU. In
    # bf16 the two are different XLA programs whose fusions round
    # intermediates differently, so a near-tied draw can flip and the two
    # trajectories part from there on. What transfers to the chip and is
    # held here: the event structure and the stop are exact, the prompt is
    # untouched, and the leading generated events agree bit-for-bit in every
    # integer field (floats to the compute dtype) -- the first divergence,
    # if any, is printed.
    req, res = reqs[-1], by_id[len(reqs) - 1]
    ref = generate(
        model, params, req.prompt, config, req.key,
        max_new_events=req.max_new_events, return_output=True,
    )
    n, lp = res.n_events, res.prompt_len
    np.testing.assert_array_equal(
        np.asarray(res.batch.event_mask), np.asarray(ref.batch.event_mask)[:, :n]
    )
    check(res.n_generated == int(ref.n_generated[0]), "n_generated differs from generate()")
    int_fields = ("dynamic_indices", "dynamic_measurement_indices", "dynamic_values_mask")
    differs = np.zeros(n, bool)
    for f in int_fields:
        a = np.asarray(getattr(res.batch, f))[0]
        b = np.asarray(getattr(ref.batch, f))[0, :n]
        differs |= (a != b).reshape(n, -1).any(axis=1)
    agree = int(np.argmax(differs)) if differs.any() else n
    say(
        f"[serve] engine vs generate(): structure and stop exact; integer content "
        f"agrees for events [0, {agree}) of {n} (prompt {lp}, "
        f"{agree - lp} of {n - lp} generated events before the first divergence)"
    )
    check(agree > lp, "the first generated event already differs from generate()")
    for f in ("time_delta", "dynamic_values"):
        a = np.asarray(getattr(res.batch, f), np.float32)[:, :agree]
        b = np.asarray(getattr(ref.batch, f), np.float32)[:, :agree]
        say(f"[serve] engine vs generate() {f} over [0, {agree}): max abs diff {np.abs(a - b).max():.3e}")
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2, err_msg=f)


def phase_train_na(data_dir: Path, train_ds, out: Path, sz: Sizes, expect_kernels: bool) -> None:
    """NA at the tutorial shape, defaults: the dep-graph kernel fwd + bwd."""
    from eventstreamgpt_tpu.ops.impl_select import resolve_impl
    from scripts.pretrain import main as pretrain_main

    save_dir = out / "pretrain_na"
    args = pretrain_args(data_dir, save_dir, sz, "na")
    say(f"[train_na] dep_graph_attention impl resolves to {resolve_impl(None)!r}")
    say(f"[train_na] scripts.pretrain.main({' '.join(args)})")
    pretrain_main(args)
    check_losses("train_na", save_dir, sz.na_steps)
    text = lowered_train_step_text(save_dir, train_ds, sz, "na")
    n_calls = text.count("tpu_custom_call")
    say(f"[train_na] tpu_custom_call sites in the lowered step: {n_calls}")
    if expect_kernels:
        check(
            "dep_graph_attention_fwd" in text and "dep_graph_attention_bwd" in text,
            "the NA train step lowered without the Pallas dep-graph kernel",
        )


def phase_kernels(sz: Sizes, kernel_impl: str) -> None:
    """One-shot Pallas-vs-XLA comparisons at the smoke's shapes, to the
    tolerances the kernels' own test files pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from eventstreamgpt_tpu.ops.band_attention import dep_graph_attention
    from eventstreamgpt_tpu.ops.fused_sampling import fused_categorical
    from eventstreamgpt_tpu.ops.pallas_heads import vocab_gather

    V = 1 + sz.n_event_types + sz.n_labs + sz.n_meds
    rng = np.random.default_rng(0)

    # dep-graph attention: the NA phase's (N, Q, H, D) against S = Q + 1.
    N = sz.na_batch * sz.data_max_seq_len
    H, D = sz.na_heads, sz.na_hidden // sz.na_heads
    Q, S = 3, 4
    # Tolerances are tests/test_pallas_dep_graph.py's envelopes taken relative
    # to the largest reference magnitude (the test's inputs are O(1) at D=8;
    # at D=64 the same reassociation noise scales with the row sums): fp32
    # forward 2e-6 (the test's 2-ulp ULP envelope is 5e-7 at D=8), fp32
    # gradients GRAD's 3e-5, bf16 forward one bf16 ulp (pinned bit-exact in
    # interpret mode; the MXU's accumulation order may flip a last bit),
    # bf16 gradients the test's 3e-2.
    for dt, fwd_tol, grad_tol in ((jnp.float32, 2e-6, 3e-5), (jnp.bfloat16, 2**-7, 3e-2)):
        mk = lambda p: jnp.asarray(rng.normal(size=(N, p, H, D)).astype(np.float32)).astype(dt)  # noqa: E731
        q, k, v = mk(Q), mk(S), mk(S)

        def fwd_and_grads(impl):
            def loss(q_, k_, v_):
                out = dep_graph_attention(q_, k_, v_, q_offset=S - Q, impl=impl)
                return (out.astype(jnp.float32) ** 2).sum(), out

            (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return [np.asarray(x, np.float32) for x in (out, *grads)]

        got, ref = fwd_and_grads(kernel_impl), fwd_and_grads("xla")
        for name, a, b, tol in zip(
            ("out", "dq", "dk", "dv"), got, ref, (fwd_tol, grad_tol, grad_tol, grad_tol)
        ):
            diff, scale = float(np.abs(a - b).max()), float(np.abs(b).max())
            say(
                f"[kernels] dep_graph_attention {jnp.dtype(dt).name} {(N, Q, H, D)} {name}: "
                f"max abs diff {diff:.3e} at scale {scale:.3e} (tol {tol:.1e} x scale)"
            )
            check(np.isfinite(a).all() and diff <= tol * scale, f"dep_graph {name}")

    # fused_categorical: bit-exact vs the XLA tail (tests/test_fused_sampling.py).
    key = jax.random.PRNGKey(3)
    for dt in (jnp.bfloat16, jnp.float32):
        logits = jnp.asarray(rng.normal(size=(sz.sample_rows, V)).astype(np.float32) * 3).astype(dt)
        for kw in ({}, {"top_k": 10, "top_p": 0.9}):
            a = np.asarray(fused_categorical(logits, key, impl=kernel_impl, **kw))
            b = np.asarray(fused_categorical(logits, key, impl="xla", **kw))
            say(
                f"[kernels] fused_categorical {jnp.dtype(dt).name} V={V} {kw or 'unfiltered'}: "
                f"{int((a != b).sum())} of {a.size} draws differ"
            )
            np.testing.assert_array_equal(a, b)

    # vocab_gather: forward exact, gradient exact in fp32 / one rounding in
    # bf16 (tests/test_pallas_heads.py).
    M = 24
    ci = jnp.asarray(rng.integers(0, V, size=(sz.gather_rows, M)), jnp.int32)
    for dt, gtol in ((jnp.float32, dict(rtol=1e-6, atol=1e-6)), (jnp.bfloat16, dict(rtol=2e-2, atol=2e-2))):
        z = jnp.asarray(rng.normal(size=(sz.gather_rows, V)).astype(np.float32)).astype(dt)
        w = jnp.asarray(rng.normal(size=(sz.gather_rows, M)).astype(np.float32))

        def val_and_grad(impl):
            f = lambda z_: (vocab_gather(z_, ci, impl=impl) * w).sum()  # noqa: E731
            return vocab_gather(z, ci, impl=impl), jax.grad(f)(z)

        (fa, ga), (fb, gb) = val_and_grad(kernel_impl), val_and_grad("xla")
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
        ga, gb = np.asarray(ga, np.float32), np.asarray(gb, np.float32)
        say(
            f"[kernels] vocab_gather {jnp.dtype(dt).name} V={V}: forward exact, "
            f"grad max abs diff {np.abs(ga - gb).max():.3e} (tol {gtol})"
        )
        np.testing.assert_allclose(ga, gb, **gtol)


def phase_attention_parity(seq_len: int, hidden: int, expect_kernels: bool) -> None:
    """``attention_implementation="pallas_flash"`` vs the einsum path on one
    model and batch: the default [local, global] stack (band einsum + flash
    kernel) and an all-local window-24 stack on packed segments (the splash
    kernel). Loss and gradients to the tolerances the on-chip arms of
    tests/test_pallas_attention.py pinned before PR 22 folded them here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import _make_model_and_batch
    from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    def twin(cfg, **over):
        return CIPPTForGenerativeSequenceModeling(
            StructuredTransformerConfig.from_dict({**cfg.to_dict(), **over})
        )

    def compare(label, einsum_model, pallas_model, batch):
        params = einsum_model.init(jax.random.PRNGKey(0), batch)
        loss_of = lambda m: jax.jit(jax.value_and_grad(lambda p: m.apply(p, batch).loss))  # noqa: E731
        text = loss_of(pallas_model).lower(params).as_text()
        if expect_kernels:
            check("tpu_custom_call" in text, f"{label}: lowered without a Pallas kernel")
        (le, ge), (lp, gp) = loss_of(einsum_model)(params), loss_of(pallas_model)(params)
        worst = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gp))
        )
        say(
            f"[kernels] {label}: loss {float(lp):.6f} vs einsum {float(le):.6f}, "
            f"max abs grad diff {worst:.3e}, {text.count('tpu_custom_call')} kernel sites"
        )
        np.testing.assert_allclose(float(lp), float(le), rtol=2e-4)
        for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-2, atol=3e-3)

    model, batch = _make_model_and_batch(
        batch_size=4, seq_len=seq_len, n_data=6, hidden=hidden, vocab=512
    )
    base = twin(model.config, attention_dropout=0.0)
    compare(
        "flash (local band + global flash)", base,
        twin(base.config, attention_implementation="pallas_flash"), batch,
    )
    seg = np.zeros((4, seq_len), np.int64)
    seg[:, 2 * seq_len // 5 :] = 1
    event_mask = np.asarray(batch.event_mask).copy()
    event_mask[:, 9 * seq_len // 10 :] = False
    packed = batch.replace(segment_ids=jnp.asarray(seg), event_mask=jnp.asarray(event_mask))
    local = twin(base.config, seq_attention_types="local", seq_window_size=24)
    compare(
        "splash (all-local window 24, packed segments)", local,
        twin(local.config, attention_implementation="pallas_flash"), packed,
    )


def phase_timing(n: int = 4096, chain: int = 32) -> None:
    """Printed, not asserted: does ``block_until_ready`` wait for the
    computation, or return at dispatch?"""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def f(a):
        for _ in range(chain):
            a = (a @ a) * jnp.bfloat16(1.0 / n)
        return a

    jax.block_until_ready(f(x))
    float(f(x)[0, 0])
    t0 = time.perf_counter()
    y = f(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    float(y[0, 0])
    t_after = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(f(x)[0, 0])
    t_readback = time.perf_counter() - t0
    tflop = chain * 2 * n**3 / 1e12
    say(
        f"[timing] {chain} chained {n}x{n} bf16 matmuls ({tflop:.2f} TFLOP): "
        f"dispatch returned at {1e3 * t_dispatch:.2f} ms, block_until_ready at "
        f"{1e3 * t_block:.2f} ms, one-element readback after it at {1e3 * t_after:.2f} ms; "
        f"dispatch+readback without block {1e3 * t_readback:.2f} ms; "
        f"block-to-block rate {tflop / t_block:.1f} TFLOP/s"
    )


# ------------------------------------------------------------ the 4-chip path
def phase_multichip(data_dir: Path, out: Path, sz: Sizes, n_chips: int) -> None:
    """CI full-width train steps on ``data x fsdp`` over all chips of the
    host vs the same steps from the same seed on one device of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
    from eventstreamgpt_tpu.parallel import kernel_mesh
    from eventstreamgpt_tpu.training import (
        PretrainConfig,
        TrainState,
        build_model,
        build_optimizer,
        make_train_step,
        parallel_mesh,
        replicate,
        shard_batch,
    )
    from eventstreamgpt_tpu.training.sharding import make_state_shardings
    from eventstreamgpt_tpu.utils.config_tool import load_config

    # The PretrainConfig `scripts.pretrain.main` would build, so the model,
    # optimizer and mesh come from the same code path train() uses.
    cfg = load_config(
        PretrainConfig, overrides=pretrain_args(data_dir, out / "pretrain_mc", sz, "ci")
    )
    train_ds = JaxDataset(cfg.data_config, split="train")
    config = cfg.build_model_config()
    config.set_to_dataset(train_ds)
    config.max_seq_len = sz.packed_seq_len
    oc = cfg.optimization_config
    oc.set_to_dataset(train_ds, steps_per_epoch=sz.steps)
    model = build_model(config)
    tx, _ = build_optimizer(oc)
    batches = []
    for b in train_ds.packed_batches(sz.batch, seq_len=sz.packed_seq_len, seed=1):
        batches.append(b)
        if len(batches) == sz.chunk:
            break
    rng = jax.random.PRNGKey(1)

    def fresh_state():
        params = model.init(jax.random.PRNGKey(0), batches[0])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))

    def probe_leaf(state):
        flat = jax.tree_util.tree_leaves_with_path(state.params)
        path, leaf = max(flat, key=lambda kv: kv[1].size)
        return jax.tree_util.keystr(path), leaf

    def run(label: str, mesh, n_fsdp: int) -> list[float]:
        state = fresh_state()
        shardings = None
        if n_fsdp > 1:
            shardings = make_state_shardings(state, mesh, strict=True)
            state = jax.device_put(state, shardings)
        else:
            state = replicate(state, mesh)
        step = make_train_step(model, tx, out_state_shardings=shardings)
        name, leaf = probe_leaf(state)
        first = shard_batch(batches[0], mesh)
        holders = {d.id for s in first.time_delta.addressable_shards for d in [s.device]}
        say(f"[{label}] mesh {dict(mesh.shape)} over devices {[d.id for d in mesh.devices.flat]}")
        say(f"[{label}] param {name} {leaf.shape}: {leaf.sharding}")
        say(
            f"[{label}] batch time_delta {first.time_delta.shape}: {first.time_delta.sharding}; "
            f"shards on devices {sorted(holders)}, "
            f"shard shape {first.time_delta.addressable_shards[0].data.shape}"
        )
        check(
            len(holders) == mesh.devices.size,
            f"{len(holders)} of {mesh.devices.size} devices hold batch shards",
        )
        losses = []
        t0 = time.perf_counter()
        with kernel_mesh(mesh):  # as training.train() does around its steps
            for b in batches:
                state, loss = step(state, shard_batch(b, mesh), rng)
                losses.append(float(loss))
        say(f"[{label}] losses {losses} ({time.perf_counter() - t0:.1f}s, compile included)")
        check(all(np.isfinite(losses)), f"[{label}] non-finite loss")
        return losses

    devices = jax.devices()
    check(len(devices) == n_chips, f"--chips {n_chips} but JAX reports {len(devices)} devices")
    from jax.sharding import Mesh

    one = Mesh(np.asarray(devices[:1]), ("data",))
    ref = run("one_device", one, 1)

    mesh_fsdp = parallel_mesh(sz.batch, n_fsdp=n_chips)
    check(mesh_fsdp.devices.size == n_chips, "the fsdp mesh dropped devices")
    got_fsdp = run(f"fsdp{n_chips}", mesh_fsdp, n_chips)

    mesh_dp = parallel_mesh(sz.batch)
    check(
        mesh_dp.devices.size == n_chips,
        f"parallel_mesh shrank the data axis to {mesh_dp.devices.size} of {n_chips} devices",
    )
    got_dp = run(f"dp{n_chips}", mesh_dp, 1)

    # Same seed, same batches, bf16 compute: the layouts differ only in the
    # order of the cross-device gradient/loss reductions.
    tol = dict(rtol=2e-2, atol=2e-2)
    for label, got in ((f"fsdp{n_chips}", got_fsdp), (f"dp{n_chips}", got_dp)):
        diff = np.abs(np.asarray(got) - np.asarray(ref)).max()
        say(f"[{label}] vs one_device: max abs loss diff {diff:.3e} (tol {tol})")
        np.testing.assert_allclose(got, ref, err_msg=label, **tol)


# ------------------------------------------------------------------- the run
def run_smoke(chips: int, sz: Sizes = FULL, out: Path = OUT) -> dict:
    """Runs the phases for ``chips`` and returns the final JSON object.
    Raises (so `main` prints no ``ok`` line) unless JAX reports a TPU."""
    import jax

    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    say(f"device: {device}; jax {jax.__version__}")
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX reports platform {dev.platform!r}. "
            "It does not fall back to another backend."
        )
    say(f"compile cache: {configure_compile_cache()}")
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    t0 = time.perf_counter()

    with phase("data"):
        data_dir, train_ds = phase_data(out, sz)
    if chips > 1:
        with phase("multichip"):
            phase_multichip(data_dir, out, sz, chips)
    else:
        with phase("timing"):
            phase_timing()
        with phase("kernels"):
            phase_kernels(sz, "pallas")
            phase_attention_parity(sz.data_max_seq_len, sz.na_hidden, expect_kernels=True)
        with phase("train_ci"):
            save_dir = phase_train_ci(data_dir, train_ds, out, sz, expect_kernels=True)
        with phase("serve"):
            phase_serve(save_dir, train_ds, sz)
        with phase("train_na"):
            phase_train_na(data_dir, train_ds, out, sz, expect_kernels=True)
    shutil.rmtree(out, ignore_errors=True)  # checkpoints of a 166.6M model
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    return {"ok": True, "device": device}


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run ONLY the data x fsdp phase over the four chips of one host",
    )
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    result = run_smoke(args.chips)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
