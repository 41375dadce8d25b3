"""Standalone scale sweep: step time / MFU vs width x depth.

Evidence for "MFU at production width": the bench's toy
shape (hidden 256, 2 layers, ~5.5M params) is dispatch-dominated, so its MFU
says nothing about realistic widths. This script probes the full production
train step (fwd+bwd+AdamW, bf16 + Pallas flash/splash kernels, packed
seq-1024 segment-ID batches) across hidden {256, 512, 1024} x layers
{2, 6, 12}, with a dispatch-echo reading before each point and the
sustained-pipeline step probe (k dependent steps + one true readback − the
measured RTT; ``utils/benchmarking.py``, the readback-subtraction
protocol).

Each point prints one JSON line immediately; the final line is a summary
table. It runs only on a TPU (raises at start otherwise, and prints the
device it found) — through the chip tool:

    python -m scripts.probe_scale [--points 256x2,1024x12]

MFU here is the standard dense estimate (6 * n_params FLOPs per event,
fwd+bwd; attention FLOPs excluded) against the device's published bf16
peak (``utils.benchmarking.DEVICE_PEAKS``, keyed by ``device_kind``).
Attention at seq 1024 adds ~12*L*h FLOPs/event per layer (~10-20%
at these shapes), so the dense MFU is a mild *underestimate* of hardware
utilization.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

PACKED_BATCH, PACKED_SEQ_LEN = 8, 1024
HEAD_DIM = 64

POINTS = [(h, l) for h in (256, 512, 1024) for l in (2, 6, 12)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", default=None, help="comma list like 256x2,1024x12")
    ap.add_argument("--head-dim", type=int, default=HEAD_DIM)
    args = ap.parse_args(argv)
    head_dim = args.head_dim

    points = POINTS
    if args.points:
        points = [
            (int(h), int(l))
            for h, l in (p.lower().split("x") for p in args.points.split(","))
        ]

    import jax
    import jax.numpy as jnp

    from eventstreamgpt_tpu.utils.benchmarking import require_tpu
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    device = require_tpu()  # raises unless a TPU with a published peak
    configure_compile_cache()

    from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig
    from eventstreamgpt_tpu.data.synthetic import write_synthetic_dataset
    from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
    from eventstreamgpt_tpu.training import (
        TrainState,
        build_model,
        build_optimizer,
        data_parallel_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    # One shared packed batch at the bench's long-context shape.
    data_dir = Path(tempfile.mkdtemp(prefix="esgpt_probe_scale_"))
    write_synthetic_dataset(
        data_dir,
        n_subjects_per_split={"train": 128, "tuning": 16},
        n_event_types=40,
        n_labs=3500,
        n_meds=500,
        mean_seq_len=200,
        max_seq_len=512,
        seed=0,
    )
    train_ds = JaxDataset(
        PytorchDatasetConfig(save_dir=data_dir, max_seq_len=256, min_seq_len=4), "train"
    )
    packed_init = next(
        b
        for b in train_ds.packed_batches(PACKED_BATCH, seq_len=PACKED_SEQ_LEN, seed=1)
        if b.event_mask.shape[0] == PACKED_BATCH
    )
    probe_events = int(np.asarray(packed_init.event_mask).sum())

    mesh = data_parallel_mesh(PACKED_BATCH)
    n_devices = int(mesh.devices.size)
    resident = shard_batch(packed_init, mesh)
    rng = jax.random.PRNGKey(0)
    oc = OptimizationConfig(
        init_lr=1e-3, batch_size=PACKED_BATCH, max_training_steps=10,
        lr_num_warmup_steps=1, lr_frac_warmup_steps=None,
    )

    rows = []
    for hidden, layers in points:
        config = StructuredTransformerConfig(
            hidden_size=hidden,
            head_dim=head_dim,
            num_attention_heads=hidden // head_dim,
            num_hidden_layers=layers,
            seq_attention_types=["local", "global"],
            seq_window_size=32,
            intermediate_size=hidden * 4,
            TTE_generation_layer_type="log_normal_mixture",
            TTE_lognormal_generation_num_components=3,
            attention_implementation="pallas_flash",
            attention_dropout=0.0,
            precision="bf16",
        )
        config.set_to_dataset(train_ds)
        config.max_seq_len = PACKED_SEQ_LEN
        model = build_model(config)
        tx, _ = build_optimizer(oc)
        params = model.init(jax.random.PRNGKey(0), packed_init)
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        state = TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
        )
        state = replicate(state, mesh)
        step = make_train_step(model, tx)

        from eventstreamgpt_tpu.utils.benchmarking import drain, sustained_step_ms

        t_c = time.perf_counter()
        state, loss = step(state, resident, rng)  # compile + warmup
        drain(loss)
        compile_s = time.perf_counter() - t_c

        # Quiet-gate (dispatch echo; one shared definition of "quiet" —
        # utils/benchmarking.py), then the sustained-pipeline probe: step
        # time = (k pipelined steps + one readback − RTT) / k.
        from eventstreamgpt_tpu.utils.benchmarking import wait_for_quiet

        probe, contended = wait_for_quiet(retries=4)

        step_ms, state, info = sustained_step_ms(step, state, resident, rng)
        ev_per_s = probe_events / (step_ms / 1000.0) / n_devices
        mfu = ev_per_s * 6 * n_params / device["bf16_flops_per_s"]

        row = {
            "hidden": hidden,
            "layers": layers,
            "n_params": n_params,
            "step_ms": round(step_ms, 3),
            "events_per_sec_per_chip": round(ev_per_s, 1),
            "mfu_dense_vs_peak": round(mfu, 4),
            "dispatch_echo_ms": round(probe, 3),
            "contended": contended,
            "compile_s": round(compile_s, 1),
            "probe_k": info["k"],
            "readback_rtt_ms": info["readback_rtt_ms"],
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

        # Free this point's state before the next (larger) one compiles.
        del state, params, step, loss

    print(json.dumps({"scale_sweep": rows, "batch": PACKED_BATCH, "seq_len": PACKED_SEQ_LEN,
                      "events_per_batch": probe_events, "n_devices": n_devices,
                      "precision": "bf16", "kernels": "pallas flash+splash",
                      "device": {k: device[k] for k in ("platform", "kind", "count")}}))
    return rows


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
