"""Probe: where does the padded-epoch wall time go (collate / transfer / dispatch)?

The r04 artifact shows ~78 ms wall per step vs 13.5 ms device compute on the
padded CI section. Candidate sinks: host collation (~4 ms measured), the
per-batch ``device_put`` transfer to the device, or per-step dispatch on
a contended control plane. This script measures each in isolation:

  A. collate-only: time ``JaxDataset.batches`` drained on the host.
  B. transfer-only: ``shard_batch`` (device_put) of pre-collated batches,
     one readback at the end, RTT-subtracted.
  C. resident-step epoch: step dispatch loop on ONE resident batch (no
     transfers) — same count as a real epoch, one drain.
  D. full epoch (prefetch pipeline, as bench.py ran it through round 4).
  E. device-resident epoch (`DeviceDataset`: CSR in HBM, on-device collate,
     ~100-byte plans on the wire) — the round-5 fix.

Host-only host timings are exact; device-involved ones use the sustained
protocol (pipelined dispatches + one readback − RTT).
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np


def main():
    import tempfile

    import jax
    import jax.numpy as jnp

    from eventstreamgpt_tpu.data import JaxDataset, PytorchDatasetConfig, prefetch_to_device
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
    from eventstreamgpt_tpu.utils.benchmarking import (
        dispatch_echo_ms,
        drain,
        readback_echo_ms,
    )

    N_TRAIN = 512
    BATCH, SEQ_LEN, HIDDEN = 32, 256, 256

    data_dir = Path(tempfile.mkdtemp(prefix="esgpt_feed_probe_"))
    write_synthetic_dataset(
        data_dir,
        n_subjects_per_split={"train": N_TRAIN},
        n_event_types=40,
        n_labs=3500,
        n_meds=500,
        mean_seq_len=200,
        max_seq_len=512,
        seed=0,
    )
    data_config = PytorchDatasetConfig(save_dir=data_dir, max_seq_len=SEQ_LEN, min_seq_len=4)
    ds = JaxDataset(data_config, "train")
    print(f"n_subjects={len(ds)} max_n_dynamic={ds.max_n_dynamic}", flush=True)

    config = StructuredTransformerConfig(
        hidden_size=HIDDEN,
        head_dim=HIDDEN // 4,
        num_attention_heads=4,
        num_hidden_layers=2,
        seq_attention_types=["local", "global"],
        seq_window_size=32,
        intermediate_size=HIDDEN * 4,
        TTE_generation_layer_type="log_normal_mixture",
        TTE_lognormal_generation_num_components=3,
        precision="bf16",
    )
    config.set_to_dataset(ds)
    oc = OptimizationConfig(init_lr=1e-3, batch_size=BATCH, max_epochs=1)
    oc.set_to_dataset(ds)

    model = build_model(config)
    tx, _ = build_optimizer(oc)
    mesh = data_parallel_mesh(BATCH)
    init_batch = next(ds.batches(BATCH, shuffle=True, seed=0))
    params = model.init(jax.random.PRNGKey(0), init_batch)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    state = replicate(state, mesh)
    train_step = make_train_step(model, tx)
    rng = jax.random.PRNGKey(0)

    resident = shard_batch(init_batch, mesh)
    state, loss = train_step(state, resident, rng)
    drain(loss)

    echo = dispatch_echo_ms()
    rtt = readback_echo_ms()
    print(f"dispatch_echo_ms={echo:.2f} readback_rtt_ms={rtt:.1f}", flush=True)

    # Batch wire size
    nbytes = sum(
        np.asarray(v).nbytes
        for v in jax.tree_util.tree_leaves(init_batch)
    )
    print(f"batch_wire_bytes={nbytes} ({nbytes/1e6:.2f} MB)", flush=True)

    # A. collate-only
    t0 = time.perf_counter()
    n_batches = 0
    for b in ds.batches(BATCH, shuffle=True, seed=1):
        n_batches += 1
    t_collate = time.perf_counter() - t0
    print(f"A collate-only: {1000*t_collate/n_batches:.2f} ms/batch ({n_batches} batches)", flush=True)

    # B. transfer-only: pre-collate, then device_put all + one readback
    host_batches = list(ds.batches(BATCH, shuffle=True, seed=2))
    for rep in range(2):
        rtt_i = readback_echo_ms()
        t0 = time.perf_counter()
        dev = [shard_batch(b, mesh) for b in host_batches]
        drain(dev[-1].time_delta)  # readback forces all transfers complete? only last...
        # force ALL: sum a scalar touching each
        s = sum(jnp.sum(d.time_delta) for d in dev)
        drain(s)
        t = 1000 * (time.perf_counter() - t0) - rtt_i
        print(f"B transfer-only rep{rep}: {t/len(host_batches):.2f} ms/batch", flush=True)
        del dev

    # C. resident-step loop: n_batches steps on one resident batch, one drain
    for rep in range(2):
        rtt_i = readback_echo_ms()
        t0 = time.perf_counter()
        for _ in range(n_batches):
            state, loss = train_step(state, resident, rng)
        drain(loss)
        t = 1000 * (time.perf_counter() - t0) - rtt_i
        print(f"C resident-steps rep{rep}: {t/n_batches:.2f} ms/step", flush=True)

    # C2. steps on alternating prefetched device batches (transfer + step, no collate)
    dev_batches = [shard_batch(b, mesh) for b in host_batches]
    s = sum(jnp.sum(d.time_delta) for d in dev_batches)
    drain(s)
    for rep in range(2):
        rtt_i = readback_echo_ms()
        t0 = time.perf_counter()
        for d in dev_batches:
            state, loss = train_step(state, d, rng)
        drain(loss)
        t = 1000 * (time.perf_counter() - t0) - rtt_i
        print(f"C2 steps-over-resident-batches rep{rep}: {t/len(dev_batches):.2f} ms/step", flush=True)
    del dev_batches

    # D. full epoch as bench runs it
    for rep in range(2):
        t0 = time.perf_counter()
        it = prefetch_to_device(
            ds.batches(BATCH, shuffle=True, seed=3 + rep),
            lambda b: shard_batch(b, mesh),
            host_stats_fn=lambda b: int(b.event_mask.sum()),
        )
        ev = 0
        nb = 0
        for d, n in it:
            ev += n
            state, loss = train_step(state, d, rng)
            nb += 1
        drain(loss)
        dt = time.perf_counter() - t0
        print(
            f"D full-epoch rep{rep}: {1000*dt/nb:.2f} ms/step, {ev/dt:.0f} ev/s",
            flush=True,
        )

    # E. device-resident epoch: upload once, per-step wire = the plan.
    from eventstreamgpt_tpu.data import DeviceDataset

    t0 = time.perf_counter()
    dd = DeviceDataset(ds, mesh=mesh)
    drain(dd.arrays["time_delta"])
    t_upload = time.perf_counter() - t0
    print(f"E upload: {dd.nbytes/1e6:.1f} MB in {1000*t_upload:.0f} ms", flush=True)
    for rep in range(3):
        t0 = time.perf_counter()
        ev = 0
        nb = 0
        for d, n in dd.batches(BATCH, shuffle=True, seed=3 + rep, with_counts=True):
            ev += n
            state, loss = train_step(state, d, rng)
            nb += 1
        drain(loss)
        dt = time.perf_counter() - t0
        print(
            f"E device-resident epoch rep{rep}: {1000*dt/nb:.2f} ms/step, {ev/dt:.0f} ev/s",
            flush=True,
        )

    # F. chunked-scan epochs: k collate+step iterations per dispatch.
    from eventstreamgpt_tpu.training import make_chunked_train_step

    for chunk in (4, 8, 16):
        chunk_step = make_chunked_train_step(model, tx, dd)
        # compile outside the timing
        plans0, _ = next(iter(dd.plan_chunks(BATCH, chunk, shuffle=True, seed=0)))
        state, _ = chunk_step(state, dd.arrays, plans0, rng)
        drain(_)
        for rep in range(2):
            t0 = time.perf_counter()
            ev = 0
            nb = 0
            for plans, n in dd.plan_chunks(BATCH, chunk, shuffle=True, seed=30 + rep):
                ev += n
                state, losses = chunk_step(state, dd.arrays, plans, rng)
                nb += plans["starts"].shape[0]
            drain(losses)
            dt = time.perf_counter() - t0
            print(
                f"F chunk={chunk} rep{rep}: {1000*dt/nb:.2f} ms/step, {ev/dt:.0f} ev/s",
                flush=True,
            )


if __name__ == "__main__":
    main()
