"""Device op-level profile of the production train step at a given width.

Traces a few pipelined steps of the packed bf16+Pallas train step through
``jax.profiler`` and prints the top HLO ops by device self-time (parsed from
the xplane with ``xprof``). It runs only on a TPU (raises at start
otherwise, and prints the device it found) — through the chip tool.

    PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python \
        python scripts/profile_width.py \
        [--hidden 1024 --layers 12 --head-dim 128 --policy save_attention]

``--policy`` selects the rematerialization policy the step compiles under
(default: ``save_attention``, the r06 production-width candidate) — the
backward's recompute mix is policy-dependent, so attributions must name
the policy they were taken under.

(The pure-python protobuf flag is needed because the installed
tensorflow/xprof protobuf generations disagree; parsing is slow but the
trace itself is unaffected.)
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

PACKED_BATCH, PACKED_SEQ_LEN = 8, 1024


def build_step(hidden: int, layers: int, head_dim: int, policy: str = "save_attention"):
    import jax
    import jax.numpy as jnp

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

    data_dir = Path(tempfile.mkdtemp(prefix="esgpt_profile_"))
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
    packed = next(
        b
        for b in train_ds.packed_batches(PACKED_BATCH, seq_len=PACKED_SEQ_LEN, seed=1)
        if b.event_mask.shape[0] == PACKED_BATCH
    )
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
        gradient_checkpointing=policy,
        precision="bf16",
    )
    config.set_to_dataset(train_ds)
    config.max_seq_len = PACKED_SEQ_LEN

    model = build_model(config)
    oc = OptimizationConfig(
        init_lr=1e-3, batch_size=PACKED_BATCH, max_training_steps=10,
        lr_num_warmup_steps=1, lr_frac_warmup_steps=None,
    )
    tx, _ = build_optimizer(oc)
    params = model.init(jax.random.PRNGKey(0), packed)
    mesh = data_parallel_mesh(PACKED_BATCH)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    state = replicate(state, mesh)
    resident = shard_batch(packed, mesh)
    return make_train_step(model, tx), state, resident


def top_ops_from_trace(trace_dir: str, top_n: int = 30):
    """Parses the xplane and returns [(self_time_us, occurrences, op name)]."""
    from xprof.convert import raw_to_tool_data

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    for tool in ("hlo_stats", "hlo_op_stats", "op_profile"):
        try:
            data, _ = raw_to_tool_data.xspace_to_tool_data(paths, tool, {})
        except Exception:
            continue
        if tool in ("hlo_stats", "hlo_op_stats"):
            rows = json.loads(data) if isinstance(data, (str, bytes)) else data
            return tool, rows
        return tool, data
    raise RuntimeError("no usable xprof tool produced data")


def summarize_categories(rows, top=25):
    """hlo_stats table ({cols, rows} gviz-style) -> [(category, self_us)].

    The per-category rollup that produced BASELINE.md (pre-PR-22 record, git history)'s head-stack tables
    (dense matmuls vs attention custom-calls vs scatter/gather vs loop
    fusions); re-run this under each remat policy (``--policy``) to see what
    the backward actually recomputes.
    """
    cols = [c["label"] if isinstance(c, dict) else c for c in rows["cols"]]
    i_cat = cols.index("HLO op category")
    i_self = cols.index("Total self time (us)")
    agg: dict = {}
    for r in rows["rows"]:
        c = r["c"] if isinstance(r, dict) else r
        vals = [x.get("v") if isinstance(x, dict) else x for x in c]
        agg[vals[i_cat]] = agg.get(vals[i_cat], 0.0) + float(vals[i_self] or 0)
    return sorted(agg.items(), key=lambda kv: -kv[1])[:top]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument(
        "--policy",
        default="save_attention",
        help="gradient_checkpointing policy to profile under "
        "(none|block|dots|dots_no_batch|save_attention)",
    )
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)

    import jax

    from eventstreamgpt_tpu.utils.benchmarking import drain, require_tpu, wait_for_quiet
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    require_tpu()  # raises unless a TPU with a published peak
    configure_compile_cache()
    step, state, resident = build_step(args.hidden, args.layers, args.head_dim, args.policy)
    rng = jax.random.PRNGKey(0)
    state, loss = step(state, resident, rng)  # compile
    drain(loss)
    echo, contended = wait_for_quiet()
    print(f"quiet gate: echo {echo:.2f} ms, contended={contended}", file=sys.stderr)

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="esgpt_trace_")
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        state, loss = step(state, resident, rng)
    drain(loss)
    jax.profiler.stop_trace()
    print(f"trace written to {trace_dir}", file=sys.stderr)

    tool, rows = top_ops_from_trace(trace_dir)
    print(f"parsed with tool={tool} (policy={args.policy})")
    if tool in ("hlo_stats", "hlo_op_stats") and isinstance(rows, dict):
        print("-- by HLO op category (device self us over traced steps) --")
        for k, v in summarize_categories(rows):
            print(f"  {v:10.0f}  {k}")
    print(json.dumps(rows)[:20000] if not isinstance(rows, list) else rows[:40])


if __name__ == "__main__":
    main()
