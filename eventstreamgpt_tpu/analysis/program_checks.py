"""Tier B of graftcheck: jaxpr/HLO invariant gates over canonical programs.

PR 1 proved "no table-sized collectives" and "device-resident hot loop" via
runtime tests; these properties are static facts of the lowered program, so
this module gates them on every PR with no hardware and no epoch runs. It
AOT-lowers the canonical step programs — the pretrain train step on the
``dp8`` and ``dp4_tp2`` virtual-mesh layouts (compiled under the r06
production-width remat policy, ``save_attention``), the NestedAttention
flagship step (fused dep-graph attention + narrow head projections), the
fine-tuning train step, and the single-dispatch generation program — and
statically asserts:

* **no f64** element types anywhere in the module (TPUs emulate f64; one
  stray weak-typed ``np.float64`` constant doubles a table),
* **no host transfers** in the step (outfeed/infeed/send/recv and
  host-callback custom-calls — a ``jax.debug.print`` or ``pure_callback``
  smuggled into the hot loop),
* **collective payload bytes within tolerance** of the committed
  ``COLLECTIVES.json`` budget (``parallel.collectives_audit
  .compare_inventory``) — an accidental full-table all-gather is a byte
  blowup here long before it is a pod-hour.

The f64 / host-transfer checks run on the *unoptimized* lowering (fast — no
XLA compile); the collective budget needs the optimized HLO, so those
layouts compile (CPU, tiny shapes, ~a minute each). Requires the 8-device
virtual CPU mesh (``__graft_entry__._provision_cpu_devices(8)`` before jax
backend init — the graftcheck CLI and tests/conftest.py both do this).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

__all__ = [
    "REPO_ROOT",
    "canonical_pretrain_step",
    "canonical_finetune_step",
    "canonical_generation_program",
    "canonical_engine_programs",
    "canonical_kvq_engine_programs",
    "canonical_nohealth_engine_programs",
    "canonical_paged_engine_programs",
    "canonical_sampling_engine_program",
    "canonical_spec_engine_programs",
    "canonical_spec_engine_na_programs",
    "canonical_service_programs",
    "canonical_tp_engine_programs",
    "canonical_swap_engine_programs",
    "check_no_f64",
    "check_no_host_transfers",
    "check_collective_budget",
    "run_program_checks",
]

REPO_ROOT = Path(__file__).resolve().parents[2]

# f64 element types in HLO ("f64[...]") or StableHLO ("tensor<2x3xf64>",
# "tensor<f64>") syntax. Substring-only matching would false-positive on
# hex-ish identifiers, so anchor to the type syntax.
_F64_RE = re.compile(r"f64\[|x\s*f64>|<f64>|tensor<f64")

_HOST_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(outfeed|infeed|send|send-done|recv|recv-done)\("
)
_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target\s*=\s*"([^"]+)"')
_STABLEHLO_CUSTOM_RE = re.compile(r"stablehlo\.custom_call\s+@(\S+?)[(\s]")
_HOST_CALLBACK_RE = re.compile(r"callback|host|outfeed|infeed|debug_print", re.IGNORECASE)


def _graft_entry():
    """Imports ``__graft_entry__`` (model/batch builders live beside it)."""
    if str(REPO_ROOT) not in sys.path:
        sys.path.insert(0, str(REPO_ROOT))
    import __graft_entry__

    return __graft_entry__


def _require_devices(n: int) -> None:
    import jax

    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"program checks need an {n}-device mesh but jax reports {have}; "
            "provision the virtual CPU platform before importing jax "
            "(__graft_entry__._provision_cpu_devices) — the graftcheck CLI and "
            "tests/conftest.py both do."
        )


# ----------------------------------------------------------- canonical steps
def canonical_pretrain_step(
    n_data: int,
    n_model: int,
    with_health: bool = False,
    na: bool = False,
    na_impl: str | None = None,
    scan: bool = False,
    n_fsdp: int = 1,
):
    """The production pretrain train step on a ``data×model`` mesh — the
    exact construction ``dryrun_multichip`` audits into ``COLLECTIVES.json``
    (same tiny shapes, so inventories are directly comparable).

    ``with_health`` builds the divergence-sentinel-instrumented variant,
    which is what ``train()`` jits by default since the reliability
    subsystem landed (sentinel_enabled defaults to true). ``na`` builds the
    NestedAttention flagship (fused dep-graph attention + narrow head
    projections — the r06 NA production defaults); ``na_impl`` pins the
    dep-graph attention implementation (``"pallas_interpret"`` builds the
    r09 Pallas-kernel program in interpreter mode, which lowers and
    compiles on the virtual CPU mesh — the TPU production program differs
    only in the kernel's Mosaic body). CI programs compile under
    ``gradient_checkpointing="save_attention"`` (the r06 production-width
    remat policy), matching the dry run.

    ``scan`` builds the r10 scan-over-layers variant (``scan_layers=True``:
    one pattern-period block body scanned over stacked params); ``n_fsdp``
    > 1 puts an ``fsdp`` axis on the mesh — parameters and Adam moments
    shard their largest dimension over it, the batch shards over
    ``(data, fsdp)`` jointly, and GSPMD's gather-on-use /
    reduce-scatter-on-grad schedule lands in the collective inventory
    (the ``fsdp8`` budget — the one layout whose bytes are all-gather +
    reduce-scatter dominated by design)."""
    import jax
    import jax.numpy as jnp

    from ..models.config import OptimizationConfig
    from ..training import TrainState, build_optimizer, make_train_step, shard_batch
    from ..training.sharding import make_mesh, make_state_shardings

    ge = _graft_entry()
    _require_devices(n_data * n_model * n_fsdp)
    mesh = make_mesh(n_data, n_model, n_fsdp=n_fsdp)
    overrides = {"scan_layers": True} if scan else {}
    if na:
        if na_impl:
            overrides["dep_graph_attention_impl"] = na_impl
        model, batch = ge._make_model_and_batch(
            batch_size=2 * n_data * n_fsdp, na=True, **overrides
        )
    else:
        model, batch = ge._make_model_and_batch(
            batch_size=2 * n_data * n_fsdp,
            gradient_checkpointing="save_attention",
            **overrides,
        )
    params = model.init(jax.random.PRNGKey(0), batch)
    oc = OptimizationConfig(
        init_lr=1e-3,
        batch_size=2 * n_data * n_fsdp,
        max_training_steps=10,
        lr_num_warmup_steps=1,
        lr_frac_warmup_steps=None,
    )
    tx, _ = build_optimizer(oc)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    shardings = make_state_shardings(state, mesh)
    state = jax.device_put(state, shardings)
    batch = shard_batch(batch, mesh)
    # Parameter-sharding layouts (tp/fsdp) pin the output state to the input
    # layout: without the pin GSPMD propagation reshards small replicated
    # leaves over `model`, silently dropping their donation (the Tier C
    # donation audit's dp4_tp2 finding) and forcing a reshard-per-dispatch.
    pin = shardings if (n_model > 1 or n_fsdp > 1) else None
    step = make_train_step(model, tx, with_health=with_health, out_state_shardings=pin)
    return step, (state, batch, jax.random.PRNGKey(0))


def canonical_finetune_step(n_data: int = 8, with_health: bool = False):
    """The fine-tuning (stream classification) train step, data-parallel.
    ``with_health``: the sentinel-instrumented production default (see
    `canonical_pretrain_step`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.config import OptimizationConfig, StructuredTransformerConfig
    from ..models.fine_tuning_model import ESTForStreamClassification
    from ..training import TrainState, build_optimizer, make_train_step, shard_batch
    from ..training.sharding import make_mesh, shard_state

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    base_model, batch = ge._make_model_and_batch(batch_size=2 * n_data)
    config = StructuredTransformerConfig.from_dict(
        {
            **base_model.config.to_dict(),
            "finetuning_task": "label",
            "id2label": {0: False, 1: True},
            "num_labels": 2,
            "problem_type": "single_label_classification",
            "task_specific_params": {"pooling_method": "last"},
        }
    )
    model = ESTForStreamClassification(config)
    labels = np.arange(2 * n_data, dtype=np.int64) % 2
    batch = batch.replace(stream_labels={"label": jnp.asarray(labels)})
    params = model.init(jax.random.PRNGKey(0), batch)
    oc = OptimizationConfig(
        init_lr=1e-3,
        batch_size=2 * n_data,
        max_training_steps=10,
        lr_num_warmup_steps=1,
        lr_frac_warmup_steps=None,
    )
    tx, _ = build_optimizer(oc)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    state = shard_state(state, mesh)
    batch = shard_batch(batch, mesh)
    step = make_train_step(model, tx, with_health=with_health)
    return step, (state, batch, jax.random.PRNGKey(0))


def canonical_generation_program(max_new_events: int = 4):
    """The single-dispatch cached generation program (``generate_program``)."""
    import jax

    from ..generation.generation_utils import _build_ci_steps

    ge = _graft_entry()
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    steps = _build_ci_steps(
        model, model.config, B=2, input_len=8, max_new_events=max_new_events
    )
    return steps["generate_program"], (params, batch, jax.random.PRNGKey(0))


def canonical_engine_programs(n_data: int = 8) -> dict:
    """The serving engine's prefill + decode-slot programs, slots sharded
    data-parallel over the virtual mesh (``serving/engine.py``).

    The decode-slot program is the serving hot loop: it must stay free of
    host transfers (per-row stopping is judged ON DEVICE — a smuggled
    callback would resurrect the per-event host sync the engine exists to
    remove) and within the committed ``engine_dp8`` collective budget
    (slot-sharded decode with replicated params is collective-free by
    construction; the budget gate keeps it that way). Returns the engine's
    ``aot_programs()`` dict: label -> (jitted fn, example args).
    """
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_kvq_engine_programs(n_data: int = 8) -> dict:
    """The r09 quantized-decode engine programs on the dp8 mesh: int8 KV
    caches — quantize-on-write at the per-row cursor, dequantize-on-read in
    the attention contraction, quantize-on-admission in prefill's admit
    scatter — through the same f64-free / host-transfer-free /
    collective-budget gates as the float engine. The ``engine_kvq_dp8``
    budget pins the contract that quantization adds (near-)zero
    communication: scales live beside the planes and every new op is
    slot-local. Sampling rides the fused tail on its mesh-auto impl (XLA
    on multi-device meshes — the kernel grid would otherwise all-gather
    the slot-sharded logits plane; see `GenerationEngine`); the Pallas
    sampling kernel itself is gated by
    `canonical_sampling_engine_program`."""
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        kv_cache_dtype="int8",
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_paged_engine_programs(n_data: int = 8) -> dict:
    """The r16 paged copy-on-write engine programs on the dp8 mesh: the
    block-pool decode (attention reads through per-slot block tables, one
    gather per layer), the paged prefill (block-scatter admit), and the
    fork prefill (ONE batch-1 forward admitting a whole CoW branch group).

    The collective contract: the pool is replicated over the mesh (its
    leading dim is num_blocks, not n_slots), so decode's pool updates
    all-gather from the slot-sharded chunk — an all-gather is already in
    the engine_dp8 kind set, so the block gather adds ZERO new collective
    kinds on dp8 (the ``engine_paged_dp8`` budget pins the inventory).
    ``block_size=4`` divides the canonical ``max_len=12`` (3 blocks/slot).
    """
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        paged_kv=True,
        block_size=4,
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_nohealth_engine_programs(n_data: int = 8) -> dict:
    """The engine with the decode health sentinel OFF — the uninstrumented
    counterpart of `canonical_engine_programs` (whose engine carries the
    production default ``health_sentinel=True``). Both register against
    the SAME committed ``engine_dp8`` / ``engine_prefill_dp8`` collective
    budgets: the sentinel must add **zero collectives and zero host
    transfers** (its detection is row-local elementwise work and its
    health row rides the existing packed boundary readback) — the serving
    mirror of PR 3's ``pretrain:dp8`` vs ``pretrain:dp8_health`` contract.
    A sentinel implementation that gathered across slots or smuggled a
    callback would break the byte-identical-budget gate here."""
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        health_sentinel=False,
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_sampling_engine_program() -> dict:
    """The fused-sampling engine programs, unsharded (one device, the
    single-replica serving topology the kernel targets): int8 cache +
    the Pallas sampling kernel in interpreter mode. The decode program is
    gated f64-free and host-transfer-free — the kernel's
    masked-fill/gumbel/argmax epilogue must not smuggle callbacks into the
    decode hot loop — and against a zero-collective budget (single device
    ⇒ any collective is a bug). Returns the engine's full ``aot_programs``
    dict (prefill + boundary pack included) so the Tier C census covers
    every program this topology can compile, not just the budget-gated
    decode."""
    import jax

    from ..serving import GenerationEngine

    ge = _graft_entry()
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=4,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        kv_cache_dtype="int8",
        sampling_impl="pallas_interpret",
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_sharded_sampling_engine_programs(n_data: int = 8) -> dict:
    """The r20 sharded fused-sampling engine: the Pallas sampling kernel on
    a MULTI-DEVICE data mesh, run under `shard_map` over the slot axis —
    each device sweeps its own ``(n_slots/dp, V)`` logits shard, so the
    grid never crosses the mesh axis. This retires the r09 mesh rule
    (auto → fused-XLA tail on any mesh): the committed
    ``engine_sampling_shard_dp8`` budget pins that the decode program
    carries NO slot-plane logits gather — its collective inventory must
    stay within the baseline ``engine_dp8`` kind set."""
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        kv_cache_dtype="int8",
        sampling_impl="pallas_interpret",
    )
    assert engine._shard_sampling, "dp8 + kernel tail must take the shard_map path"
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_composed_engine_programs(n_data: int = 4, n_model: int = 2) -> dict:
    """THE composed production configuration (r20 tentpole): speculative
    decoding × int8 KV cache × serve-time tensor parallelism behind one
    engine, with the dedicated-prefill split halves included. Every
    capacity multiplier at once: spec's ~K× events per target forward,
    int8's ~2× slots per chip, TP's width-past-one-chip — the
    configuration the composition matrix exists to license. The committed
    ``engine_composed_*_dp4_tp2`` budgets pin the contract that
    composition pays exactly the per-layer TP reduce pattern the plain TP
    engine already pays (zero NEW collective kinds vs ``engine_dp8``
    beyond the documented TP reduces), and the donation audit keeps the
    spec state's donation from being dropped by a layout reshard (the
    out_shardings pin, Tier C fix)."""
    import jax

    from ..serving import GenerationEngine, SpecConfig, truncated_draft
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data * n_model)
    mesh = make_mesh(n_data, n_model)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    dcfg, dparams = truncated_draft(model.config, params, 1)
    draft_model = type(model)(dcfg)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        kv_cache_dtype="int8",
        spec=SpecConfig(model=draft_model, params=dparams, config=dcfg, k=2),
    )
    assert engine.tensor_parallel and engine._kv_quantized
    return engine.aot_programs(bucket_len=8, group=2, include_prefill_stream=True)


def canonical_tp_engine_programs(n_data: int = 4, n_model: int = 2) -> dict:
    """The serve-time tensor-parallel engine programs on a
    ``data×model`` mesh (``serving/engine.py`` with a ``model`` axis): the
    params shard with the training TP rules (`training/sharding.TP_RULES`)
    and the decode/prefill programs carry the per-layer all-reduces GSPMD
    inserts — the serving fleet's widths-past-one-chip leg. The committed
    ``engine_tp_dp4_tp2`` / ``engine_tp_prefill_dp4_tp2`` budgets pin the
    contract that TP serving pays exactly the per-layer reduce pattern and
    nothing more: an accidental re-replication (or a slot-axis gather
    smuggled in by the sampling tail) is a byte blowup here long before it
    is a latency cliff on a pod."""
    import jax

    from ..serving import GenerationEngine
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data * n_model)
    mesh = make_mesh(n_data, n_model)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
    )
    assert engine.tensor_parallel
    # include_prefill_stream: the dedicated-prefill split halves are hot-path
    # programs on a prefill-tier fleet (the compute forward runs per
    # admission group, the donating admit scatter per handoff) — they get
    # the same gates as the fused prefill instead of escaping the census.
    return engine.aot_programs(bucket_len=8, group=2, include_prefill_stream=True)


def canonical_swap_engine_programs() -> dict:
    """The hot-swap engine's programs, unsharded (the zero-downtime weight
    swap leg of the serving fleet): the ordinary decode/prefill/boundary
    set plus ``swap_reshard`` — the shadow-load program that pins a
    host-loaded checkpoint to the live weights' layout so the flip is a
    pure pointer swap. The reshard is gated f64-free, host-transfer-free,
    and against a zero-collective budget (``engine_swap_reshard_1dev``):
    a collective or callback here would stall live decode for the whole
    swap window."""
    import jax

    from ..serving import GenerationEngine

    ge = _graft_entry()
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=4,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        hot_swap=True,
    )
    # The split prefill halves ride the swap engine's set too (unsharded:
    # zero-collective by construction, f64/host-transfer gated like the
    # rest — a callback smuggled into prefill_compute or admit would stall
    # the handoff exactly like one in decode).
    return engine.aot_programs(bucket_len=8, group=2, include_prefill_stream=True)


def canonical_spec_engine_programs(n_data: int = 8) -> dict:
    """The r13 speculative-decoding engine programs, slots sharded
    data-parallel over the virtual mesh: the draft-chunk program (K
    one-event draft forwards + proposal recording), the verify program (ONE
    K+1-event target forward on the vector-length cache branch + the
    accept/commit math), the fused target+draft prefill, and the widened
    boundary pack. The verify program is the serving hot loop's new center
    of mass: it must stay f64-free, host-transfer-free, and show **zero new
    collective kinds vs the baseline decode** (``engine_dp8``) — the
    fused-sampling mesh rule (auto → XLA tail on multi-device meshes, no
    all-gather of the slot-sharded logits plane) must keep holding inside
    the K-event verify forward, which the ``engine_spec_verify_dp8`` budget
    pins."""
    import jax

    from ..serving import GenerationEngine, SpecConfig, truncated_draft
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)
    dcfg, dparams = truncated_draft(model.config, params, 1)
    draft_model = type(model)(dcfg)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=2 * n_data,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        mesh=mesh,
        spec=SpecConfig(model=draft_model, params=dparams, config=dcfg, k=2),
    )
    return engine.aot_programs(bucket_len=8, group=2)


def canonical_spec_engine_na_programs() -> dict:
    """The NA speculative-decoding variant, unsharded: the draft chunk runs
    the full per-event dep-graph level walk on the truncated draft, the
    verify scores the whole proposed measurement chain teacher-forced in one
    fused pass (partial-content level embeddings + the per-layer history
    head) and finishes the correction event's walk. Gated f64-free and
    host-transfer-free with zero-collective budgets (single device)."""
    import jax

    from ..data.config import MeasurementConfig
    from ..serving import GenerationEngine, SpecConfig, truncated_draft

    ge = _graft_entry()
    # The canonical NA model is a training artifact; generation-side fill
    # paths additionally need per-measurement configs for the dep-graph
    # levels' measurements.
    model, batch = ge._make_model_and_batch(
        batch_size=2,
        seq_len=8,
        na=True,
        measurement_configs={
            "lab": MeasurementConfig(
                name="lab",
                temporality="dynamic",
                modality="multivariate_regression",
                values_column="v",
            )
        },
    )
    params = model.init(jax.random.PRNGKey(0), batch)
    dcfg, dparams = truncated_draft(model.config, params, 1)
    draft_model = type(model)(dcfg)
    engine = GenerationEngine(
        model,
        params,
        model.config,
        template=batch,
        n_slots=4,
        max_len=12,
        decode_chunk=2,
        min_bucket=8,
        spec=SpecConfig(model=draft_model, params=dparams, config=dcfg, k=2),
    )
    programs = engine.aot_programs(bucket_len=8, group=2)
    # The NA prefill/boundary are structurally the CI spec set's; the NA
    # census rows gate the two programs with new machinery (the fused
    # teacher-forced verify and the level-walking draft chunk).
    return {k: v for k, v in programs.items() if k in ("draft_chunk", "verify")}


def canonical_service_programs(n_data: int = 8) -> dict:
    """The online serving service's dispatch programs on the dp8 mesh
    (``serving/service.py``): a 2-replica service whose replicas shard
    their slots data-parallel over the virtual mesh.

    The service dispatches exactly the engine's compiled programs — the
    slot-decode chunk, bucketed prefill, and the boundary pack (the packed
    done-mask/accounting array whose host copy is the ONLY device->host
    traffic of the serving loop, started async at dispatch), plus replica
    1's differently-chunked decode program (``decode_r1`` — both replicas'
    hot loops get the f64/host-transfer gates; replica 0's additionally
    gates against the committed ``service_dp8`` collective budget). Pins
    the service hot path f64-free and host-transfer-free beyond that one
    designed fetch. Returns label -> (jitted fn, args).
    """
    import jax

    from ..serving import GenerationEngine, ServingService
    from ..training.sharding import make_mesh

    ge = _graft_entry()
    _require_devices(n_data)
    mesh = make_mesh(n_data, 1)
    model, batch = ge._make_model_and_batch(batch_size=2, seq_len=8)
    params = model.init(jax.random.PRNGKey(0), batch)

    def replica(chunk):
        return GenerationEngine(
            model,
            params,
            model.config,
            template=batch,
            n_slots=2 * n_data,
            max_len=12,
            decode_chunk=chunk,
            dispatch_depth=2,
            min_bucket=8,
            mesh=mesh,
        )

    # Replica 0 uses a distinct decode_chunk from the engine canonical so
    # the gated program is a genuinely different compile, not a cache hit.
    service = ServingService(
        [replica(4), replica(2)], prefill_budget_events=32
    )
    return service.aot_programs(bucket_len=8, group=2)


# ------------------------------------------------------------------- checks
def check_no_f64(program_text: str, label: str = "program") -> list[str]:
    """No f64 element types anywhere in the lowered/compiled module."""
    problems = []
    for i, line in enumerate(program_text.splitlines(), start=1):
        if _F64_RE.search(line):
            problems.append(f"{label}: f64 element type at module line {i}: {line.strip()[:160]}")
    return problems


def check_no_host_transfers(program_text: str, label: str = "program") -> list[str]:
    """No outfeed/infeed/send/recv and no host-callback custom-calls."""
    problems = []
    for i, line in enumerate(program_text.splitlines(), start=1):
        m = _HOST_OP_RE.search(line)
        if m:
            problems.append(
                f"{label}: host transfer op `{m.group(1)}` at module line {i}: "
                f"{line.strip()[:160]}"
            )
            continue
        for target_m in _CUSTOM_CALL_TARGET_RE.finditer(line):
            if _HOST_CALLBACK_RE.search(target_m.group(1)):
                problems.append(
                    f"{label}: host-callback custom-call `{target_m.group(1)}` "
                    f"at module line {i}"
                )
        sm = _STABLEHLO_CUSTOM_RE.search(line)
        if sm and _HOST_CALLBACK_RE.search(sm.group(1)):
            problems.append(
                f"{label}: host-callback custom-call `{sm.group(1)}` at module line {i}"
            )
    return problems


def check_collective_budget(
    inventory: dict, layout: str, budget_path: Path, rel_tol: float = 0.25
) -> list[str]:
    """Inventory vs the committed per-layout budget in ``COLLECTIVES.json``."""
    from ..parallel import compare_inventory

    budgets = json.loads(Path(budget_path).read_text())["layouts"]
    if layout not in budgets:
        return [f"{layout}: no budget entry in {budget_path}"]
    return [f"{layout}: {p}" for p in compare_inventory(inventory, budgets[layout], rel_tol)]


# ------------------------------------------------------------------- runner
def run_program_checks(
    budget_path: Path | None = None,
    rel_tol: float = 0.25,
    compile_collectives: bool = True,
    verbose: bool = True,
) -> list[str]:
    """Runs every Tier-B gate; returns violations (empty ⇒ all gates pass).

    Fast gates (f64-free, host-transfer-free) run on the unoptimized
    lowering of all canonical programs. With ``compile_collectives`` the
    ``dp8`` / ``dp4_tp2`` pretrain layouts also compile and gate their
    collective inventories against ``COLLECTIVES.json``.
    """
    from ..parallel import collective_inventory

    if budget_path is None:
        budget_path = REPO_ROOT / "COLLECTIVES.json"
    problems: list[str] = []

    def log(msg: str) -> None:
        if verbose:
            print(f"graftcheck[B]: {msg}", flush=True)

    layouts = {"dp8": (8, 1), "dp4_tp2": (4, 2)}
    programs: dict[str, tuple] = {}
    for name, (n_data, n_model) in layouts.items():
        programs[f"pretrain:{name}"] = canonical_pretrain_step(n_data, n_model)
    # The sentinel-instrumented variants are the PRODUCTION default (train()
    # jits with_health=True unless sentinel_enabled is false), so they must
    # pass the same static gates as the bare step — and the dp8 health
    # variant is additionally held to the bare dp8 collective budget below:
    # the divergence sentinel's contract is that it adds no collectives and
    # no host traffic to the step.
    programs["pretrain:dp8_health"] = canonical_pretrain_step(8, 1, with_health=True)
    # The NA flagship (r06): fused dep-graph attention + narrow head
    # projections are production defaults, so the lowered NA program is held
    # to the same f64-free/host-transfer-free gates and its own committed
    # collective budget — the fused walk must not smuggle host callbacks or
    # unbudgeted collectives into the step.
    programs["pretrain:na_dp8"] = canonical_pretrain_step(8, 1, na=True)
    # The r09 Pallas dep-graph kernel variant (interpreter mode on the CPU
    # mesh — same program structure as the TPU production compile modulo
    # the Mosaic kernel body): the hand kernel's custom_vjp must not
    # smuggle callbacks, f64, or unbudgeted collectives into the step.
    programs["pretrain:na_pallas_dp8"] = canonical_pretrain_step(
        8, 1, na=True, na_impl="pallas_interpret"
    )
    # The r10 scale-up programs: the scan-over-layers step on the pure-dp
    # mesh (stacked params, one scanned body — its budget differs from dp8
    # only in gradient-sweep *shape*, not magnitude) and the FSDP step
    # (scan + parameter/optimizer sharding over an 8-way fsdp axis — the
    # one layout whose budget is all-gather/reduce-scatter dominated; an
    # accidental re-replication or a per-step full-state gather is a byte
    # blowup here long before it is an HBM OOM at width 4096).
    programs["pretrain:scan_dp8"] = canonical_pretrain_step(8, 1, scan=True)
    programs["pretrain:fsdp8"] = canonical_pretrain_step(1, 1, scan=True, n_fsdp=8)
    programs["finetune:dp8"] = canonical_finetune_step(8)
    programs["finetune:dp8_health"] = canonical_finetune_step(8, with_health=True)
    programs["generation:ci"] = canonical_generation_program()
    # The serving engine's programs (slot-sharded over dp8): the decode-slot
    # program is the serving hot loop and additionally gates against its own
    # committed collective budget below.
    for label, (fn, args) in canonical_engine_programs(8).items():
        programs[f"engine:{label}"] = (fn, args)
    # The health-sentinel contract (ISSUE 15, the serving mirror of the
    # dp8-vs-dp8_health pretrain gate): the engine above carries the
    # production default health_sentinel=True; this uninstrumented variant
    # is held to the SAME committed budgets below — the sentinel must add
    # zero collectives and zero host transfers.
    for label, (fn, args) in canonical_nohealth_engine_programs(8).items():
        programs[f"engine_nohealth:{label}"] = (fn, args)
    # The r09 quantized-decode engine (int8 cache, fused-XLA sampling on
    # the sharded mesh): the decode hot loop with quantize-on-write /
    # dequantize-on-read gates against its own committed budget.
    for label, (fn, args) in canonical_kvq_engine_programs(8).items():
        programs[f"engine_kvq:{label}"] = (fn, args)
    # The r16 paged copy-on-write engine: block-pool decode, paged-admit
    # prefill, and the fork (CoW branch group) prefill, each against its
    # own committed budget — the decode budget pins "zero new collective
    # kinds vs engine_dp8" for the block gather.
    for label, (fn, args) in canonical_paged_engine_programs(8).items():
        programs[f"engine_paged:{label}"] = (fn, args)
    # The Pallas fused-sampling decode program (unsharded single-replica
    # topology): zero-collective by construction, and the kernel epilogue
    # must stay callback-free.
    for label, (fn, args) in canonical_sampling_engine_program().items():
        programs[f"engine_sampling:{label}"] = (fn, args)
    # The r13 speculative-decoding programs: the dp8 CI spec engine's
    # draft-chunk/verify/prefill/boundary set (the verify budget pins "zero
    # new collective kinds vs the baseline decode") and the NA variant's
    # draft-chunk/verify pair.
    for label, (fn, args) in canonical_spec_engine_programs(8).items():
        programs[f"engine_spec:{label}"] = (fn, args)
    for label, (fn, args) in canonical_spec_engine_na_programs().items():
        programs[f"engine_spec_na:{label}"] = (fn, args)
    # The online service's dispatch programs (2-replica service over dp8,
    # deeper decode chunk): the service hot path must stay host-transfer-
    # free beyond the one async boundary fetch — a callback smuggled into
    # decode, prefill, or the boundary pack would re-serialize the
    # double-buffered pipeline.
    for label, (fn, args) in canonical_service_programs(8).items():
        programs[f"service:{label}"] = (fn, args)
    # The serving fleet's r12 programs: the tensor-parallel engine on the
    # dp4×tp2 mesh (decode/prefill must carry exactly the per-layer TP
    # all-reduces, budgeted below) and the hot-swap engine with its
    # shadow-load reshard (collective- and callback-free by contract).
    for label, (fn, args) in canonical_tp_engine_programs(4, 2).items():
        programs[f"engine_tp:{label}"] = (fn, args)
    for label, (fn, args) in canonical_swap_engine_programs().items():
        programs[f"engine_swap:{label}"] = (fn, args)
    # The r20 composition-closure programs: the slot-sharded fused-sampling
    # engine on dp8 (the Pallas sampling grid runs on each slot shard — its
    # decode budget pins "no slot-plane gather", retiring the r09 mesh
    # fallback rule) and the composed spec × int8-cache × serve-time-TP
    # engine on dp4×tp2 with the prefill-stream split — ONE engine carrying
    # all three capacity multipliers; each program's budget pins "the
    # per-layer TP reduce pattern and nothing more" over the spec budgets.
    for label, (fn, args) in canonical_sharded_sampling_engine_programs(8).items():
        programs[f"engine_sampling_shard:{label}"] = (fn, args)
    for label, (fn, args) in canonical_composed_engine_programs(4, 2).items():
        programs[f"engine_composed:{label}"] = (fn, args)

    lowered = {}
    for label, (fn, args) in programs.items():
        log(f"lowering {label}")
        lowered[label] = fn.lower(*args)
        text = lowered[label].as_text()
        problems += check_no_f64(text, label)
        problems += check_no_host_transfers(text, label)

    if compile_collectives:
        # label -> COLLECTIVES.json budget key; the health variant reuses the
        # bare dp8 budget (the sentinel must live within it), the NA program
        # has its own committed budget (na_dp8).
        budget_keys = {f"pretrain:{name}": name for name in layouts}
        budget_keys["pretrain:dp8_health"] = "dp8"
        budget_keys["pretrain:scan_dp8"] = "scan_dp8"
        budget_keys["pretrain:fsdp8"] = "fsdp8"
        budget_keys["pretrain:na_dp8"] = "na_dp8"
        budget_keys["pretrain:na_pallas_dp8"] = "na_pallas_dp8"
        budget_keys["engine:decode"] = "engine_dp8"
        budget_keys["engine:prefill_b8"] = "engine_prefill_dp8"
        # Uninstrumented vs instrumented: byte-identical budgets, per the
        # health-sentinel zero-collective/zero-transfer contract.
        budget_keys["engine_nohealth:decode"] = "engine_dp8"
        budget_keys["engine_nohealth:prefill_b8"] = "engine_prefill_dp8"
        budget_keys["engine_kvq:decode"] = "engine_kvq_dp8"
        budget_keys["engine_kvq:prefill_b8"] = "engine_kvq_prefill_dp8"
        budget_keys["engine_paged:decode"] = "engine_paged_dp8"
        budget_keys["engine_paged:prefill_b8"] = "engine_paged_prefill_dp8"
        budget_keys["engine_paged:prefill_fork_fwd_b8"] = (
            "engine_paged_fork_prefill_dp8"
        )
        budget_keys["engine_paged:prefill_fork_admit"] = (
            "engine_paged_fork_admit_dp8"
        )
        budget_keys["engine_sampling:decode"] = "engine_sampling_1dev"
        budget_keys["engine_spec:draft_chunk"] = "engine_spec_draft_dp8"
        budget_keys["engine_spec:verify"] = "engine_spec_verify_dp8"
        budget_keys["engine_spec:prefill_b8"] = "engine_spec_prefill_dp8"
        budget_keys["engine_spec_na:draft_chunk"] = "engine_spec_na_draft_1dev"
        budget_keys["engine_spec_na:verify"] = "engine_spec_na_verify_1dev"
        budget_keys["service:decode"] = "service_dp8"
        budget_keys["service:prefill_b8"] = "service_prefill_dp8"
        budget_keys["service:boundary_pack"] = "service_boundary_dp8"
        budget_keys["service:decode_r1"] = "service_r1_dp8"
        budget_keys["engine_tp:decode"] = "engine_tp_dp4_tp2"
        budget_keys["engine_tp:prefill_b8"] = "engine_tp_prefill_dp4_tp2"
        budget_keys["engine_tp:prefill_compute_b8"] = "engine_tp_prefill_compute_dp4_tp2"
        budget_keys["engine_tp:admit"] = "engine_tp_admit_dp4_tp2"
        budget_keys["engine_swap:swap_reshard"] = "engine_swap_reshard_1dev"
        budget_keys["engine_sampling_shard:decode"] = "engine_sampling_shard_dp8"
        budget_keys["engine_composed:draft_chunk"] = "engine_composed_draft_dp4_tp2"
        budget_keys["engine_composed:verify"] = "engine_composed_verify_dp4_tp2"
        budget_keys["engine_composed:prefill_b8"] = "engine_composed_prefill_dp4_tp2"
        budget_keys["engine_composed:prefill_compute_b8"] = (
            "engine_composed_prefill_compute_dp4_tp2"
        )
        budget_keys["engine_composed:admit"] = "engine_composed_admit_dp4_tp2"
        for label, budget_key in budget_keys.items():
            log(f"compiling {label} for the collective budget gate")
            compiled = lowered[label].compile()
            text = compiled.as_text()
            problems += check_no_f64(text, f"{label} (optimized)")
            problems += check_no_host_transfers(text, f"{label} (optimized)")
            inv = collective_inventory(text)
            log(
                f"{label}: {inv['total_count']} collectives, "
                f"{inv['total_bytes']} payload bytes"
            )
            problems += check_collective_budget(inv, budget_key, budget_path, rel_tol)
    return problems
