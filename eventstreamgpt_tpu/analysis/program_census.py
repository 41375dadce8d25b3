"""Tier C of graftcheck: the whole-fleet compiled-program census.

The stack emits dozens of distinct compiled programs — pretrain layouts
(dp/tp/scan/fsdp), the serving engine's decode + per-bucket prefill +
boundary pack (float, quantized-cache, and fused-sampling variants), the
online service's per-replica programs, and the width-ladder rungs.
Tier B gates a hand-picked canonical list at toy shapes; Tier C is the
**census**: every ``aot_programs`` provider registers its program factories
here (`register_aot_provider` — the hooks live in ``training/sharding.py``,
``serving/engine.py``, ``serving/service.py``, plus this module's own
generation and width-ladder providers), so a compiled program nobody
registered is itself a failure, and every registered program is AOT-lowered
and compiled on the 8-device virtual mesh and statically audited:

* **peak HBM** per program from XLA's buffer assignment
  (``analysis/memory_checks.py``), gated against the committed
  ``MEMORY.json``; the width-4096 replicated ladder rung is the negative
  control (it must FAIL the 16 GB/chip budget) and the fsdp8 rung the
  positive one (it must fit).
* **kind-resolved collective inventories** at BOTH toy and scaled shapes
  (width >= 2048): the scaled fsdp8 backward must show reduce-scatter —
  not just all-reduce — once folded AR+slice pairs are resolved
  (``parallel.collectives_audit.resolve_folded_reduce_scatters``); toy
  inventories re-gate against ``COLLECTIVES.json``, scaled ones against
  their ``MEMORY.json`` entry.
* **donation completeness**: every donated argument leaf actually aliased
  in the compiled output (an undonated-in-practice buffer double-buffers
  HBM even when GC005 passes at the AST level).
* **implicit resharding**: declared input shardings diffed against the
  compiled executable's expected layouts.

Module-level code is stdlib-only (like ``lint``); jax and the model stack
load lazily inside the factories, so importing the registry costs nothing.

Regenerate budgets with ``python scripts/graftcheck.py --write-memory``
(byte-reproducible; CI diffs the regenerated file against the committed
one). See docs/analysis.md "Tier C".
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Callable

__all__ = [
    "CensusProgram",
    "register_aot_provider",
    "registered_providers",
    "census_programs",
    "aot_surface",
    "collect_census",
    "run_census",
    "write_memory_budgets",
    "MEMORY_PATH",
    "HBM_BUDGET_GB",
    "SCALED_WIDTHS",
    "SCALED_LAYERS",
]

REPO_ROOT = Path(__file__).resolve().parents[2]
MEMORY_PATH = REPO_ROOT / "MEMORY.json"
COLLECTIVES_PATH = REPO_ROOT / "COLLECTIVES.json"

# The documented serving/training chip budget (docs/scaling.md).
HBM_BUDGET_GB = 16.0
# Scaled-shape policy: width >= 2048 is where HBM-fit reasoning becomes
# real (the replicated 4096 train state cannot fit a 16 GB chip) and where
# the FSDP gradient sweep's reduce-scatter must be visible in the
# kind-resolved inventory. 12 layers, as the benchmark's ci_w1024.
SCALED_WIDTHS = (2048, 4096)
SCALED_LAYERS = 12


@dataclasses.dataclass
class CensusProgram:
    """One registered compiled program and its Tier-C gate metadata.

    ``fn``/``args`` are what ``jax.jit(...).lower(*args)`` needs — args may
    be concrete arrays (toy shapes) or ``jax.ShapeDtypeStruct`` trees with
    shardings (scaled shapes, where materializing the state would not fit
    host RAM, let alone a chip). ``budget_key`` names the raw-inventory
    COLLECTIVES.json layout this program re-gates against (None: no
    committed toy budget). ``scaled`` programs commit their kind-resolved
    inventory to MEMORY.json instead. ``hbm_expect`` is "fit"/"oom"/None
    against `HBM_BUDGET_GB`; ``require_kinds`` must appear in the resolved
    inventory with count >= 1.
    """

    label: str
    fn: Any
    args: tuple
    donate_argnums: tuple = ()
    budget_key: str | None = None
    scaled: bool = False
    hbm_expect: str | None = None
    require_kinds: tuple = ()


_PROVIDERS: dict[str, Callable[[], dict[str, CensusProgram]]] = {}


def register_aot_provider(
    name: str, factory: Callable[[], dict[str, CensusProgram]]
) -> None:
    """Registers a subsystem's program factory under ``name``.

    The factory is lazy: it builds the subsystem's canonical instances and
    returns ``{label: CensusProgram}`` only when the census actually runs.
    Re-registering a name replaces the factory (idempotent module reload).
    """
    _PROVIDERS[name] = factory


def _import_provider_hooks() -> None:
    """Imports the modules whose bottom-of-module hooks register providers.

    Keeping the hook in each provider module (rather than a central list
    here) is what makes an unregistered provider loud: a new subsystem that
    grows an ``aot_programs`` without a hook fails the census-completeness
    test, not a code review.
    """
    from ..serving import engine as _engine  # noqa: F401
    from ..serving import fleet as _fleet  # noqa: F401
    from ..serving import service as _service  # noqa: F401
    from ..training import sharding as _sharding  # noqa: F401


def registered_providers() -> dict[str, Callable[[], dict[str, CensusProgram]]]:
    _import_provider_hooks()
    return dict(_PROVIDERS)


def census_programs() -> dict[str, CensusProgram]:
    """Builds every registered provider's programs (no lowering yet)."""
    programs: dict[str, CensusProgram] = {}
    for provider, factory in sorted(registered_providers().items()):
        for label, prog in factory().items():
            if label in programs:
                raise ValueError(
                    f"census label collision: provider {provider!r} re-registers "
                    f"{label!r}"
                )
            programs[label] = prog
    return programs


# --------------------------------------------------- built-in providers
def _generation_programs() -> dict[str, CensusProgram]:
    """The single-dispatch cached generation program (Tier B's
    ``generation:ci``): no donation (params are reused across calls), no
    committed collective budget (single-program, collective-free)."""
    from . import program_checks as pc

    fn, args = pc.canonical_generation_program()
    return {"generation:ci": CensusProgram("generation:ci", fn, args)}


def _scaled_model_and_batch(width: int, layers: int, batch_size: int = 8, seq_len: int = 8):
    """The width-ladder rung geometry at census scale: proper proportions
    (head_dim 128, 4x MLP, scan-over-layers, the production remat policy)
    on the toy vocabulary — parameter bytes, not dataset width, are what
    the HBM analysis measures."""
    import numpy as np

    from ..data.types import EventStreamBatch
    from ..models.ci_model import CIPPTForGenerativeSequenceModeling
    from ..models.config import StructuredTransformerConfig

    vocab = 32
    cfg = StructuredTransformerConfig(
        vocab_sizes_by_measurement={"event_type": vocab // 2, "lab": vocab // 2 - 1},
        vocab_offsets_by_measurement={"event_type": 1, "lab": vocab // 2 + 1},
        measurements_idxmap={"event_type": 1, "lab": 2},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["lab"],
            "multivariate_regression": ["lab"],
        },
        max_seq_len=seq_len,
        hidden_size=width,
        head_dim=128,
        num_attention_heads=width // 128,
        num_hidden_layers=layers,
        intermediate_size=4 * width,
        TTE_generation_layer_type="log_normal_mixture",
        TTE_lognormal_generation_num_components=2,
        scan_layers=True,
        gradient_checkpointing="save_attention",
        attention_dropout=0.0,
    )
    rng = np.random.default_rng(0)
    n_data = 4
    em = np.ones((batch_size, seq_len), dtype=bool)
    dm = np.full((batch_size, seq_len, n_data), 2, dtype=np.int64)
    dm[:, :, 0] = 1
    di = np.where(
        dm == 1,
        rng.integers(1, vocab // 2 + 1, size=dm.shape),
        rng.integers(vocab // 2 + 1, vocab, size=dm.shape),
    )
    batch = EventStreamBatch(
        event_mask=em,
        time_delta=rng.uniform(0.5, 10.0, size=em.shape).astype(np.float32),
        static_indices=rng.integers(1, vocab, size=(batch_size, 2)),
        static_measurement_indices=np.ones((batch_size, 2), dtype=np.int64),
        dynamic_indices=di,
        dynamic_measurement_indices=dm,
        dynamic_values=rng.normal(size=dm.shape).astype(np.float32),
        dynamic_values_mask=(dm == 2) & (rng.random(dm.shape) < 0.5),
    )
    return CIPPTForGenerativeSequenceModeling(cfg), batch


def _scaled_train_program(width: int, layers: int, layout: str):
    """``(fn, abstract args)`` for a scaled train step — abstract because a
    2.4B-parameter replicated tree must never materialize on this host; the
    compile (and every gate) only needs shapes + declared shardings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models.config import OptimizationConfig
    from ..training import TrainState, build_optimizer, make_train_step
    from ..training.sharding import (
        batch_partition_axes,
        make_mesh,
        make_state_shardings,
    )

    mesh = make_mesh(1, 1, n_fsdp=8) if layout == "fsdp8" else make_mesh(8, 1)
    model, batch = _scaled_model_and_batch(width, layers)
    oc = OptimizationConfig(
        init_lr=1e-3,
        batch_size=8,
        max_training_steps=10,
        lr_num_warmup_steps=1,
        lr_frac_warmup_steps=None,
    )
    tx, _ = build_optimizer(oc)

    def init_fn(key):
        p = model.init(key, jax.tree_util.tree_map(jnp.asarray, batch))
        return TrainState(step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p))

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    if layout == "fsdp8":
        shardings = make_state_shardings(shapes, mesh)
    else:
        shardings = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), shapes)
    state_sds = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), shapes, shardings
    )
    axes = batch_partition_axes(mesh)
    dim0 = axes if len(axes) > 1 else axes[0]

    def batch_sds(x):
        x = np.asarray(x)
        sharding = NamedSharding(mesh, P(dim0, *([None] * (x.ndim - 1))))
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    args = (
        state_sds,
        jax.tree_util.tree_map(batch_sds, batch),
        jax.ShapeDtypeStruct((2,), np.uint32),
    )
    # The fsdp rungs pin the output state to the declared layout (see
    # make_train_step) — the donation audit requires in/out layouts to match.
    pin = shardings if layout == "fsdp8" else None
    return make_train_step(model, tx, out_state_shardings=pin), args


def _ladder_programs() -> dict[str, CensusProgram]:
    """The width-ladder rungs as census programs: scaled shapes where the
    HBM-fit verdicts and the reduce-scatter visibility are real, not toy
    artifacts. The replicated width-4096 rung is the committed negative
    control for the 16 GB budget gate."""
    out: dict[str, CensusProgram] = {}
    specs = [
        # (label, width, layout, hbm_expect, require_kinds)
        ("ladder:fsdp8@w2048", 2048, "fsdp8", "fit", ("reduce-scatter",)),
        ("ladder:fsdp8@w4096", 4096, "fsdp8", "fit", ("reduce-scatter",)),
        ("ladder:replicated_dp8@w4096", 4096, "replicated", "oom", ()),
    ]
    for label, width, layout, expect, kinds in specs:
        fn, args = _scaled_train_program(width, SCALED_LAYERS, layout)
        out[label] = CensusProgram(
            label,
            fn,
            args,
            donate_argnums=(0,),
            scaled=True,
            hbm_expect=expect,
            require_kinds=kinds,
        )
    return out


register_aot_provider("generation", _generation_programs)
register_aot_provider("ladder", _ladder_programs)


# --------------------------------------------------------- the census run
def aot_surface() -> dict[str, set[str]]:
    """Every program label the canonical ``aot_programs`` surfaces expose.

    Enumerated independently of the registry (straight from the engine /
    service / training canonical constructions), so the completeness test
    can assert census ∪ Tier B covers it with no self-reference.
    """
    from . import program_checks as pc

    surface: dict[str, set[str]] = {
        "training": {
            "pretrain:dp8",
            "pretrain:dp4_tp2",
            "pretrain:dp8_health",
            "pretrain:na_dp8",
            "pretrain:na_pallas_dp8",
            "pretrain:scan_dp8",
            "pretrain:fsdp8",
            "finetune:dp8",
            "finetune:dp8_health",
        },
        "generation": {"generation:ci"},
        "engine": {f"engine:{k}" for k in pc.canonical_engine_programs(8)}
        | {f"engine_nohealth:{k}" for k in pc.canonical_nohealth_engine_programs(8)}
        | {f"engine_kvq:{k}" for k in pc.canonical_kvq_engine_programs(8)}
        | {f"engine_sampling:{k}" for k in pc.canonical_sampling_engine_program()}
        | {f"engine_spec:{k}" for k in pc.canonical_spec_engine_programs(8)}
        | {f"engine_spec_na:{k}" for k in pc.canonical_spec_engine_na_programs()}
        | {f"engine_paged:{k}" for k in pc.canonical_paged_engine_programs(8)}
        | {
            f"engine_sampling_shard:{k}"
            for k in pc.canonical_sharded_sampling_engine_programs(8)
        },
        "service": {f"service:{k}" for k in pc.canonical_service_programs(8)},
        "fleet": {f"engine_tp:{k}" for k in pc.canonical_tp_engine_programs(4, 2)}
        | {f"engine_swap:{k}" for k in pc.canonical_swap_engine_programs()}
        | {f"engine_composed:{k}" for k in pc.canonical_composed_engine_programs(4, 2)},
        "ladder": {
            "ladder:fsdp8@w2048",
            "ladder:fsdp8@w4096",
            "ladder:replicated_dp8@w4096",
        },
    }
    return surface


def collect_census(
    programs: dict[str, CensusProgram] | None = None, verbose: bool = True
) -> tuple[dict[str, dict], list[str]]:
    """Lowers + compiles every registered program and extracts the facts.

    ``programs`` lets callers that already built the registry (for budget
    metadata) pass it in — the factories construct real models, engines,
    and the 2-replica service, so rebuilding the fleet is the expensive
    half of census setup.

    Returns ``(per-label report, budget-independent violations)``: the
    report carries each program's memory breakdown, donation audit,
    resharding audit, and collective inventories (raw always, kind-resolved
    for scaled programs); the violations are the gates that need no
    committed budget — donation completeness, implicit resharding,
    HBM-fit expectations, required collective kinds, and (for the scaled
    programs Tier B never sees) f64/host-transfer cleanliness.
    """
    from ..parallel import collective_inventory
    from . import program_checks as pc
    from .memory_checks import (
        check_hbm_fit,
        donation_report,
        memory_report,
        resharding_report,
    )

    def log(msg: str) -> None:
        if verbose:
            print(f"graftcheck[C]: {msg}", flush=True)

    if programs is None:
        programs = census_programs()
    report: dict[str, dict] = {}
    problems: list[str] = []
    for label, prog in programs.items():
        log(f"lowering + compiling {label}")
        lowered = prog.fn.lower(*prog.args)
        if prog.scaled:
            # Tier B's text gates only see toy shapes; the scaled programs
            # get the same f64/host-transfer cleanliness here.
            text = lowered.as_text()
            problems += pc.check_no_f64(text, label)
            problems += pc.check_no_host_transfers(text, label)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        entry: dict[str, Any] = {"memory": memory_report(compiled)}

        if prog.donate_argnums:
            d = donation_report(compiled, prog.args, prog.donate_argnums, hlo_text=hlo)
            entry["donation"] = {
                "n_donated": d["n_donated"],
                "n_aliased": d["n_aliased"],
                "n_pruned": d["n_pruned"],
            }
            for u in d["undonated"]:
                problems.append(
                    f"{label}: donated-but-unaliased buffer ({u}) — the donation "
                    "is a no-op in the compiled program and the buffer "
                    "double-buffers HBM"
                )

        reshard = resharding_report(compiled, prog.args)
        entry["resharding_ok"] = not reshard
        problems += [f"{label}: {p}" for p in reshard]

        entry["collectives"] = collective_inventory(hlo)
        if prog.scaled:
            entry["collectives_resolved"] = collective_inventory(hlo, resolve_folded=True)
            for kind in prog.require_kinds:
                if entry["collectives_resolved"].get(kind, {}).get("count", 0) == 0:
                    problems.append(
                        f"{label}: kind-resolved inventory shows no {kind} — the "
                        "scaled-shape sweep this layout exists for is not being "
                        "scattered"
                    )
        if prog.hbm_expect is not None:
            problems += check_hbm_fit(
                entry["memory"], HBM_BUDGET_GB, prog.hbm_expect == "fit", label
            )
        mem = entry["memory"]
        log(
            f"{label}: peak {mem['peak_hbm_bytes'] / 1e9:.3f} GB/device, "
            f"{entry['collectives']['total_count']} collectives"
        )
        report[label] = entry
    return report, problems


def _memory_budget_entry(label: str, prog_report: dict, prog: CensusProgram) -> dict:
    entry = {"peak_hbm_bytes": prog_report["memory"]["peak_hbm_bytes"]}
    entry.update(
        {k: v for k, v in prog_report["memory"].items() if k != "peak_hbm_bytes"}
    )
    if "donation" in prog_report:
        entry["n_donated"] = prog_report["donation"]["n_donated"]
        entry["n_aliased"] = prog_report["donation"]["n_aliased"]
        # jit-pruned donated leaves hold no buffer (nothing to alias, nothing
        # double-buffered); committed only when present so the clean contract
        # n_donated == n_aliased + n_pruned stays checkable from the file.
        if prog_report["donation"]["n_pruned"]:
            entry["n_pruned"] = prog_report["donation"]["n_pruned"]
    if prog.scaled:
        entry["collectives"] = prog_report["collectives_resolved"]
        entry["hbm_expect"] = prog.hbm_expect
    return entry


def run_census(
    memory_path: Path | None = None,
    collectives_path: Path | None = None,
    rel_tol: float = 0.10,
    verbose: bool = True,
    regen_path: Path | None = None,
) -> tuple[list[str], dict]:
    """Runs every Tier-C gate; returns ``(violations, per-program report)``.

    On top of `collect_census`'s budget-free gates: every program's peak
    HBM against its committed ``MEMORY.json`` entry (a registered program
    with no entry is a violation — run ``--write-memory``), toy-shape raw
    inventories re-gated against ``COLLECTIVES.json``, and scaled-shape
    kind-resolved inventories against their ``MEMORY.json`` entry.

    ``regen_path`` additionally writes the regenerated budget file from the
    SAME census pass — what CI diffs against the committed ``MEMORY.json``
    without paying a second whole-fleet compile.
    """
    from ..parallel import compare_inventory
    from .memory_checks import compare_memory

    memory_path = memory_path or MEMORY_PATH
    collectives_path = collectives_path or COLLECTIVES_PATH
    budgets = (
        json.loads(Path(memory_path).read_text())["programs"]
        if Path(memory_path).exists()
        else {}
    )
    coll_budgets = json.loads(Path(collectives_path).read_text())["layouts"]

    programs = census_programs()
    report, problems = collect_census(programs, verbose=verbose)
    if regen_path is not None:
        _write_budget_file(programs, report, Path(regen_path))
    for label, entry in report.items():
        prog = programs[label]
        if label not in budgets:
            problems.append(
                f"{label}: registered program has no committed MEMORY.json entry — "
                "regenerate with `python scripts/graftcheck.py --write-memory`"
            )
            continue
        problems += [
            f"{label}: {p}" for p in compare_memory(entry["memory"], budgets[label], rel_tol)
        ]
        if prog.budget_key is not None:
            if prog.budget_key not in coll_budgets:
                # Same graceful path as a missing MEMORY.json entry: a typo'd
                # or not-yet-committed key must be a reported violation, not a
                # KeyError traceback after minutes of fleet compilation.
                problems.append(
                    f"{label}: budget key {prog.budget_key!r} has no entry in "
                    "COLLECTIVES.json — regenerate with dryrun_multichip(8) or "
                    "fix the registered key"
                )
            else:
                problems += [
                    f"{label}: {p}"
                    for p in compare_inventory(
                        entry["collectives"], coll_budgets[prog.budget_key]
                    )
                ]
        if prog.scaled and "collectives" in budgets[label]:
            # The scaled rungs pin all-reduce tighter than the default bound:
            # a PARTIAL reduce-scatter→all-reduce substitution leaves the rs
            # kind present (the presence rule passes) and at these budgets
            # +25% of the all-reduce bytes could hide most of a re-routed
            # sweep; +10% cannot.
            problems += [
                f"{label} (resolved): {p}"
                for p in compare_inventory(
                    entry["collectives_resolved"],
                    budgets[label]["collectives"],
                    per_kind_tol={"all-reduce": (0.10, 64 * 1024)},
                )
            ]
    return problems, report


def _write_budget_file(
    programs: dict[str, CensusProgram], report: dict[str, dict], path: Path
) -> None:
    out = {
        "note": (
            "graftcheck Tier C memory budgets: per-compiled-program peak HBM "
            "(bytes/device, from XLA buffer assignment on the 8-device virtual "
            "mesh), donation-aliasing counts, and kind-resolved collective "
            "inventories for the scaled-shape ladder rungs. Regenerate with "
            "`python scripts/graftcheck.py --write-memory`; see docs/analysis.md."
        ),
        "n_devices": 8,
        "hbm_budget_gb": HBM_BUDGET_GB,
        "programs": {
            label: _memory_budget_entry(label, report[label], programs[label])
            for label in sorted(report)
        },
    }
    Path(path).write_text(json.dumps(out, indent=1) + "\n")


def write_memory_budgets(
    memory_path: Path | None = None, verbose: bool = True
) -> tuple[Path, list[str]]:
    """Regenerates ``MEMORY.json`` from a fresh census run.

    Byte-reproducible on a fixed jax/jaxlib (sorted labels, stable key
    order, indent 1, trailing newline) — CI regenerates and diffs against
    the committed file, the same discipline COLLECTIVES.json gets from the
    multichip dry run. Budget-free violations (donation, resharding,
    HBM-fit expectations) are returned, not suppressed: a budget refresh
    must never paper over a broken donation.
    """
    memory_path = Path(memory_path or MEMORY_PATH)
    programs = census_programs()
    report, problems = collect_census(programs, verbose=verbose)
    _write_budget_file(programs, report, memory_path)
    return memory_path, problems
