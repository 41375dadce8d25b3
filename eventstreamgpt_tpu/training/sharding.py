"""Parameter sharding rules: data-parallel, tensor-parallel, and FSDP layouts.

The reference's distributed story is data-parallel only (Lightning DDP;
SURVEY §2.10). TPU-native scaling adds two parameter-sharding axes:

* a ``model`` mesh axis with Megatron-style tensor parallelism where it pays
  at event-stream scale: the unified vocabulary embedding table and
  classification head are the widest matrices in the model (vocab can be
  ~10k+; SURVEY §2.10 names the vocab-sharded ``ClassificationLayer`` as the
  first TP candidate) — both sharded over the vocab dimension; MLP blocks
  split column-then-row (``c_fc`` columns, ``c_proj`` rows) and attention
  splits by heads (``q/k/v`` columns, ``out_proj`` rows), so each pair needs
  a single all-reduce inserted by XLA GSPMD;

* an ``fsdp`` mesh axis (r10 scale-up round, per the pjit/TPUv4 playbook in
  PAPERS.md): EVERY parameter — and, via `shard_state`, its Adam moments —
  shards its largest eligible dimension over the axis, and the batch shards
  over ``(data, fsdp)`` jointly, so XLA GSPMD inserts the FSDP schedule
  automatically: all-gather each (layer's) weights on use in forward and
  backward, reduce-scatter the gradients, and update each optimizer shard
  locally. Per-chip parameter+optimizer HBM drops by the fsdp factor, which
  is what lets widths the replicated layout cannot fit (the bench width
  ladder's 4096 rung) compile at all. Stacked scan-over-layers parameters
  (``h_scan`` scopes, leading ``(L/p,)`` layer axis — models/transformer.py)
  shard a *within-layer* dimension, never the layer axis, so each scan step
  gathers exactly one layer's shards.

Rules are regex → ``PartitionSpec`` over flattened parameter paths for TP,
plus the generic largest-divisible-dim rule for FSDP; unmatched leaves
replicate. No explicit collectives anywhere — layouts are declared, XLA
inserts the psums/gathers over ICI/DCN.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TP_RULES: list[tuple[str, tuple]] = [
    (r".*/embed_table$", ("model", None)),
    (r".*/ClassificationLayer/kernel$", (None, "model")),
    (r".*/ClassificationLayer/bias$", ("model",)),
    (r".*/mlp/c_fc/kernel$", (None, "model")),
    (r".*/mlp/c_fc/bias$", ("model",)),
    (r".*/mlp/c_proj/kernel$", ("model", None)),
    (r".*/attention/[qkv]_proj/kernel$", (None, "model")),
    (r".*/attention/out_proj/kernel$", ("model", None)),
]

# Scanned layer stacks carry a leading (L/p,) layer axis that FSDP must not
# shard: the scan gathers one layer per step, so sharding the stack axis
# would turn every step's gather into a cross-layer collective.
_SCAN_SCOPE_RE = re.compile(r"(^|/)h_scan(/|$)")


def make_mesh(n_data: int, n_model: int = 1, n_fsdp: int = 1, devices=None) -> Mesh:
    """A ``(data[, fsdp], model)`` mesh over the first ``n_data·n_fsdp·n_model``
    devices. The historical 2-D ``(data, model)`` shape is preserved when
    ``n_fsdp == 1`` so existing layouts (and their committed collective
    budgets) are unchanged; ``fsdp`` slots between ``data`` and ``model`` —
    parameter all-gathers ride higher-bandwidth links than the gradient
    sweep, but the per-layer TP all-reduces keep the innermost axis."""
    if devices is None:
        devices = jax.devices()
    n = n_data * n_fsdp * n_model
    if len(devices) < n:
        raise ValueError(
            f"Need {n} devices for a {n_data}x{n_fsdp}x{n_model} mesh; have {len(devices)}"
        )
    if n < len(devices):
        print(
            f"make_mesh: a {n_data}x{n_fsdp}x{n_model} mesh uses the first {n} of "
            f"{len(devices)} devices."
        )
    if n_fsdp == 1:
        return Mesh(np.asarray(devices[:n]).reshape(n_data, n_model), ("data", "model"))
    return Mesh(
        np.asarray(devices[:n]).reshape(n_data, n_fsdp, n_model),
        ("data", "fsdp", "model"),
    )


def batch_partition_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes the batch dimension shards over: ``data`` plus (when
    present) ``fsdp`` — FSDP is data parallelism with sharded state, so the
    batch splits over both jointly."""
    return tuple(
        a for a in ("data", "fsdp") if a in mesh.axis_names and mesh.shape.get(a, 1) >= 1
    )


def _leaf_path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _fsdp_dim(path_str: str, shape: tuple, spec: list, n_fsdp: int) -> int | None:
    """The dimension FSDP shards: the largest dim divisible by ``n_fsdp``
    that no other axis already occupies, excluding a scanned stack's leading
    layer axis. ``None`` when no dimension qualifies (the leaf replicates
    over ``fsdp`` and is reported by `make_param_shardings`)."""
    stacked = bool(_SCAN_SCOPE_RE.search(path_str))
    candidates = [
        d
        for d in range(len(shape))
        if spec[d] is None
        and shape[d] % n_fsdp == 0
        and shape[d] > 0
        and not (stacked and d == 0)
    ]
    if not candidates:
        return None
    return max(candidates, key=lambda d: (shape[d], -d))


def make_param_shardings(
    params: Any,
    mesh: Mesh,
    strict: bool = False,
    max_replicated_frac: float = 0.5,
    verbose: bool = True,
) -> Any:
    """NamedSharding tree for ``params``: TP rules + FSDP + replicated fallback.

    ``verbose=False`` suppresses the replication warnings (strict-mode
    errors still raise) — the serve-time TP path (`serving/engine.py`)
    builds a layout per engine replica with ``strict=True, verbose=False``:
    a fleet would otherwise print the same small-leaf report once per
    replica, but a layout that replicates most parameter bytes still
    raises at engine construction instead of OOMing at admit.

    Tensor-parallel rules apply first (``model`` axis; dimensions that don't
    divide the axis evenly are left unsharded for that rule — GSPMD would
    handle uneven shards, but even splits keep layouts predictable), then
    the ``fsdp`` axis shards each leaf's largest remaining divisible
    dimension (`_fsdp_dim`). Leaves no rule touches replicate.

    Every replicated-despite-a-requested-axis leaf is reported by path with
    its shape, and ``strict=True`` upgrades the report to an error when more
    than ``max_replicated_frac`` of the parameter *bytes* stay replicated —
    a sharding layout that silently replicates the big tables is an HBM
    budget lie, not a warning.
    """
    has_model = "model" in mesh.axis_names and mesh.shape.get("model", 1) > 1
    has_fsdp = "fsdp" in mesh.axis_names and mesh.shape.get("fsdp", 1) > 1
    n_model = mesh.shape.get("model", 1)
    n_fsdp = mesh.shape.get("fsdp", 1)

    n_sharded = 0
    tp_skipped: list[str] = []
    replicated: list[str] = []
    replicated_bytes = 0
    total_bytes = 0

    def rule_for(path, leaf):
        nonlocal n_sharded, replicated_bytes, total_bytes
        nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        total_bytes += nbytes
        p_str = _leaf_path(path)
        spec = [None] * leaf.ndim
        stacked = bool(_SCAN_SCOPE_RE.search(p_str))
        if has_model:
            for pattern, tp_spec in TP_RULES:
                if re.match(pattern, p_str):
                    # Stacked scan params carry a leading layer axis on top of
                    # the rule's rank; the rule then applies to the trailing
                    # within-layer dims.
                    offset = 1 if stacked and len(tp_spec) + 1 == leaf.ndim else 0
                    if len(tp_spec) + offset == leaf.ndim and all(
                        axis is None or leaf.shape[d + offset] % n_model == 0
                        for d, axis in enumerate(tp_spec)
                    ):
                        for d, axis in enumerate(tp_spec):
                            spec[d + offset] = axis
                    else:
                        tp_skipped.append(f"{p_str} {tuple(leaf.shape)}")
                    break
        if has_fsdp:
            d = _fsdp_dim(p_str, tuple(leaf.shape), spec, n_fsdp)
            if d is not None:
                spec[d] = "fsdp"
        if any(axis is not None for axis in spec):
            n_sharded += 1
            # Normalized spec (no trailing Nones): jit's propagated output
            # shardings drop them, and a donated step whose inputs compare
            # structurally unequal to its outputs re-compiles once.
            while spec and spec[-1] is None:
                spec.pop()
            return NamedSharding(mesh, P(*spec))
        if has_model or has_fsdp:
            replicated.append(f"{p_str} {tuple(leaf.shape)}")
            replicated_bytes += nbytes
        # P() — not P(None, ..., None): the specs are semantically equal but
        # compare unequal, and a donated step whose input shardings differ
        # structurally from its propagated outputs re-compiles every other
        # dispatch (the CompileGuard suite pins this).
        return NamedSharding(mesh, P())

    out = jax.tree_util.tree_map_with_path(rule_for, params)
    if has_model and tp_skipped and verbose:
        # Partial failures matter most when the widest matrices (embedding /
        # classification head — the motivation for TP) are the ones skipped.
        print(
            f"WARNING: {len(tp_skipped)} TP-eligible parameter(s) have dims not divisible by "
            f"the model axis ({n_model}) and stay replicated for that rule: "
            + "; ".join(tp_skipped[:5])
            + ("; ..." if len(tp_skipped) > 5 else "")
        )
    if (has_model or has_fsdp) and replicated:
        frac = replicated_bytes / max(total_bytes, 1)
        axes = "/".join(
            n for n, on in (("model", has_model), ("fsdp", has_fsdp)) if on
        )
        msg = (
            f"{len(replicated)} parameter(s) ({replicated_bytes} bytes, "
            f"{100.0 * frac:.1f}% of parameter bytes) matched no {axes} sharding rule "
            "and stay replicated: " + "; ".join(replicated[:8])
            + ("; ..." if len(replicated) > 8 else "")
        )
        if strict and frac > max_replicated_frac:
            raise ValueError(
                f"strict sharding: {msg} — exceeds max_replicated_frac="
                f"{max_replicated_frac}. Check that hidden/vocab dims divide the "
                "requested shard counts."
            )
        if verbose:
            print(f"WARNING: {msg}")
    if (has_model or has_fsdp) and n_sharded == 0:
        msg = (
            "a parameter-sharding mesh axis was requested but NO parameter is "
            "sharded — all parameters are replicated. Check that hidden/vocab "
            "dims divide the shard counts."
        )
        if strict:
            raise ValueError(f"strict sharding: {msg}")
        if verbose:
            print(f"WARNING: {msg}")
    return out


def shard_params(params: Any, mesh: Mesh, strict: bool = False) -> Any:
    """Device-puts parameters per `make_param_shardings`."""
    return jax.device_put(params, make_param_shardings(params, mesh, strict=strict))


def make_state_shardings(state: Any, mesh: Mesh, strict: bool = False) -> Any:
    """Sharding tree for a `TrainState` (or its ``jax.eval_shape``): params
    per `make_param_shardings`, optimizer moments alongside their
    parameters, scalars replicated.

    Optimizer moments (adamw ``mu``/``nu``, possibly nested under MultiSteps)
    are param-structured subtrees; they are detected by tree structure and
    given the parameter shardings so each moment lives beside its parameter
    shard — under ``fsdp`` this is exactly the ZeRO-style sharded optimizer
    state (each chip updates only its own parameter shard).

    Accepting ``eval_shape`` output is what makes big-model init honest:
    ``jax.jit(init_fn, out_shardings=make_state_shardings(shapes, mesh))``
    materializes each parameter (and moment) directly into its shard —
    at the width-ladder 4096 rung the replicated tree this avoids would not
    fit one chip's HBM at all (`train_state_bytes`).
    """
    param_sh = make_param_shardings(state.params, mesh, strict=strict)
    param_treedef = jax.tree_util.tree_structure(state.params)
    replicated = NamedSharding(mesh, P())

    def is_param_tree(x) -> bool:
        try:
            return jax.tree_util.tree_structure(x) == param_treedef
        except Exception:
            return False

    def sh(node):
        if is_param_tree(node):
            return param_sh
        return jax.tree_util.tree_map(lambda _: replicated, node)

    return type(state)(
        step=replicated,
        params=param_sh,
        opt_state=jax.tree_util.tree_map(sh, state.opt_state, is_leaf=is_param_tree),
    )


def shard_state(state: Any, mesh: Mesh, strict: bool = False) -> Any:
    """Device-puts a materialized `TrainState` per `make_state_shardings`."""
    return jax.device_put(state, make_state_shardings(state, mesh, strict=strict))


def train_state_bytes(n_params: int, adam_moments: int = 2, grad_bytes: int = 4) -> int:
    """Analytic steady-state training footprint of ``n_params`` parameters:
    fp32 params + fp32 Adam ``mu``/``nu`` + one transient fp32 gradient tree
    (activations excluded — they scale with batch/remat policy, not width
    alone). The census's width ladder (`analysis/program_census.py`) holds
    this against the documented 16 GB/chip HBM budget to decide which rungs
    fit replicated and which are FSDP-only."""
    return int(n_params) * (4 * (1 + adam_moments) + grad_bytes)


# ------------------------------------------------- graftcheck Tier C census
def _census_programs():
    """The training subsystem's compiled-program fleet for the Tier C
    census: every canonical pretrain layout this module's meshes/shardings
    can produce, plus the fine-tune steps. The builders are Tier B's
    canonical constructions (same toy shapes, so the committed COLLECTIVES
    budgets re-apply); the donated argument is always the train state."""
    from ..analysis import program_checks as pc
    from ..analysis.program_census import CensusProgram

    specs = [
        # (label, COLLECTIVES.json budget key, builder)
        ("pretrain:dp8", "dp8", lambda: pc.canonical_pretrain_step(8, 1)),
        ("pretrain:dp4_tp2", "dp4_tp2", lambda: pc.canonical_pretrain_step(4, 2)),
        (
            "pretrain:dp8_health",
            "dp8",
            lambda: pc.canonical_pretrain_step(8, 1, with_health=True),
        ),
        ("pretrain:na_dp8", "na_dp8", lambda: pc.canonical_pretrain_step(8, 1, na=True)),
        (
            "pretrain:na_pallas_dp8",
            "na_pallas_dp8",
            lambda: pc.canonical_pretrain_step(8, 1, na=True, na_impl="pallas_interpret"),
        ),
        ("pretrain:scan_dp8", "scan_dp8", lambda: pc.canonical_pretrain_step(8, 1, scan=True)),
        (
            "pretrain:fsdp8",
            "fsdp8",
            lambda: pc.canonical_pretrain_step(1, 1, scan=True, n_fsdp=8),
        ),
        ("finetune:dp8", None, lambda: pc.canonical_finetune_step(8)),
        (
            "finetune:dp8_health",
            None,
            lambda: pc.canonical_finetune_step(8, with_health=True),
        ),
    ]
    out = {}
    for label, budget_key, build in specs:
        fn, args = build()
        out[label] = CensusProgram(
            label, fn, args, donate_argnums=(0,), budget_key=budget_key
        )
    return out


def _register_census() -> None:
    from ..analysis.program_census import register_aot_provider

    register_aot_provider("training", _census_programs)


_register_census()
