"""Communication audit: collective inventory of a compiled sharded program.

The multi-chip dry run proves the parallel layouts *execute*;
this module quantifies what they *communicate* — without hardware. The
compiled HLO names every collective XLA GSPMD inserted (op kind + output
shape), so per-layout communication volume is a static property of the
executable:

* ``collective_inventory(hlo_text)`` → per-kind op counts and payload bytes
  (from the collective outputs' shapes) plus a total.
* ``audit_step(jitted, *args)`` → AOT-lowers and compiles the step, returns
  ``(compiled, inventory)`` so callers can both inspect and execute the very
  same executable.

Used by ``__graft_entry__.dryrun_multichip`` (per-layout inventories in the
dry-run output and ``COLLECTIVES.json``) and by the ring-attention
communication test, which asserts the ring's per-step transfer stays
O(kv-block) — e.g. an accidental full-sequence all-gather in the attention
or a vocab-sharded head gathering its logits would show up here as a
payload-bytes blowup long before any hardware run.

**Kind resolution** (graftcheck Tier C): the CPU backend's GSPMD pipeline
never rewrites the all-reduce + partition-sized dynamic-slice pair into a
``reduce-scatter`` op (that pass is accelerator-only), so the FSDP gradient
sweep that compiles to a real reduce-scatter on TPU shows up here as plain
all-reduce bytes. ``collective_inventory(..., resolve_folded=True)`` walks
the compiled module's def-use chains (through copies/bitcasts and into
called fusions) and re-classifies every all-reduce whose payload is
immediately partition-sliced as an *effective* reduce-scatter with the
per-shard payload — which is what the op costs on hardware. Raw (default)
inventories keep byte-compatibility with the committed Tier-B budgets.
"""

from __future__ import annotations

import collections
import math
import re

__all__ = [
    "collective_inventory",
    "audit_step",
    "compare_inventory",
    "resolve_folded_reduce_scatters",
    "COLLECTIVE_KINDS",
]

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"(?:%(?P<name>[\w.\-]+)\s*)?"
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?P<start>-start)?\("
)

# ---- HLO module indexing for kind resolution (graftcheck Tier C) ----------
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_OP_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<type>\([^)]*\)|\S+)\s+(?P<op>[\w\-]+)"
)
_CALLEE_RE = re.compile(r"(?:to_apply=|calls=|condition=|body=)%?([\w.\-]+)")
_PARAM_IDX_RE = re.compile(r"parameter\((\d+)\)")
_GROUPS_2D_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")

# Ops a collective payload flows through unchanged (element count preserved)
# on its way to the slice that makes it an effective reduce-scatter.
_PASSTHROUGH_OPS = frozenset(
    {"copy", "bitcast", "reshape", "transpose", "all-reduce-done"}
)


def _shapes_bytes(shape_str: str, tuple_max: bool = False) -> int:
    """Bytes of one HLO result type (scalar, array, or tuple).

    ``tuple_max`` takes the LARGEST tuple member instead of the sum — the
    payload convention for async ``-start`` ops, whose tuples carry
    (operand, result[, aux]): for all-reduce/collective-permute the members
    are equal, for all-gather the result (the gathered tensor — this
    module's payload definition) is the largest.
    """
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
        sizes.append(n * _DTYPE_BYTES[dtype])
    if not sizes:
        return 0
    return max(sizes) if tuple_max else sum(sizes)


def _index_hlo_module(hlo_text: str) -> dict:
    """Parses optimized HLO into ``computation -> {op name -> op record}``.

    Each record carries the opcode, result type, operand names (refs inside
    the op's argument parens only — attribute refs like ``to_apply=%add``
    are collected separately as ``callees``), the ``parameter(i)`` index for
    parameter ops, and the replica-group size for collectives. Line-oriented
    and tolerant: unrecognized lines are skipped, which is the right failure
    mode for an analyzer that must never crash the gate on new HLO syntax.
    """
    comps: dict[str, dict] = {}
    cur: str | None = None
    for line in hlo_text.splitlines():
        stripped = line.rstrip()
        if cur is None:
            # A computation header ends in "{" and declares "-> <type> {".
            if stripped.endswith("{") and ") -> " in stripped:
                m = _COMP_HDR_RE.match(stripped)
                if m:
                    cur = m.group(1)
                    comps[cur] = {}
            continue
        if stripped == "}":
            cur = None
            continue
        om = _OP_LINE_RE.match(line)
        if not om:
            continue
        name, typ, op = om.group("name"), om.group("type"), om.group("op")
        rest = line[om.end():]
        operands: list[str] = []
        i = rest.find("(")
        if i >= 0:
            depth = 0
            j = i
            for j in range(i, len(rest)):
                if rest[j] == "(":
                    depth += 1
                elif rest[j] == ")":
                    depth -= 1
                    if depth == 0:
                        break
            operands = re.findall(r"%([\w.\-]+)", rest[i : j + 1])
        pidx = None
        if op == "parameter":
            pm = _PARAM_IDX_RE.search(line)
            if pm:
                pidx = int(pm.group(1))
        group = None
        gm = _GROUPS_2D_RE.search(line)
        if gm:
            group = int(gm.group(1))
        else:
            gm = _GROUPS_LIST_RE.search(line)
            if gm:
                group = len(gm.group(1).split(","))
        comps[cur][name] = {
            "op": op,
            "type": typ,
            "operands": operands,
            "callees": _CALLEE_RE.findall(rest),
            "pidx": pidx,
            "group": group,
        }
    return comps


def resolve_folded_reduce_scatters(hlo_text: str) -> dict[str, int]:
    """All-reduce ops whose payload is immediately partition-sliced.

    Returns ``{all-reduce op name: per-shard payload bytes}`` for every
    all-reduce (sync or ``-start``) whose result flows — through
    copies/bitcasts/reshapes/transposes and into called fusions — to a
    ``dynamic-slice`` producing exactly ``1/group`` of the reduced tensor.
    That pair is what a reduce-scatter lowers to when the backend lacks the
    reduce-scatter-creation rewrite (XLA:CPU); on TPU the same program
    compiles to a real reduce-scatter, so the *effective* kind — and the
    hardware cost — is reduce-scatter with the per-shard payload.
    """
    comps = _index_hlo_module(hlo_text)
    np_m = _NUM_PARTITIONS_RE.search(hlo_text)
    default_group = int(np_m.group(1)) if np_m else 1

    consumers: dict[str, dict[str, list[str]]] = {
        c: collections.defaultdict(list) for c in comps
    }
    for c, ops in comps.items():
        for name, info in ops.items():
            for ref in info["operands"]:
                if ref in ops and ref != name:
                    consumers[c][ref].append(name)

    def resolves(comp: str, start: str, want_bytes: int, group: int) -> bool:
        seen: set[tuple[str, str]] = set()
        stack = [(comp, start)]
        while stack:
            c, n = stack.pop()
            if (c, n) in seen:
                continue
            seen.add((c, n))
            for cn in consumers[c][n]:
                info = comps[c][cn]
                if info["op"] == "dynamic-slice":
                    if _shapes_bytes(info["type"]) * group == want_bytes:
                        return True
                    continue
                if info["op"] in _PASSTHROUGH_OPS:
                    stack.append((c, cn))
                elif info["op"] in ("fusion", "call") and info["callees"]:
                    callee = info["callees"][0]
                    if callee not in comps:
                        continue
                    for pos, ref in enumerate(info["operands"]):
                        if ref != n:
                            continue
                        for pname, pinfo in comps[callee].items():
                            if pinfo["op"] == "parameter" and pinfo["pidx"] == pos:
                                stack.append((callee, pname))
        return False

    folded: dict[str, int] = {}
    for c, ops in comps.items():
        for name, info in ops.items():
            if info["op"] not in ("all-reduce", "all-reduce-start"):
                continue
            group = info["group"] or default_group
            if group <= 1:
                continue
            b = _shapes_bytes(
                info["type"], tuple_max=info["op"].endswith("-start")
            )
            if b and resolves(c, name, b, group):
                folded[name] = b // group
    return folded


def collective_inventory(hlo_text: str, resolve_folded: bool = False) -> dict:
    """Parses optimized HLO into per-collective-kind counts and bytes.

    Async pairs count once (the ``-start`` op carries the shape; ``-done``
    is skipped). ``bytes`` is the payload size of each collective's output —
    for an all-gather that is the gathered (global) tensor, for a
    collective-permute the per-hop block.

    ``resolve_folded=True`` additionally re-classifies all-reduces whose
    payload is immediately partition-sliced (`resolve_folded_reduce_scatters`)
    under ``reduce-scatter`` with the per-shard payload — the kind-resolved
    inventory graftcheck Tier C gates at scaled shapes, where the FSDP
    gradient sweep must show up as reduce-scatter, not all-reduce. The raw
    (default) parse stays byte-compatible with the committed Tier-B budgets.
    """
    folded = resolve_folded_reduce_scatters(hlo_text) if resolve_folded else {}
    inv = {kind: {"count": 0, "bytes": 0, "max_bytes": 0} for kind in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        shape = m.group("shape")
        # Async -start ops output (operand, result[, aux]) tuples; the
        # payload is the result (largest member), counted once.
        b = _shapes_bytes(shape, tuple_max=bool(m.group("start")) and shape.startswith("("))
        name = m.group("name")
        if kind == "all-reduce" and name is not None and name in folded:
            kind, b = "reduce-scatter", folded[name]
        inv[kind]["bytes"] += b
        inv[kind]["max_bytes"] = max(inv[kind]["max_bytes"], b)
        inv[kind]["count"] += 1
    inv["total_bytes"] = sum(v["bytes"] for v in inv.values() if isinstance(v, dict))
    inv["total_count"] = sum(v["count"] for v in inv.values() if isinstance(v, dict))
    return inv


def compare_inventory(
    inventory: dict,
    budget: dict,
    rel_tol: float = 0.25,
    abs_slack: int = 64 * 1024,
    per_kind_tol: dict[str, tuple[float, int]] | None = None,
) -> list[str]:
    """Gates an inventory against a committed budget (``COLLECTIVES.json``).

    The graftcheck contract: per-kind and total payload bytes must stay
    within ``budget * (1 + rel_tol) + abs_slack``, and a kind that the
    budget says is absent may not appear beyond the absolute slack — an
    accidental table-sized all-gather shows up as a new kind or a byte
    blowup long before hardware. The bound is **per-kind**:
    ``per_kind_tol={"all-reduce": (0.05, 4096), ...}`` overrides the default
    ``(rel_tol, abs_slack)`` pair for the named kinds, so layouts whose
    budget is dominated by one kind can pin the others tightly.

    A kind the budget commits real bytes to (beyond its absolute slack)
    must also still be PRESENT (count >= 1): a reduce-scatter →
    all-reduce substitution at equal bytes keeps every byte bound happy
    while silently multiplying the hardware cost of the sweep, and the
    presence rule is what catches it. Shrinking below budget otherwise
    never fails — regressions in the good direction just mean the budget
    file deserves a refresh (which is also the fix when a kind's
    disappearance is an intentional optimization).

    Returns human-readable violations (empty ⇒ within budget).
    """
    problems: list[str] = []

    def bounds(kind: str) -> tuple[float, int]:
        if per_kind_tol and kind in per_kind_tol:
            return per_kind_tol[kind]
        return (rel_tol, abs_slack)

    for kind in COLLECTIVE_KINDS:
        k_rel, k_abs = bounds(kind)
        have = inventory.get(kind, {}).get("bytes", 0)
        want = budget.get(kind, {}).get("bytes", 0)
        if have > want * (1.0 + k_rel) + k_abs:
            problems.append(
                f"{kind}: {have} payload bytes exceeds budget {want} "
                f"(+{k_rel:.0%} + {k_abs}B slack)"
            )
        if want > k_abs and inventory.get(kind, {}).get("count", 0) == 0:
            problems.append(
                f"{kind}: budget commits {want} payload bytes but the compiled "
                "program emits none — a kind substitution (e.g. reduce-scatter "
                "re-routed through all-reduce) keeps the byte totals while "
                "changing the hardware cost; refresh the budget if the "
                "disappearance is an intentional optimization"
            )
    have_total = inventory.get("total_bytes", 0)
    want_total = budget.get("total_bytes", 0)
    if have_total > want_total * (1.0 + rel_tol) + abs_slack:
        problems.append(
            f"total collective payload {have_total}B exceeds budget {want_total}B "
            f"(+{rel_tol:.0%} + {abs_slack}B slack)"
        )
    return problems


def audit_step(jitted_fn, *args, **kwargs):
    """AOT-compiles ``jitted_fn(*args)`` and returns ``(compiled, inventory)``.

    The compiled executable is callable with the same arguments (donation
    semantics preserved), so callers pay one compile for both the audit and
    the execution.
    """
    compiled = jitted_fn.lower(*args, **kwargs).compile()
    return compiled, collective_inventory(compiled.as_text())
