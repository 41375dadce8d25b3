"""The serving composition matrix — ONE source of truth for which serving
features compose.

Every entry is a pair of serving features that is either **closed** (the
pair constructs and serves, with a parity contract pinned by a named
test) or **open** (a loud typed ``ValueError`` at engine construction
whose message names the cell and the nearest supported configuration —
the anti-silent-scope-cut discipline from r16/r20).

Three consumers read this module and must stay in sync by construction:

* ``tests/test_composition.py`` walks every row: ``composes`` rows must
  name a test that exists; ``raises`` rows must actually raise with the
  committed message fragment when the pair is constructed.
* ``docs/serving.md`` ("The composition matrix") embeds the table that
  :func:`render_matrix` produces, between ``BEGIN/END composition
  matrix`` markers; a tier-1 test diffs the docs region against the
  renderer, so the published matrix cannot drift from the code.
  Regenerate with ``python -m eventstreamgpt_tpu.serving.composition``.
* ``serving/engine.py``'s constructor raises the matching errors; the
  ``match`` fragments below are committed API (tests pin them), so
  reworded guards fail the suite rather than silently orphaning docs.

Open cells are tracked as ROADMAP item 3 (composition closure, issue
#21): closing one means flipping its row to ``composes``, writing the
parity pin it names, and regenerating the docs table — one diff, three
consumers.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Cell", "MATRIX", "render_matrix"]


@dataclass(frozen=True)
class Cell:
    """One composition-matrix row.

    ``status`` is ``"composes"`` (cell is closed; ``pinned_by`` names the
    parity test) or ``"raises"`` (cell is open; ``match`` is the
    committed error-message fragment the constructor must emit).
    """

    a: str
    b: str
    status: str
    contract: str
    pinned_by: str = ""
    match: str = ""


MATRIX: tuple[Cell, ...] = (
    # ------------------------------------------------------- closed cells
    Cell(
        "speculative decoding",
        "int8 KV cache",
        "composes",
        "draft AND target caches quantize-on-write; the int8 spec engine "
        "reproduces the int8 baseline engine (the r13 strict-greedy parity "
        "contract, carried cell-wise) and stays bitwise chunk-invariant "
        "when sampling",
        pinned_by="tests/test_composition.py::TestClosedCells::"
        "test_spec_x_int8_matches_float_spec",
    ),
    Cell(
        "speculative decoding",
        "top_k/top_p filtering",
        "composes",
        "the accept rule runs over the filtered-and-renormalized pmfs "
        "(draft, verify, and residual all filter tie-inclusively); greedy "
        "decoding under the filter reproduces the filtered baseline engine",
        pinned_by="tests/test_composition.py::TestClosedCells::"
        "test_spec_x_filter_greedy_parity",
    ),
    Cell(
        "speculative decoding",
        "tensor parallelism",
        "composes",
        "draft/verify programs pin out_shardings to the input layout (the "
        "donation-preserving Tier C fix); serves run-to-run deterministic "
        "on the data x model mesh, values vs the replicated engine in the "
        "TP reassociation envelope",
        pinned_by="tests/test_composition.py::TestClosedCellsSlow::"
        "test_spec_x_tp_serves_deterministically",
    ),
    Cell(
        "speculative decoding",
        "prefill stream",
        "composes",
        "the handoff ships the draft cache seed beside the target rows; "
        "stream results are bit-identical to the synchronous spec engine "
        "(both tiers must run the same spec configuration — a mixed pair "
        "is a loud error)",
        pinned_by="tests/test_composition.py::TestClosedCellsSlow::"
        "test_spec_x_prefill_stream_parity",
    ),
    Cell(
        "spec x int8 x TP",
        "router / fleet",
        "composes",
        "THE composed production engine (r20 acceptance): all three "
        "capacity multipliers behind one router as ONE engine, "
        "per-request outputs matching the synchronous single-engine "
        "reference; every compiled program budget-gated "
        "(engine_composed_*_dp4_tp2)",
        pinned_by="tests/test_composition.py::TestClosedCellsSlow::"
        "test_composed_spec_int8_tp_behind_router",
    ),
    Cell(
        "fused sampling kernel",
        "multi-device data mesh",
        "composes",
        "the Pallas sampling grid runs under shard_map over the slot axis "
        "— each device sweeps its own (n_slots/dp, V) logits shard, no "
        "slot-plane gather (engine_sampling_shard_dp8 budget); retires "
        "the r09 fall-back-to-XLA-on-any-mesh rule",
        pinned_by="tests/test_composition.py::TestClosedCellsSlow::"
        "test_sharded_sampling_matches_xla_tail",
    ),
    Cell(
        "int8 KV cache",
        "online service",
        "composes",
        "service replicas with quantized caches reproduce float "
        "generate() trajectories — structure/integers exact, floats "
        "within the documented tolerance",
        pinned_by="tests/test_kv_quant.py::TestQuantizedParityTier1::"
        "test_int8_engine_and_service_match_generate",
    ),
    Cell(
        "paged KV cache",
        "int8 KV cache",
        "composes",
        "the scale tables page alongside the quantized planes; the int8 "
        "paged engine equals the int8 monolithic engine bitwise",
        pinned_by="tests/test_paged_cache.py::TestPagedMonolithicE2E::"
        "test_int8_kvq_composes",
    ),
    # -------------------------------------------------------- open cells
    Cell(
        "paged KV cache",
        "speculative decoding",
        "raises",
        "the verify window re-reads freshly written positions through the "
        "draft/target cache pair, which still admits monolithically",
        match="paged x spec",
    ),
    Cell(
        "paged KV cache",
        "tensor parallelism",
        "raises",
        "the block pool replicates over the mesh, defeating the "
        "model-axis KV sharding",
        match="paged x TP",
    ),
    Cell(
        "paged KV cache",
        "nested attention",
        "raises",
        "the dep-graph caches reset per event and do not page",
        match="nested-attention models",
    ),
    Cell(
        "speculative decoding",
        "device stopping criteria",
        "raises",
        "custom device_criteria cannot be re-evaluated per committed "
        "prefix inside the verify program",
        match="device_criteria",
    ),
    Cell(
        "multi_op sampling tail",
        "top_k/top_p filtering",
        "raises",
        "filtering lives in the fused tail's masked-fill epilogue; the "
        "r07 baseline arm has no filter stage",
        match="fused sampling tail",
    ),
    Cell(
        "fork() branched rollouts",
        "monolithic KV cache",
        "raises",
        "branches share prefix blocks copy-on-write, which the per-slot "
        "monolithic cache cannot express",
        match="paged_kv=True",
    ),
)


def render_matrix() -> str:
    """The docs/serving.md table, rendered from :data:`MATRIX`.

    Pinned byte-for-byte by ``tests/test_composition.py`` against the
    region between the ``BEGIN/END composition matrix`` markers.
    """
    lines = [
        "| Feature A | Feature B | Status | Contract |",
        "| --- | --- | --- | --- |",
    ]
    for c in MATRIX:
        status = "**composes**" if c.status == "composes" else "loud error"
        tail = c.contract
        if c.status == "composes":
            tail += f" (pinned by `{c.pinned_by}`)"
        else:
            tail += f' (raises with "…{c.match}…")'
        lines.append(f"| {c.a} | {c.b} | {status} | {tail} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    print(render_matrix(), end="")
