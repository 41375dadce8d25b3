"""The serving composition matrix, executed.

``eventstreamgpt_tpu/serving/composition.py`` is the single source of
truth for which serving features compose (ISSUE 20). This suite walks
every row of that matrix:

* **Open cells** (``status == "raises"``): constructing the pair must
  raise a ``ValueError`` carrying the committed message fragment — a
  reworded or dropped guard fails here, so scope cuts stay loud.
* **Closed cells** (``status == "composes"``): the ``pinned_by``
  reference must name a test that actually exists (checked by import),
  and the cells whose pins live in THIS module are exercised below —
  compact pins in tier-1, the model-heavy mesh/fleet pins in the slow
  chunk.
* **Docs**: the table docs/serving.md publishes between the
  ``BEGIN/END composition matrix`` markers is byte-identical to
  ``render_matrix()`` — the published matrix cannot drift from the code.

The acceptance pin (``test_composed_spec_int8_tp_behind_router``) runs
speculative decoding x int8 KV cache x serve-time tensor parallelism
behind a router as ONE composed engine and requires per-request outputs
identical to the synchronous single-engine reference.
"""

import re
from pathlib import Path

import jax
import numpy as np
import pytest

from eventstreamgpt_tpu.serving import (
    GenerationEngine,
    PrefillStream,
    Request,
    ServingFleet,
    ServingService,
    SpecConfig,
    truncated_draft,
)
from eventstreamgpt_tpu.serving.composition import MATRIX, render_matrix

from .test_spec import (
    MAX_LEN,
    assert_results_match,
    build,
    engine_for,
    mixed_requests,
)

pytestmark = pytest.mark.serving

REPO_ROOT = Path(__file__).resolve().parent.parent

OPEN_CELLS = [c for c in MATRIX if c.status == "raises"]
CLOSED_CELLS = [c for c in MATRIX if c.status == "composes"]


@pytest.fixture(scope="module")
def ci():
    return build("ci")


@pytest.fixture(scope="module")
def na():
    return build("na")


def spec_for(ci, **kw):
    config, model, params, prompt, cls = ci
    dcfg, dparams = truncated_draft(config, params, 1)
    return SpecConfig(model=cls(dcfg), params=dparams, config=dcfg, k=2, **kw)


def assert_same_content(a, b):
    assert a.n_events == b.n_events and a.n_generated == b.n_generated
    for f in ("event_mask", "time_delta", "dynamic_indices", "dynamic_values"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.batch, f)), np.asarray(getattr(b.batch, f))
        )


# --------------------------------------------------- matrix data (tier-1)
class TestMatrixData:
    def test_docs_table_matches_renderer(self):
        """docs/serving.md's published matrix is the renderer's output,
        byte for byte (regenerate with
        ``python -m eventstreamgpt_tpu.serving.composition``)."""
        doc = (REPO_ROOT / "docs" / "serving.md").read_text()
        m = re.search(
            r"<!-- BEGIN composition matrix[^>]*-->\n(.*?)<!-- END composition matrix -->",
            doc,
            re.S,
        )
        assert m, "docs/serving.md lost its composition-matrix markers"
        assert m.group(1) == render_matrix(), (
            "docs/serving.md composition matrix drifted from "
            "eventstreamgpt_tpu/serving/composition.py — regenerate with "
            "`python -m eventstreamgpt_tpu.serving.composition`"
        )

    def test_every_closed_cell_names_an_existing_test(self):
        import importlib

        for cell in CLOSED_CELLS:
            path, cls_name, fn_name = cell.pinned_by.split("::")
            mod = importlib.import_module(f"tests.{Path(path).stem}")
            suite = getattr(mod, cls_name)
            assert callable(getattr(suite, fn_name, None)), (
                f"matrix cell ({cell.a}) x ({cell.b}) pins a test that does "
                f"not exist: {cell.pinned_by}"
            )

    def test_every_open_cell_has_a_builder(self):
        assert {(c.a, c.b) for c in OPEN_CELLS} == set(OPEN_BUILDERS), (
            "every open matrix cell needs a construction builder below "
            "(and no orphan builders)"
        )


# ----------------------------------------------------- open cells (tier-1)
def _paged_spec(ci, na):
    config, model, params, prompt, _ = ci
    return engine_for(
        model, params, config, prompt, spec=spec_for(ci), paged_kv=True
    )


def _paged_tp(ci, na):
    from eventstreamgpt_tpu.training.sharding import make_mesh

    config, model, params, prompt, _ = ci
    return engine_for(
        model, params, config, prompt, paged_kv=True, mesh=make_mesh(2, 2)
    )


def _paged_na(ci, na):
    config, model, params, prompt, _ = na
    return engine_for(model, params, config, prompt, paged_kv=True)


def _spec_criteria(ci, na):
    from eventstreamgpt_tpu.generation.stopping_criteria import MaxLengthCriteria

    config, model, params, prompt, _ = ci
    return engine_for(
        model, params, config, prompt,
        spec=spec_for(ci), device_criteria=(MaxLengthCriteria(6),),
    )


def _multiop_filter(ci, na):
    config, model, params, prompt, _ = ci
    return engine_for(
        model, params, config, prompt, sampling_impl="multi_op", top_k=2
    )


def _fork_monolithic(ci, na):
    config, model, params, prompt, _ = ci
    eng = engine_for(model, params, config, prompt)
    return eng.fork(
        prompt.slice((slice(0, 1), slice(0, 3))), n_branches=2, max_new_events=2
    )


OPEN_BUILDERS = {
    ("paged KV cache", "speculative decoding"): _paged_spec,
    ("paged KV cache", "tensor parallelism"): _paged_tp,
    ("paged KV cache", "nested attention"): _paged_na,
    ("speculative decoding", "device stopping criteria"): _spec_criteria,
    ("multi_op sampling tail", "top_k/top_p filtering"): _multiop_filter,
    ("fork() branched rollouts", "monolithic KV cache"): _fork_monolithic,
}


class TestOpenCells:
    @pytest.mark.parametrize(
        "cell", OPEN_CELLS, ids=[f"{c.a} x {c.b}" for c in OPEN_CELLS]
    )
    def test_open_cells_raise_their_committed_message(self, cell, ci, na):
        """Every open cell is a LOUD typed error whose message carries the
        committed fragment from the matrix — never a silent degrade."""
        with pytest.raises(ValueError, match=re.escape(cell.match)):
            OPEN_BUILDERS[(cell.a, cell.b)](ci, na)


# -------------------------------------------- closed cells, compact (tier-1)
class TestClosedCells:
    def test_spec_x_int8_matches_float_spec(self, ci):
        """The spec x int8 cell (r20 lift of the PR 13 scope cut): the
        int8-cache spec engine carries the r13 parity contract cell-wise.
        Strict-greedy spec on int8 caches reproduces the int8 baseline
        engine (structure/integers bitwise, floats in the fusion
        envelope), and the sampled int8 spec engine is bitwise invariant
        to decode chunking."""
        config, model, params, prompt, cls = ci
        base = engine_for(
            model, params, config, prompt, greedy=True, kv_cache_dtype="int8"
        ).run(mixed_requests(prompt))
        spec = engine_for(
            model, params, config, prompt, greedy=True, kv_cache_dtype="int8",
            spec=spec_for(ci, value_rtol=0.0, value_atol=0.0),
        ).run(mixed_requests(prompt))
        assert_results_match(base, spec, rtol=2e-5, atol=1e-6, label="int8 strict")

        a = engine_for(
            model, params, config, prompt, kv_cache_dtype="int8", spec=spec_for(ci)
        ).run(mixed_requests(prompt))
        b = engine_for(
            model, params, config, prompt, kv_cache_dtype="int8",
            spec=spec_for(ci), decode_chunk=1, n_slots=3,
        ).run(mixed_requests(prompt))
        by_id = {r.request_id: r for r in b}
        for r in a:
            assert_same_content(r, by_id[r.request_id])

    def test_spec_x_filter_greedy_parity(self, ci):
        """The spec x top_k/top_p cell: the accept rule runs over the
        filtered-and-renormalized pmfs, so strict-greedy spec under a
        top-k filter reproduces the filtered baseline engine."""
        config, model, params, prompt, cls = ci
        base = engine_for(
            model, params, config, prompt, greedy=True, top_k=2
        ).run(mixed_requests(prompt))
        spec = engine_for(
            model, params, config, prompt, greedy=True, top_k=2,
            spec=spec_for(ci, value_rtol=0.0, value_atol=0.0),
        ).run(mixed_requests(prompt))
        assert_results_match(base, spec, rtol=2e-5, atol=1e-6, label="filtered strict")


# ----------------------------------------- closed cells, model-heavy (slow)
@pytest.mark.slow
class TestClosedCellsSlow:
    def test_spec_x_tp_serves_deterministically(self, ci):
        """The spec x TP cell: the spec engine on a data x model mesh
        shards params by the TP rules and serves run-to-run
        deterministically (the TP value envelope vs the replicated engine
        is the training dp4_tp2 contract; what this cell pins is that the
        composed programs exist, serve, and are stable)."""
        from eventstreamgpt_tpu.training.sharding import make_mesh

        config, model, params, prompt, cls = ci
        mesh = make_mesh(2, 2)
        key = jax.random.PRNGKey(7)

        def eng():
            return engine_for(
                model, params, config, prompt,
                n_slots=4, mesh=mesh, base_key=key, spec=spec_for(ci),
            )

        e1 = eng()
        assert e1.tensor_parallel and e1.spec is not None
        r1 = e1.run(mixed_requests(prompt))
        r2 = eng().run(mixed_requests(prompt))
        assert len(r1) == 4 and all(r.n_generated >= 0 for r in r1)
        for a, b in zip(r1, r2):
            assert_same_content(a, b)

    def test_spec_x_prefill_stream_parity(self, ci):
        """The spec x prefill-stream cell: a spec decode replica behind a
        matched spec prefill replica — the handoff ships the draft cache
        seed, and results are bit-identical to the synchronous spec
        engine. The decode replica never prefills."""
        config, model, params, prompt, cls = ci
        key = jax.random.PRNGKey(5)
        sync = engine_for(
            model, params, config, prompt,
            dispatch_depth=1, base_key=key, spec=spec_for(ci),
        ).run(mixed_requests(prompt))
        svc = ServingService(
            [engine_for(model, params, config, prompt, spec=spec_for(ci))],
            base_key=key,
            prefill_stream=PrefillStream(
                engine_for(model, params, config, prompt, spec=spec_for(ci))
            ),
        )
        streamed = svc.run(mixed_requests(prompt))
        assert len(streamed) == 4
        for a, b in zip(sync, streamed):
            assert_same_content(a, b)
        assert svc.replicas[0]._prefill_jits == {}

    def test_composed_spec_int8_tp_behind_router(self, ci):
        """THE acceptance pin: spec x int8 x TP serves behind the router
        as ONE composed engine, and the fleet's accepted set reproduces
        the synchronous single-engine reference per request."""
        from eventstreamgpt_tpu.training.sharding import make_mesh

        config, model, params, prompt, cls = ci
        mesh = make_mesh(2, 2)
        key = jax.random.PRNGKey(23)

        def composed():
            return engine_for(
                model, params, config, prompt,
                n_slots=4, mesh=mesh, kv_cache_dtype="int8", spec=spec_for(ci),
            )

        probe = composed()
        assert probe.tensor_parallel and probe._kv_quantized and probe.spec is not None
        sync = engine_for(
            model, params, config, prompt,
            n_slots=4, mesh=mesh, kv_cache_dtype="int8", spec=spec_for(ci),
            dispatch_depth=1, base_key=key,
        ).run(mixed_requests(prompt))
        fleet = ServingFleet([ServingService([probe])], base_key=key)
        res = fleet.run(
            [(f"subject-{i}", r) for i, r in enumerate(mixed_requests(prompt))]
        )
        assert len(res) == 4
        for a, b in zip(sync, res):
            assert_same_content(a, b)

    def test_sharded_sampling_matches_xla_tail(self, ci):
        """The fused-sampling x data-mesh cell (retiring the r09 mesh
        rule): the Pallas sampling grid runs under shard_map over the
        slot axis, and results are bit-identical to the fused-XLA tail on
        the same mesh."""
        from eventstreamgpt_tpu.training.sharding import make_mesh

        config, model, params, prompt, cls = ci
        mesh = make_mesh(2, 1)
        key = jax.random.PRNGKey(11)
        kernel = engine_for(
            model, params, config, prompt,
            n_slots=4, mesh=mesh, base_key=key, sampling_impl="pallas_interpret",
        )
        assert kernel._shard_sampling, "dp2 + kernel tail must take shard_map"
        xla = engine_for(
            model, params, config, prompt,
            n_slots=4, mesh=mesh, base_key=key, sampling_impl="xla",
        )
        a = kernel.run(mixed_requests(prompt))
        b = xla.run(mixed_requests(prompt))
        for ra, rb in zip(a, b):
            assert_same_content(ra, rb)
