"""Tests for the pod-scale serving fleet (serving/fleet.py + router.py) and
the engine's serve-time TP / hot-swap / prefill-handoff legs.

The load-bearing invariants (ISSUE 12 acceptance):

* **Router stability**: consistent-hash placement is pinned by a committed
  fixture (stable across process restarts and platforms), invariant to the
  service set's iteration order, and a resize moves only ~1/N of subjects —
  every mover to the new service.
* **Fleet-vs-service bit-exactness** (the PR 5/6 contract, one level up):
  the same accepted set through a router-over-2-services fleet — under any
  affinity map, through a dedicated prefill stream, across a hot-swap
  window — produces outputs bit-identical to one synchronous service/engine
  serving that set in fleet-accept order.
* **Zero-downtime hot swap**: a fleet-wide `promote` drops zero accepted
  requests (held routes release after the flip) and every post-flip result
  is bit-identical to a fresh service built on the new checkpoint.
* **Serve-time model parallelism**: an engine whose mesh carries a
  ``model`` axis really shards its params by the training TP rules, carries
  the per-layer all-reduces in its compiled decode, and serves
  deterministically (bitwise run-to-run; values vs the replicated engine
  are NOT bitwise — the TP matmul split reassociates reductions, same
  envelope as training's dp4_tp2 layout).

Router/unit/validation tests and one compact parity pin run in tier-1;
everything needing repeated model builds, meshes, or replays is marked slow
(the fleet slow-e2e CI chunk).
"""

import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from eventstreamgpt_tpu.serving import (
    ConsistentHashRouter,
    GenerationEngine,
    PrefillStream,
    Request,
    ServingFleet,
    ServingService,
    stable_hash,
)

from .test_generation import make_prompt

pytestmark = pytest.mark.serving

MAX_LEN = 8
FIXTURE = Path(__file__).parent / "fixtures" / "router_assignment.json"


def build_ci():
    from eventstreamgpt_tpu.models.ci_model import CIPPTForGenerativeSequenceModeling

    from .test_generation import ci_config

    config = ci_config()
    prompt = make_prompt(B=4, L=4)
    model = CIPPTForGenerativeSequenceModeling(config)
    init = jax.jit(model.init)
    params = init(jax.random.PRNGKey(0), prompt)
    params2 = init(jax.random.PRNGKey(99), prompt)
    return config, model, params, params2, prompt


@pytest.fixture(scope="module")
def ci():
    return build_ci()


def engine_for(ci, *, params2=False, **kw):
    config, model, params_a, params_b, prompt = ci
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("min_bucket", 2)
    params = params_b if params2 else params_a
    return GenerationEngine(model, params, config, template=prompt, **kw)


def mixed_requests(prompt, n=4, start_id=0):
    reqs = []
    for i in range(start_id, start_id + n):
        Lp = 3 if i % 2 == 0 else 4
        reqs.append(
            Request(
                prompt=prompt.slice((slice(i % 4, i % 4 + 1), slice(0, Lp))),
                max_new_events=MAX_LEN - Lp,
                request_id=i,
            )
        )
    return reqs


def assert_same_content(a, b):
    assert a.n_events == b.n_events and a.n_generated == b.n_generated
    for f in ("event_mask", "time_delta", "dynamic_indices", "dynamic_values"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.batch, f)), np.asarray(getattr(b.batch, f))
        )


# ----------------------------------------------------------- router (tier-1)
class TestRouterHashStability:
    @pytest.fixture(scope="class")
    def fixture(self):
        return json.loads(FIXTURE.read_text())

    def test_assignment_pinned_by_committed_fixture(self, fixture):
        """Placement must survive process restarts byte-for-byte: sha256
        derivation, never Python's process-salted hash()."""
        subjects = sorted(fixture["assignment_4"])
        router = ConsistentHashRouter(fixture["services_4"], n_vnodes=fixture["n_vnodes"])
        assert router.assignment(subjects) == fixture["assignment_4"]
        router5 = ConsistentHashRouter(fixture["services_5"], n_vnodes=fixture["n_vnodes"])
        assert router5.assignment(subjects) == fixture["assignment_5"]

    def test_invariant_to_iteration_order(self, fixture):
        subjects = sorted(fixture["assignment_4"])
        for ids in (
            list(reversed(fixture["services_4"])),
            sorted(fixture["services_4"], key=stable_hash),
        ):
            assert (
                ConsistentHashRouter(ids, n_vnodes=fixture["n_vnodes"]).assignment(subjects)
                == fixture["assignment_4"]
            )

    def test_resize_moves_about_one_in_n_and_only_to_the_new_service(self, fixture):
        a4, a5 = fixture["assignment_4"], fixture["assignment_5"]
        subjects = sorted(a4)
        moved = [s for s in subjects if a4[s] != a5[s]]
        # Expected 1/(N+1) = 20%; vnodes bound the skew well inside 2x.
        assert 0.05 * len(subjects) <= len(moved) <= 0.40 * len(subjects)
        assert all(a5[s] == "svc4" for s in moved), (
            "survivor-to-survivor movement would re-prefill sessions scale-out "
            "never touched"
        )
        # Unmoved subjects keep their placement exactly.
        assert all(a5[s] == a4[s] for s in subjects if s not in set(moved))

    def test_incremental_add_matches_fresh_ring(self, fixture):
        router = ConsistentHashRouter(fixture["services_4"], n_vnodes=fixture["n_vnodes"])
        router.add_service("svc4")
        assert router.assignment(sorted(fixture["assignment_5"])) == fixture["assignment_5"]

    def test_remove_redistributes_only_the_removed_arcs(self):
        subjects = [f"u{i}" for i in range(200)]
        r3 = ConsistentHashRouter(["a", "b", "c"])
        before = r3.assignment(subjects)
        r3.remove_service("b")
        after = r3.assignment(subjects)
        for s in subjects:
            if before[s] != "b":
                assert after[s] == before[s]
            else:
                assert after[s] in {"a", "c"}

    def test_eviction_assignment_pinned_by_committed_fixture(self, fixture):
        """The fleet's eviction path (`ServingFleet.evict_service`) leans on
        `remove_service` placement being byte-stable across restarts: the
        post-eviction assignment is pinned by the committed fixture, movers
        are EXACTLY the evicted service's subjects, and every mover lands
        on a survivor — survivor sessions never re-prefill."""
        evicted = fixture["evicted_service"]
        subjects = sorted(fixture["assignment_4"])
        router = ConsistentHashRouter(fixture["services_4"], n_vnodes=fixture["n_vnodes"])
        router.remove_service(evicted)
        after = router.assignment(subjects)
        assert after == fixture["assignment_4_evict_svc1"]
        before = fixture["assignment_4"]
        survivors = set(fixture["services_4"]) - {evicted}
        movers = {s for s in subjects if before[s] != after[s]}
        assert movers == {s for s in subjects if before[s] == evicted}
        assert all(after[s] in survivors for s in movers)

    def test_validation(self):
        with pytest.raises(ValueError, match="duplicate"):
            ConsistentHashRouter(["a", "a"])
        with pytest.raises(ValueError, match="at least one"):
            ConsistentHashRouter([])
        with pytest.raises(ValueError, match="n_vnodes"):
            ConsistentHashRouter(["a"], n_vnodes=0)
        r = ConsistentHashRouter(["a", "b"])
        with pytest.raises(ValueError, match="already on the ring"):
            r.add_service("a")
        with pytest.raises(KeyError):
            r.remove_service("zzz")
        r.remove_service("b")
        with pytest.raises(ValueError, match="last service"):
            r.remove_service("a")


# --------------------------------------------------- fleet policy (tier-1)
class TestFleetValidation:
    def test_service_constraints(self, ci):
        s1 = ServingService([engine_for(ci)])
        with pytest.raises(ValueError, match="distinct"):
            ServingFleet([s1, s1])
        s2 = ServingService([engine_for(ci, max_len=MAX_LEN - 2)])
        with pytest.raises(ValueError, match="share max_len"):
            ServingFleet([s1, s2])
        with pytest.raises(ValueError, match="at least one service"):
            ServingFleet([])

    def test_promote_requires_hot_swap_engines(self, ci):
        fleet = ServingFleet([ServingService([engine_for(ci)])])
        with pytest.raises(RuntimeError, match="hot_swap"):
            fleet.promote(ci[2])

    def test_engine_rejects_non_serving_mesh_axes(self, ci):
        from eventstreamgpt_tpu.training.sharding import make_mesh

        mesh = make_mesh(2, 1, n_fsdp=2)
        with pytest.raises(ValueError, match="fsdp"):
            engine_for(ci, n_slots=4, mesh=mesh)

    def test_prefill_stream_constraints(self, ci):
        e = engine_for(ci)
        stream = PrefillStream(e)
        with pytest.raises(ValueError, match="dedicated"):
            ServingService([e], prefill_stream=stream)
        with pytest.raises(ValueError, match="max_len"):
            ServingService(
                [engine_for(ci, max_len=MAX_LEN - 2)],
                prefill_stream=PrefillStream(engine_for(ci)),
            )
        with pytest.raises(ValueError, match="prefill stream replaces"):
            ServingService(
                [engine_for(ci)],
                prefill_stream=PrefillStream(engine_for(ci)),
                prefill_budget_events=4,
            )
        svc = ServingService([engine_for(ci)], prefill_stream=PrefillStream(engine_for(ci)))
        with pytest.raises(RuntimeError, match="already attached"):
            ServingService([engine_for(ci)], prefill_stream=svc.prefill_stream)

    def test_prefill_stream_rejects_mismatched_weights(self, ci):
        """The attach-time weights gate: the handoff is bit-identical to
        local prefill only when program, weights, and keys all match, so a
        prefill replica built from a different checkpoint than its decode
        targets must be a loud construction-time error — not a silent
        generate-under-A-decode-under-B contract break."""
        import jax.numpy as jnp

        config, model, params, _, prompt = ci
        with pytest.raises(ValueError, match="weights"):
            ServingService(
                [engine_for(ci)],
                prefill_stream=PrefillStream(engine_for(ci, params2=True)),
            )
        # The same checkpoint through a DISTINCT params object attaches fine
        # (the fingerprint path, not the object-identity fast path)...
        copied = jax.tree_util.tree_map(jnp.array, params)
        eng_copy = GenerationEngine(
            model, copied, config, template=prompt,
            n_slots=2, max_len=MAX_LEN, decode_chunk=2, min_bucket=2,
        )
        svc = ServingService([engine_for(ci)], prefill_stream=PrefillStream(eng_copy))
        assert svc.prefill_stream is not None
        # ...and check_weights=False is the documented opt-out.
        stream = PrefillStream(engine_for(ci, params2=True), check_weights=False)
        ServingService([engine_for(ci)], prefill_stream=stream)

    def test_prefill_stream_rejects_mismatched_sampling_filter(self, ci):
        """The prefill replica's tail samples each handed-off request's
        FIRST event, so a top_k/top_p filter that differs from the decode
        replicas' would sample it under the wrong distribution — loudly
        rejected at attach (impl families are bit-exact by the r09 contract
        and stay free)."""
        with pytest.raises(ValueError, match="sampling filter"):
            ServingService(
                [engine_for(ci, top_k=5)],
                prefill_stream=PrefillStream(engine_for(ci)),
            )

    def test_swap_scoreboard_detects_a_lost_held_request(self, ci):
        """`swap_dropped_requests` must count the fleet's own ledger against
        where requests physically live (held queues + service pending), not
        against bookkeeping that moves in lockstep with it — a held entry
        lost before its post-flip release must READ as dropped, not hide as
        forever in-flight."""
        _, _, _, _, prompt = ci
        fleet = ServingFleet({"s": ServingService([engine_for(ci)])})
        fleet._holding.add("s")  # a swap window: routes to "s" hold
        ok = fleet.submit(
            "subj",
            Request(prompt=prompt.slice((slice(0, 1), slice(0, 3))), max_new_events=2),
        )
        rep = fleet.swap_report()
        assert ok and rep["in_flight"] == 1 and rep["swap_dropped_requests"] == 0
        fleet._held["s"].clear()  # the bug class the scoreboard exists for
        assert fleet.swap_report()["swap_dropped_requests"] == 1

    def test_prefill_compute_requires_explicit_keys(self, ci):
        _, _, _, _, prompt = ci
        eng = engine_for(ci)
        req = Request(prompt=prompt.slice((slice(0, 1), slice(0, 3))), max_new_events=2)
        with pytest.raises(ValueError, match="explicit request keys"):
            eng.prefill_compute([req], 4, 1)

    def test_hot_swap_flip_guards(self, ci):
        _, _, params, params2, prompt = ci
        eng = engine_for(ci, hot_swap=True)
        with pytest.raises(RuntimeError, match="no shadow"):
            eng.flip()
        plain = engine_for(ci)
        with pytest.raises(RuntimeError, match="hot_swap is disabled"):
            plain.load_shadow(params2)
        eng.load_shadow(params2)
        assert eng.shadow_loaded
        eng.submit(
            Request(prompt=prompt.slice((slice(0, 1), slice(0, 3))), max_new_events=2)
        )
        eng.plan_and_dispatch()
        with pytest.raises(RuntimeError, match="drained"):
            eng.flip()
        eng.run()
        eng.flip()
        assert eng.weights_version == 1
        eng.drop_shadow()
        assert not eng.shadow_loaded

    def test_slots_report_accounts_double_buffer(self, ci):
        plain = engine_for(ci)
        swap = engine_for(ci, hot_swap=True)
        a = plain.slots_report()
        b = swap.slots_report()
        assert not a["hot_swap"] and b["hot_swap"]
        assert b["params_bytes"] == 2 * a["params_bytes"]
        # Fewer admissible slots under the double-buffered weights.
        for dtype in a["per_dtype"]:
            assert (
                b["per_dtype"][dtype]["max_slots"]
                <= a["per_dtype"][dtype]["max_slots"]
            )
        # The override path (width-ladder accounting) doubles too.
        assert (
            swap.slots_report(params_bytes=1000)["params_bytes"] == 2000
            and plain.slots_report(params_bytes=1000)["params_bytes"] == 1000
        )


# ------------------------------------------------- tier-1 parity (acceptance)
class TestFleetParity:
    def test_fleet_bit_identical_to_sync_engine(self, ci):
        """The acceptance pin, one level up from PR 6: the same accepted
        set through (a) the synchronous engine, (b) a 2-service fleet with
        hash routing, and (c) a service with a dedicated prefill stream —
        identical per-request outputs, bit for bit."""
        _, _, _, _, prompt = ci
        key = jax.random.PRNGKey(7)
        sync = engine_for(ci, dispatch_depth=1, base_key=key).run(
            mixed_requests(prompt)
        )

        fleet = ServingFleet(
            [
                ServingService([engine_for(ci, dispatch_depth=2)]),
                ServingService([engine_for(ci, n_slots=4, decode_chunk=3)]),
            ],
            base_key=key,
        )
        fr = fleet.run(
            [(f"subject-{i}", r) for i, r in enumerate(mixed_requests(prompt))]
        )
        assert [r.fleet_index for r in fr] == [0, 1, 2, 3]
        assert len({r.service for r in fr}) == 2, "affinity map split the subjects"
        for a, b in zip(sync, fr):
            assert_same_content(a, b)

        svc = ServingService(
            [engine_for(ci, dispatch_depth=2)],
            base_key=key,
            prefill_stream=PrefillStream(engine_for(ci)),
        )
        streamed = svc.run(mixed_requests(prompt))
        for a, b in zip(sync, streamed):
            assert_same_content(a, b)
        stats = svc.stats()["prefill_stream"]
        assert stats["prefilled_total"] == 4 and stats["dispatches"] >= 1
        # The decode replica never ran a prefill forward of its own.
        assert svc.replicas[0].scheduler.pending == 0
        assert svc.replicas[0]._prefill_jits == {}


# ------------------------------------------------------------------ slow e2e
@pytest.mark.slow
class TestPrefillStreamE2E:
    def test_stream_parity_across_adversarial_geometry(self, ci):
        """2 decode replicas with different slot counts/chunks behind one
        prefill replica, many short requests through few slots: handoffs
        land in recycled slots under pipelined boundaries, results stay
        bit-identical to the synchronous engine."""
        _, _, _, _, prompt = ci
        key = jax.random.PRNGKey(5)

        def reqs():
            out = []
            for i in range(8):
                out.append(
                    Request(
                        prompt=prompt.slice((slice(i % 4, i % 4 + 1), slice(0, 3))),
                        max_new_events=1 + (i % 3),
                        request_id=i,
                    )
                )
            return out

        base = engine_for(ci, n_slots=2, dispatch_depth=1, base_key=key).run(reqs())
        svc = ServingService(
            [
                engine_for(ci, n_slots=2, decode_chunk=2, dispatch_depth=3),
                engine_for(ci, n_slots=4, decode_chunk=3, dispatch_depth=2),
            ],
            base_key=key,
            prefill_stream=PrefillStream(engine_for(ci)),
        )
        redo = svc.run(reqs())
        assert len(base) == len(redo) == 8
        for a, b in zip(base, redo):
            assert_same_content(a, b)
        assert {r.replica for r in redo} == {0, 1}

    def test_stream_inside_fleet_with_arrivals(self, ci):
        _, _, _, _, prompt = ci
        key = jax.random.PRNGKey(11)

        def services():
            return [
                ServingService(
                    [engine_for(ci, n_slots=2, dispatch_depth=2)],
                    prefill_stream=PrefillStream(engine_for(ci)),
                )
                for _ in range(2)
            ]

        trace = [
            (
                f"subject-{i}",
                dataclasses.replace(
                    mixed_requests(prompt)[i % 4], request_id=i, arrival_time=0.002 * i
                ),
            )
            for i in range(10)
        ]
        fleet = ServingFleet(services(), base_key=key)
        res = fleet.run(trace, use_arrival_times=True)
        assert len(res) == fleet.stats()["accepted_total"] == 10
        # Replay with arrivals is bit-identical to the up-front submit.
        fleet2 = ServingFleet(services(), base_key=key)
        res2 = fleet2.run([(s, dataclasses.replace(r, arrival_time=0.0)) for s, r in trace])
        for a, b in zip(res, res2):
            assert a.service == b.service
            assert_same_content(a, b)


@pytest.mark.slow
class TestHotSwapE2E:
    def test_idle_promote_post_flip_bit_identical_to_fresh_service(self, ci):
        _, _, _, params2, prompt = ci
        key = jax.random.PRNGKey(7)
        fleet = ServingFleet(
            [
                ServingService([engine_for(ci, hot_swap=True)]),
                ServingService([engine_for(ci, hot_swap=True)]),
            ],
            base_key=key,
        )
        pre = fleet.run(
            [(f"s{i}", r) for i, r in enumerate(mixed_requests(prompt, n=2))]
        )
        fleet.promote(params2)
        post = fleet.run(
            [
                (f"s{i}", r)
                for i, r in enumerate(
                    mixed_requests(prompt, n=2, start_id=2), start=2
                )
            ]
        )
        assert all(r.weights_version == 0 for r in pre)
        assert all(r.weights_version == 1 for r in post)
        assert fleet.swap_report()["swap_dropped_requests"] == 0
        assert fleet.swap_report()["promotions"] == 1

        # Fresh engine on the NEW checkpoint, fed the post-flip accepted set
        # with the fleet's keys: bit-identical.
        ref_reqs = [
            dataclasses.replace(r, key=fleet._request_key(i))
            for i, r in enumerate(mixed_requests(prompt, n=2, start_id=2), start=2)
        ]
        ref = engine_for(ci, params2=True, dispatch_depth=1).run(ref_reqs)
        for a, b in zip(ref, post):
            assert_same_content(a, b)
        # And the pre-flip half matches a fresh engine on the OLD checkpoint.
        old_reqs = [
            dataclasses.replace(r, key=fleet._request_key(i))
            for i, r in enumerate(mixed_requests(prompt, n=2))
        ]
        old_ref = engine_for(ci, dispatch_depth=1).run(old_reqs)
        for a, b in zip(old_ref, pre):
            assert_same_content(a, b)

    def test_swap_under_traffic_holds_routes_and_drops_nothing(self, ci):
        """The zero-downtime state machine, driven step by step: requests
        arrive for a DRAINING service mid-swap, hold at the fleet, release
        after the flip, and complete on the new weights — zero drops, both
        halves bit-identical to their checkpoint's reference."""
        _, _, _, params2, prompt = ci
        key = jax.random.PRNGKey(13)
        fleet = ServingFleet(
            [
                ServingService([engine_for(ci, hot_swap=True)]),
                ServingService([engine_for(ci, hot_swap=True)]),
            ],
            base_key=key,
        )
        first = mixed_requests(prompt, n=4)
        for i, r in enumerate(first):
            assert fleet.submit(f"subject-{i}", r)
        fleet.promote(params2)  # busy -> arms; the loop below drives it
        assert fleet._promotion is not None

        results, extras_submitted = [], False
        guard = 0
        while fleet._promotion is not None or fleet._any_busy():
            guard += 1
            assert guard < 500, "swap state machine failed to converge"
            fleet._advance_promotion()
            draining = (fleet._promotion or {}).get("draining")
            if draining and not extras_submitted:
                # Find subjects routing to the draining service and submit
                # mid-drain: they must hold, not drop, not reject.
                extras = 0
                for j in range(100, 200):
                    if extras == 2:
                        break
                    if fleet.route(f"subject-{j}") == draining:
                        assert fleet.submit(
                            f"subject-{j}",
                            dataclasses.replace(
                                mixed_requests(prompt, n=1)[0], request_id=j
                            ),
                        )
                        extras += 1
                assert extras == 2 and len(fleet._held[draining]) == 2
                extras_submitted = True
            for sid in sorted(fleet.services):
                svc = fleet.services[sid]
                for sr in svc.step(lambda: 0.0):
                    results.append(fleet._wrap(sr, sid))

        assert extras_submitted, "no drain window was observed"
        rep = fleet.swap_report()
        assert rep["swap_dropped_requests"] == 0
        assert rep["held_peak"] >= 2
        assert rep["swap_history"][0]["held_released"] >= 2
        assert len(results) == fleet.stats()["accepted_total"] == 6
        # Held requests completed post-flip on the new weights.
        held_results = [r for r in results if r.fleet_index >= 4]
        assert all(r.weights_version == 1 for r in held_results)
        ref_reqs = [
            dataclasses.replace(
                mixed_requests(prompt, n=1)[0],
                request_id=r.request_id,
                key=fleet._request_key(r.fleet_index),
            )
            for r in held_results
        ]
        ref = engine_for(ci, params2=True, dispatch_depth=1).run(ref_reqs)
        for a, b in zip(ref, sorted(held_results, key=lambda r: r.fleet_index)):
            assert_same_content(a, b)

    def test_promote_with_prefill_streams_flips_the_prefill_replica_too(self, ci):
        _, _, _, params2, prompt = ci
        key = jax.random.PRNGKey(17)
        svc = ServingService(
            [engine_for(ci, hot_swap=True)],
            prefill_stream=PrefillStream(engine_for(ci, hot_swap=True)),
        )
        fleet = ServingFleet([svc], base_key=key)
        fleet.run([(f"s{i}", r) for i, r in enumerate(mixed_requests(prompt, n=2))])
        fleet.promote(params2)
        assert svc.replicas[0].weights_version == 1
        assert svc.prefill_stream.engine.weights_version == 1
        post = fleet.run(
            [
                (f"s{i}", r)
                for i, r in enumerate(mixed_requests(prompt, n=2, start_id=2), start=2)
            ]
        )
        ref_reqs = [
            dataclasses.replace(r, key=fleet._request_key(i))
            for i, r in enumerate(mixed_requests(prompt, n=2, start_id=2), start=2)
        ]
        ref = engine_for(ci, params2=True, dispatch_depth=1).run(ref_reqs)
        for a, b in zip(ref, post):
            assert_same_content(a, b)


@pytest.mark.slow
class TestTensorParallelServing:
    """Serve-time model parallelism: the engine on a (data, model) mesh.

    The TP value contract mirrors training's dp4_tp2 layout: bitwise
    run-to-run determinism on the SAME layout, but NOT bitwise vs the
    replicated engine (the sharded matmuls reassociate reductions). What is
    pinned: params actually shard by the TP rules, the compiled decode
    carries the per-layer all-reduces (budgeted in COLLECTIVES.json via
    graftcheck), and requests serve to completion."""

    def test_tp_engine_shards_params_and_serves_deterministically(self, ci):
        from jax.sharding import PartitionSpec as P

        from eventstreamgpt_tpu.training.sharding import make_mesh

        _, _, _, _, prompt = ci
        mesh = make_mesh(2, 2)
        key = jax.random.PRNGKey(7)

        def tp_engine():
            return engine_for(ci, n_slots=4, mesh=mesh, base_key=key)

        e1 = tp_engine()
        assert e1.tensor_parallel
        cls_kernel = e1.params["params"]["output_layer"]["ClassificationLayer"][
            "kernel"
        ]
        assert cls_kernel.sharding.spec == P(None, "model")
        r1 = e1.run(mixed_requests(prompt))
        r2 = tp_engine().run(mixed_requests(prompt))
        assert len(r1) == 4 and all(r.n_events > r.prompt_len for r in r1)
        for a, b in zip(r1, r2):
            assert_same_content(a, b)

    def test_tp_decode_carries_all_reduces(self, ci):
        from eventstreamgpt_tpu.training.sharding import make_mesh

        eng = engine_for(ci, n_slots=4, mesh=make_mesh(2, 2))
        hlo = eng._decode_jit.lower(eng.params, eng._state).compile().as_text()
        assert "all-reduce" in hlo, "TP decode lost its per-layer reduces"

    def test_tp_service_behind_the_router(self, ci):
        from eventstreamgpt_tpu.training.sharding import make_mesh

        _, _, _, _, prompt = ci
        mesh = make_mesh(2, 2)
        key = jax.random.PRNGKey(23)
        fleet = ServingFleet(
            [ServingService([engine_for(ci, n_slots=4, mesh=mesh)])],
            base_key=key,
        )
        res = fleet.run(
            [(f"subject-{i}", r) for i, r in enumerate(mixed_requests(prompt))]
        )
        assert len(res) == 4 and all(r.n_generated >= 0 for r in res)
