"""graftcheck: the static-analysis suite's own contract tests.

Three layers, matching the tool's tiers:

* **Rule fixtures** — for each lint rule GC001-GC005, a snippet that
  deliberately violates it (true positive: the finding fires with the right
  rule id and line) and an idiomatic repo pattern (false-positive guard:
  the rule stays silent on code we actually write).
* **Workflow** — the baseline file suppresses known findings but fails new
  ones; inline ``graftcheck: allow`` waivers; the repo itself lints clean
  under the checked-in baseline; the CLI exit-code contract.
* **Tier B** — the f64 / host-transfer detectors and the collective budget
  comparator on crafted program text, plus the real no-f64 / no-host-
  transfer gates on the *lowered* canonical pretrain and fine-tune steps
  (lowering only — the compiled collective audit runs in the CI
  ``graftcheck`` job via ``scripts/graftcheck.py --tier all``). The no-f64
  lowering test is the regression pin for the host-only scope of the
  ``np.float64`` preprocessing code.
"""

import json
import textwrap
from pathlib import Path

import pytest

from eventstreamgpt_tpu.analysis.lint import (
    RULES,
    apply_baseline,
    default_targets,
    lint_paths,
    lint_source,
    load_baseline,
    save_baseline,
)

pytestmark = pytest.mark.graftcheck

REPO_ROOT = Path(__file__).resolve().parent.parent


def rules_on(src: str, path: str = "fixture.py") -> list[tuple[str, int]]:
    return [(f.rule, f.line) for f in lint_source(textwrap.dedent(src), path)]


def rule_ids(src: str, path: str = "fixture.py") -> set[str]:
    return {r for r, _ in rules_on(src, path)}


# ------------------------------------------------------------ GC001 fixtures
class TestGC001HostSync:
    def test_float_in_jitted_fn_fires(self):
        src = """
        import jax, jax.numpy as jnp

        @jax.jit
        def step(x):
            return float(jnp.sum(x))
        """
        assert ("GC001", 6) in rules_on(src)

    def test_item_in_factory_returned_step_fires(self):
        # The repo idiom: a factory's nested step fn jitted by a caller.
        src = """
        import jax

        def make_body(model):
            def train_step(state, batch):
                return state.loss.item()
            return train_step

        def make_step(model):
            return jax.jit(make_body(model), donate_argnums=(0,))
        """
        assert "GC001" in rule_ids(src)

    def test_np_asarray_in_scan_body_fires(self):
        src = """
        import jax
        import numpy as np

        def outer(xs):
            def body(c, x):
                return c, np.asarray(x)
            return jax.lax.scan(body, 0, xs)
        """
        assert "GC001" in rule_ids(src)

    def test_sync_in_dispatch_loop_fires(self):
        src = """
        import jax

        def fit(model, batches):
            step = jax.jit(model)
            losses = []
            for b in batches:
                state, loss = step(b)
                losses.append(float(loss))
            return losses
        """
        assert ("GC001", 9) in rules_on(src)

    def test_sync_via_loop_helper_fires(self):
        # handle_window-style: the sync hides in a nested helper the loop calls.
        src = """
        import jax

        def fit(step_body, batches):
            step = jax.jit(step_body)

            def flush(loss):
                return float(loss)

            out = []
            for b in batches:
                loss = step(b)
                out.append(flush(loss))
            return out
        """
        assert "GC001" in rule_ids(src)

    def test_callback_defined_in_loop_is_clean(self):
        # A callback *defined* inside the dispatch loop doesn't run per-step
        # unless called there — only calls are followed.
        src = """
        import jax

        def fit(model, batches, logger):
            step = jax.jit(model)
            for b in batches:
                loss = step(b)
                logger.defer(lambda v=loss: float(v))
        """
        assert "GC001" not in rule_ids(src)

    def test_host_loop_without_jit_is_clean(self):
        src = """
        def summarize(rows):
            return [float(r) for r in rows]

        def fit(rows):
            out = []
            for r in rows:
                out.append(float(r))
            return out
        """
        assert "GC001" not in rule_ids(src)

    def test_float_of_literal_in_traced_scope_is_clean(self):
        src = """
        import jax

        @jax.jit
        def step(x):
            best = float("inf")
            return x * float(2)
        """
        assert "GC001" not in rule_ids(src)

    def test_inline_waiver_suppresses(self):
        src = """
        import jax

        @jax.jit
        def step(x):
            return float(x)  # graftcheck: allow GC001 -- fixture waiver
        """
        assert "GC001" not in rule_ids(src)


# ------------------------------------------------------------ GC002 fixtures
class TestGC002Float64:
    def test_np_float64_attr_fires(self):
        assert "GC002" in rule_ids("import numpy as np\nx = np.zeros(3, dtype=np.float64)\n")

    def test_astype_string_fires(self):
        assert "GC002" in rule_ids("import numpy as np\nx = np.zeros(3).astype('float64')\n")

    def test_dtype_string_kwarg_fires(self):
        assert "GC002" in rule_ids("import numpy as np\nx = np.arange(3, dtype='float64')\n")

    def test_enable_x64_fires(self):
        assert "GC002" in rule_ids("import jax\njax.config.update('jax_enable_x64', True)\n")

    def test_preprocessing_allowlist_is_clean(self):
        src = "import numpy as np\nx = np.zeros(3, dtype=np.float64)\n"
        assert rule_ids(src, "eventstreamgpt_tpu/data/preprocessing/scaler.py") == set()
        assert rule_ids(src, "eventstreamgpt_tpu/data/dataset_pandas.py") == set()
        assert rule_ids(src, "eventstreamgpt_tpu/data/synthetic.py") == set()

    def test_float32_is_clean(self):
        assert "GC002" not in rule_ids(
            "import numpy as np\nx = np.zeros(3, dtype=np.float32)\n"
        )


# ------------------------------------------------------------ GC003 fixtures
class TestGC003KeyReuse:
    def test_straight_line_reuse_fires(self):
        src = """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """
        assert ("GC003", 6) in rules_on(src)

    def test_loop_reuse_fires(self):
        src = """
        import jax

        def noisy(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key, (3,)))
            return out
        """
        assert "GC003" in rule_ids(src)

    def test_split_reassign_idiom_is_clean(self):
        src = """
        import jax

        def sample(key):
            key, k1 = jax.random.split(key)
            a = jax.random.normal(k1, (3,))
            key, k2 = jax.random.split(key)
            b = jax.random.uniform(k2, (3,))
            return a + b
        """
        assert "GC003" not in rule_ids(src)

    def test_fold_in_per_iteration_is_clean(self):
        src = """
        import jax

        def noisy(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(jax.random.fold_in(key, i), (3,)))
            return out
        """
        assert "GC003" not in rule_ids(src)

    def test_split_elements_are_distinct_keys(self):
        src = """
        import jax

        def make_inputs(seed):
            ks = jax.random.split(jax.random.PRNGKey(seed), 3)
            q = jax.random.normal(ks[0], (4,))
            k = jax.random.normal(ks[1], (4,))
            v = jax.random.normal(ks[2], (4,))
            return q, k, v
        """
        assert "GC003" not in rule_ids(src)

    def test_early_return_branch_is_not_reuse(self):
        src = """
        import jax

        def gen(key, fast):
            if fast:
                return jax.random.normal(key, (3,))
            key, sub = jax.random.split(key)
            return jax.random.normal(sub, (3,))
        """
        assert "GC003" not in rule_ids(src)


# ------------------------------------------------------------ GC004 fixtures
class TestGC004TracedControlFlow:
    def test_if_on_traced_value_fires(self):
        src = """
        import jax

        @jax.jit
        def f(x):
            if x.sum() > 0:
                return x
            return -x
        """
        assert ("GC004", 6) in rules_on(src)

    def test_while_on_traced_value_fires(self):
        src = """
        import jax

        @jax.jit
        def f(x):
            while x > 0:
                x = x - 1
            return x
        """
        assert "GC004" in rule_ids(src)

    def test_static_tests_are_clean(self):
        src = """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x, mask=None):
            if mask is None:
                mask = jnp.ones_like(x)
            if x.ndim == 2:
                x = x[None]
            if len(x.shape) > 3:
                x = x.reshape(-1)
            if isinstance(mask, tuple):
                mask = mask[0]
            return jnp.where(mask > 0, x, 0.0)
        """
        assert "GC004" not in rule_ids(src)

    def test_static_argnames_param_is_clean(self):
        src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("interpret",))
        def f(x, interpret=False):
            if interpret:
                return x
            return -x
        """
        assert "GC004" not in rule_ids(src)

    def test_str_annotated_param_is_clean(self):
        src = """
        import jax

        @jax.jit
        def f(x, mode: str = "mean"):
            if mode == "mean":
                return x.mean()
            return x.sum()
        """
        assert "GC004" not in rule_ids(src)


# ------------------------------------------------------------ GC005 fixtures
class TestGC005UndonatedTrainStep:
    def test_jit_of_train_step_without_donation_fires(self):
        src = """
        import jax

        def train_step(state, batch):
            return state

        step = jax.jit(train_step)
        """
        assert ("GC005", 7) in rules_on(src)

    def test_decorated_train_step_without_donation_fires(self):
        src = """
        import jax

        @jax.jit
        def train_step(state, batch):
            return state
        """
        assert "GC005" in rule_ids(src)

    def test_donated_train_step_is_clean(self):
        src = """
        import jax

        def train_step(state, batch):
            return state

        step = jax.jit(train_step, donate_argnums=(0,))
        """
        assert "GC005" not in rule_ids(src)

    def test_eval_step_without_donation_is_clean(self):
        # Eval steps don't update state in place; donation is a train-step
        # contract only.
        src = """
        import jax

        def eval_step(params, batch):
            return params

        step = jax.jit(eval_step)
        """
        assert "GC005" not in rule_ids(src)

    def test_engine_decode_attribute_jit_without_donation_fires(self):
        # The serving engine's dispatch jits bind methods to attributes:
        # `self._decode_jit = jax.jit(self._decode_chunk_ci)` — both the
        # attribute target and the attribute arg name the step.
        src = """
        import jax

        class Engine:
            def __init__(self):
                self._decode_jit = jax.jit(self._decode_chunk_ci)

            def _decode_chunk_ci(self, params, st):
                return st
        """
        assert "GC005" in rule_ids(src)

    def test_engine_decode_attribute_jit_with_donation_is_clean(self):
        src = """
        import jax

        class Engine:
            def __init__(self):
                self._decode_jit = jax.jit(self._decode_chunk_ci, donate_argnums=(1,))

            def _decode_chunk_ci(self, params, st):
                return st
        """
        assert "GC005" not in rule_ids(src)

    def test_prefill_factory_jit_without_donation_fires(self):
        # The prefill memo idiom: `self._prefill_jits[key] = jax.jit(fn)` —
        # Subscript targets carry no name, but an IfExp/attr arg naming
        # prefill does.
        src = """
        import jax

        class Engine:
            def _prefill_jit(self, bucket):
                fn = jax.jit(self._prefill_bucket)
                return fn

            def _prefill_bucket(self, params, st):
                return st
        """
        assert "GC005" in rule_ids(src)

    def test_ifexp_decode_arg_fires(self):
        src = """
        import jax

        class Engine:
            def __init__(self, na):
                self._step = jax.jit(self._decode_na if na else self._decode_ci)

            def _decode_na(self, p, st):
                return st

            def _decode_ci(self, p, st):
                return st
        """
        assert "GC005" in rule_ids(src)

    def test_boundary_pack_jit_is_clean(self):
        # Read-only packs don't update state; no trigger name, no finding.
        src = """
        import jax

        class Engine:
            def __init__(self):
                self._pack_boundary_jit = jax.jit(lambda st: st.done)
        """
        assert "GC005" not in rule_ids(src)


# ------------------------------------------- GC006-GC008 (serving-scoped)
SERVING_PATH = "eventstreamgpt_tpu/serving/fixture.py"


class TestGC006SetIteration:
    def test_for_over_set_literal_fires(self):
        src = """
        def place(slots):
            for s in {3, 1, 2}:
                slots.admit(s)
        """
        assert ("GC006", 3) in rules_on(src, SERVING_PATH)

    def test_for_over_set_call_fires(self):
        src = """
        def evict(sessions):
            for sid in set(sessions):
                sessions.drop(sid)
        """
        assert "GC006" in rule_ids(src, SERVING_PATH)

    def test_comprehension_over_set_var_fires(self):
        src = """
        def order(pending):
            ready = {r for r in pending if r.ok}
            return [r.key for r in ready]
        """
        assert ("GC006", 4) in rules_on(src, SERVING_PATH)

    def test_sorted_wrap_is_clean(self):
        src = """
        def place(slots):
            ready = set(slots)
            for s in sorted(ready):
                admit(s)
        """
        assert "GC006" not in rule_ids(src, SERVING_PATH)

    def test_membership_test_is_clean(self):
        src = """
        def gate(live, sid):
            seen = {1, 2, 3}
            if sid in seen:
                return live
        """
        assert "GC006" not in rule_ids(src, SERVING_PATH)

    def test_outside_serving_is_clean(self):
        src = """
        def anywhere():
            for x in {1, 2}:
                print(x)
        """
        assert "GC006" not in rule_ids(src, "eventstreamgpt_tpu/training/fixture.py")

    def test_reassigned_to_list_is_clean(self):
        src = """
        def place(slots):
            ready = set(slots)
            ready = sorted(ready)
            for s in ready:
                admit(s)
        """
        assert "GC006" not in rule_ids(src, SERVING_PATH)


class TestGC007NondeterministicSources:
    def test_builtin_hash_fires(self):
        src = """
        def route(subject, n):
            return hash(subject) % n
        """
        assert "GC007" in rule_ids(src, SERVING_PATH)

    def test_wall_clock_fires(self):
        src = """
        import time

        def arrival():
            return time.time()
        """
        assert "GC007" in rule_ids(src, SERVING_PATH)

    def test_random_module_fires(self):
        src = """
        import random

        def pick(replicas):
            return random.choice(replicas)
        """
        assert "GC007" in rule_ids(src, SERVING_PATH)

    def test_uuid4_fires(self):
        src = """
        import uuid

        def request_id():
            return str(uuid.uuid4())
        """
        assert "GC007" in rule_ids(src, SERVING_PATH)

    def test_perf_counter_is_sanctioned(self):
        src = """
        import time

        def measure():
            t0 = time.perf_counter()
            return time.perf_counter() - t0
        """
        assert "GC007" not in rule_ids(src, SERVING_PATH)

    def test_jax_random_is_clean(self):
        src = """
        import jax

        def draw(key):
            return jax.random.uniform(key)
        """
        assert "GC007" not in rule_ids(src, SERVING_PATH)

    def test_outside_serving_is_clean(self):
        src = """
        def anywhere(x):
            return hash(x)
        """
        assert "GC007" not in rule_ids(src, "eventstreamgpt_tpu/data/fixture.py")

    def test_inline_waiver_suppresses(self):
        src = """
        import time

        def stamp():
            return time.time()  # graftcheck: allow GC007 -- log timestamp, never a decision input
        """
        assert "GC007" not in rule_ids(src, SERVING_PATH)


class TestGC008LedgerDiscipline:
    def test_decref_outside_owners_fires(self):
        src = """
        class Engine:
            def harvest(self, slot):
                self._block_alloc.decref(self._tables[slot])
        """
        assert "GC008" in rule_ids(src, SERVING_PATH)

    def test_alias_alloc_outside_owners_fires(self):
        src = """
        class Engine:
            def admit(self, n):
                a = self._block_alloc
                return a.alloc(n)
        """
        assert "GC008" in rule_ids(src, SERVING_PATH)

    def test_internal_touch_outside_owners_fires(self):
        src = """
        def steal(engine):
            a = engine._block_alloc
            return a._free.pop()
        """
        assert "GC008" in rule_ids(src, SERVING_PATH)

    def test_sanctioned_owner_funcs_are_clean(self):
        src = """
        class Engine:
            def _free_slot_blocks(self, slot):
                self._block_alloc.decref(self._tables[slot])

            def _plan_admission_tables(self, group):
                alloc = self._block_alloc
                return alloc.alloc(2)

            def reset(self):
                self._block_alloc.reset_occupancy()
        """
        assert "GC008" not in rule_ids(src, SERVING_PATH)

    def test_allocator_class_itself_is_clean(self):
        src = """
        class BlockAllocator:
            def decref(self, blocks):
                for b in blocks:
                    self._rc[b] -= 1
                    if self._rc[b] == 0:
                        self._free.append(b)
        """
        assert "GC008" not in rule_ids(src, SERVING_PATH)

    def test_readonly_counters_are_clean(self):
        src = """
        def stats(engine):
            a = engine._block_alloc
            return {"in_use": a.in_use, "free": a.free_blocks}
        """
        assert "GC008" not in rule_ids(src, SERVING_PATH)


class TestServingPackageDeterminismClean:
    def test_serving_package_has_no_unbaselined_gc006_gc008(self):
        # Satellite guarantee: the real control plane is clean under the
        # determinism lint at HEAD (inline waivers are part of clean).
        findings = lint_paths(default_targets(REPO_ROOT), REPO_ROOT)
        baseline = load_baseline(
            REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json"
        )
        new, _ = apply_baseline(findings, baseline)
        det = [f for f in new if f.rule in ("GC006", "GC007", "GC008")]
        assert det == [], "\n".join(f.render() for f in det)


# -------------------------------------------------------------- baseline
class TestBaselineWorkflow:
    SRC = textwrap.dedent(
        """
        import numpy as np
        x = np.zeros(3, dtype=np.float64)
        """
    )

    def test_round_trip_suppresses_known_and_fails_new(self, tmp_path):
        findings = lint_source(self.SRC, "mod.py")
        assert len(findings) == 1
        fp = tmp_path / "baseline.json"
        save_baseline(findings, fp)
        baseline = load_baseline(fp)

        new, suppressed = apply_baseline(lint_source(self.SRC, "mod.py"), baseline)
        assert new == [] and suppressed == 1

        # A second, new finding is NOT covered by the old baseline.
        grown = self.SRC + "y = np.ones(3, dtype=np.float64)\n"
        new, suppressed = apply_baseline(lint_source(grown, "mod.py"), baseline)
        assert suppressed == 1
        assert len(new) == 1 and new[0].rule == "GC002"

    def test_baseline_keys_survive_line_drift(self, tmp_path):
        findings = lint_source(self.SRC, "mod.py")
        fp = tmp_path / "baseline.json"
        save_baseline(findings, fp)
        # Same code, shifted three lines down: still suppressed (keys are
        # path+rule+snippet, not line numbers).
        shifted = "#\n#\n#\n" + self.SRC
        new, suppressed = apply_baseline(
            lint_source(shifted, "mod.py"), load_baseline(fp)
        )
        assert new == [] and suppressed == 1

    def test_repo_lints_clean_under_checked_in_baseline(self):
        findings = lint_paths(default_targets(REPO_ROOT), REPO_ROOT)
        baseline = load_baseline(
            REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json"
        )
        new, _ = apply_baseline(findings, baseline)
        assert new == [], "new lint findings:\n" + "\n".join(f.render() for f in new)

    def test_checked_in_baseline_is_valid_json_with_rule_ids(self):
        fp = REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json"
        data = json.loads(fp.read_text())
        assert data["findings"], "baseline exists but is empty?"
        assert all(rec["rule"] in RULES for rec in data["findings"])


# ------------------------------------------------------------------ CLI
class TestCLI:
    def test_exit_zero_on_repo(self):
        from scripts.graftcheck import main

        assert main([]) == 0

    def test_exit_nonzero_on_violation_file(self, tmp_path, capsys):
        from scripts.graftcheck import main

        bad = tmp_path / "bad.py"
        bad.write_text("import numpy as np\nx = np.zeros(3, dtype=np.float64)\n")
        rc = main([str(bad)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "GC002" in out and "bad.py:2" in out

    def test_all_five_rules_reported_with_file_line(self, tmp_path, capsys):
        """One seeded fixture per rule: the CLI exits non-zero and names
        every violation as file:line + rule id."""
        from scripts.graftcheck import main

        bad = tmp_path / "five.py"
        bad.write_text(
            textwrap.dedent(
                """
                import jax
                import numpy as np

                @jax.jit
                def traced(x):
                    return float(x.sum())          # GC001 (line 7)

                table = np.zeros(4, dtype=np.float64)  # GC002 (line 9)

                def sample(key):
                    a = jax.random.normal(key, (3,))
                    b = jax.random.uniform(key, (3,))  # GC003 (line 13)
                    return a + b

                @jax.jit
                def branchy(x):
                    if x.sum() > 0:                # GC004 (line 18)
                        return x
                    return -x

                def train_step(state, batch):
                    return state

                step = jax.jit(train_step)         # GC005 (line 25)
                """
            )
        )
        rc = main([str(bad)])
        out = capsys.readouterr().out
        assert rc == 1
        expected = {"GC001": 7, "GC002": 9, "GC003": 13, "GC004": 18, "GC005": 25}
        for rule, line in expected.items():
            assert rule in out, f"{rule} missing from CLI output"
            assert f"five.py:{line}" in out, f"{rule} not reported at five.py:{line}"

    def test_list_rules(self, capsys):
        from scripts.graftcheck import main

        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in RULES:
            assert rule in out

    def test_write_baseline_rejects_explicit_paths(self, tmp_path, capsys):
        # A partial lint must never overwrite the whole-repo baseline.
        from scripts.graftcheck import main

        f = tmp_path / "one.py"
        f.write_text("x = 1\n")
        with pytest.raises(SystemExit) as exc:
            main(["--write-baseline", str(f)])
        assert exc.value.code == 2
        assert "cannot be combined" in capsys.readouterr().err


# ------------------------------------------------------- Tier B detectors
class TestProgramCheckDetectors:
    def test_f64_detector(self):
        from eventstreamgpt_tpu.analysis.program_checks import check_no_f64

        assert check_no_f64("  %x = f64[4,2] parameter(0)") != []
        assert check_no_f64("  %y = stablehlo.add : tensor<2x3xf64>") != []
        assert check_no_f64("  %x = f32[4,2] parameter(0)") == []
        # hex-ish identifiers must not false-positive
        assert check_no_f64('  metadata={op_name="jit(f)/af64b"}') == []

    def test_host_transfer_detector(self):
        from eventstreamgpt_tpu.analysis.program_checks import check_no_host_transfers

        assert check_no_host_transfers("  %o = token[] outfeed(%x, %tok)") != []
        assert (
            check_no_host_transfers(
                '  %c = f32[] custom-call(), custom_call_target="xla_python_cpu_callback"'
            )
            != []
        )
        assert (
            check_no_host_transfers(
                '  %c = stablehlo.custom_call @xla_ffi_python_cpu_callback(%x)'
            )
            != []
        )
        # ordinary compute and LAPACK custom-calls pass
        assert check_no_host_transfers("  %a = f32[4] add(%x, %y)") == []
        assert (
            check_no_host_transfers(
                '  %c = f32[] custom-call(), custom_call_target="lapack_sgetrf"'
            )
            == []
        )

    def test_collective_budget_comparator(self):
        from eventstreamgpt_tpu.parallel import compare_inventory

        budget = {
            "all-reduce": {"bytes": 100_000},
            "all-gather": {"bytes": 0},
            "total_bytes": 100_000,
        }
        ok = {"all-reduce": {"count": 1, "bytes": 110_000}, "total_bytes": 110_000}
        assert compare_inventory(ok, budget, rel_tol=0.25) == []
        # 10x blowup fails both the kind and the total
        blowup = {"all-reduce": {"count": 1, "bytes": 1_000_000}, "total_bytes": 1_000_000}
        assert len(compare_inventory(blowup, budget, rel_tol=0.25)) == 2
        # a table-sized all-gather is a NEW kind beyond slack
        new_kind = {
            "all-reduce": {"count": 1, "bytes": 100_000},
            "all-gather": {"count": 1, "bytes": 50_000_000},
            "total_bytes": 50_100_000,
        }
        problems = compare_inventory(new_kind, budget, rel_tol=0.25)
        assert any("all-gather" in p for p in problems)
        # shrinking below budget never fails while the kind stays present
        shrink = {"all-reduce": {"count": 1, "bytes": 10}, "total_bytes": 10}
        assert compare_inventory(shrink, budget, rel_tol=0.25) == []

    def test_per_kind_tolerance_override(self):
        from eventstreamgpt_tpu.parallel import compare_inventory

        budget = {
            "all-reduce": {"bytes": 100_000},
            "reduce-scatter": {"bytes": 0},
            "total_bytes": 100_000,
        }
        # +20% all-reduce growth passes the default bound but fails a
        # tightened per-kind one.
        grown = {"all-reduce": {"count": 1, "bytes": 120_000}, "total_bytes": 120_000}
        assert compare_inventory(grown, budget) == []
        problems = compare_inventory(
            grown, budget, per_kind_tol={"all-reduce": (0.05, 1024)}
        )
        assert any("all-reduce" in p for p in problems)

    def test_reduce_scatter_substitution_cannot_slip_through(self):
        """The satellite regression: a reduce-scatter → all-reduce
        substitution at equal bytes keeps the total unchanged and can hide
        inside the uniform +25%/64KiB slack of the larger all-reduce
        budget; the per-kind presence rule must catch it."""
        from eventstreamgpt_tpu.parallel import compare_inventory

        budget = {
            "all-reduce": {"count": 64, "bytes": 633_140},
            "reduce-scatter": {"count": 22, "bytes": 100_000},
            "total_bytes": 733_140,
        }
        # Seeded substitution: the reduce-scatter's bytes re-routed through
        # all-reduce; per-byte bounds all pass (633k + 100k < 633k * 1.25
        # + 64KiB and the total is unchanged).
        substituted = {
            "all-reduce": {"count": 65, "bytes": 733_140},
            "reduce-scatter": {"count": 0, "bytes": 0},
            "total_bytes": 733_140,
        }
        problems = compare_inventory(substituted, budget)
        assert any("reduce-scatter" in p and "substitution" in p for p in problems), problems
        # the honest inventory passes
        honest = {
            "all-reduce": {"count": 64, "bytes": 633_140},
            "reduce-scatter": {"count": 22, "bytes": 100_000},
            "total_bytes": 733_140,
        }
        assert compare_inventory(honest, budget) == []


# --------------------------------------------- Tier B gates on real programs
class TestLoweredProgramGates:
    """The no-f64 / no-host-transfer pins on the canonical steps (lowering
    only — fast). The host-only scope of the np.float64 preprocessing code
    (data/preprocessing/, dataset_pandas.py) is exactly what keeps these
    green: f64 lives in pandas fit statistics, never in the lowered step."""

    @pytest.fixture(scope="class")
    def pretrain_lowered(self):
        from eventstreamgpt_tpu.analysis.program_checks import canonical_pretrain_step

        fn, args = canonical_pretrain_step(8, 1)
        return fn.lower(*args).as_text()

    def test_pretrain_step_is_f64_free(self, pretrain_lowered):
        from eventstreamgpt_tpu.analysis.program_checks import check_no_f64

        assert "f64[" not in pretrain_lowered
        assert check_no_f64(pretrain_lowered, "pretrain:dp8") == []

    def test_pretrain_step_is_host_transfer_free(self, pretrain_lowered):
        from eventstreamgpt_tpu.analysis.program_checks import check_no_host_transfers

        assert check_no_host_transfers(pretrain_lowered, "pretrain:dp8") == []

    def test_finetune_step_is_f64_and_host_transfer_free(self):
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_finetune_step,
            check_no_f64,
            check_no_host_transfers,
        )

        fn, args = canonical_finetune_step(8)
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "finetune:dp8") == []
        assert check_no_host_transfers(text, "finetune:dp8") == []

    def test_na_fused_step_is_f64_and_host_transfer_free(self):
        """The r06 NA flagship program (fused dep-graph attention + narrow
        head projections): the fused walk is elementwise/reduce work and the
        narrow projections are kernel column slices — neither may introduce
        f64 constants or host callbacks into the lowered step."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_pretrain_step,
            check_no_f64,
            check_no_host_transfers,
        )

        fn, args = canonical_pretrain_step(8, 1, na=True)
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "pretrain:na_dp8") == []
        assert check_no_host_transfers(text, "pretrain:na_dp8") == []

    def test_engine_programs_are_f64_and_host_transfer_free(self):
        """The serving engine's slot-decode + bucketed-prefill programs on
        the dp8 mesh: per-row stopping is judged ON DEVICE, so the decode
        program must carry no host callbacks (a smuggled sync would
        resurrect the per-event readback continuous batching removes), and
        neither program may introduce f64."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_engine_programs,
            check_no_f64,
            check_no_host_transfers,
        )

        programs = canonical_engine_programs(8)
        assert set(programs) == {"decode", "prefill_b8", "boundary_pack"}
        for label, (fn, args) in programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"engine:{label}") == []
            assert check_no_host_transfers(text, f"engine:{label}") == []

    def test_health_sentinel_engine_shares_budgets_with_uninstrumented(self):
        """The ISSUE-15 gate, mirroring PR 3's dp8-vs-dp8_health contract on
        the serving side: the decode health sentinel (production default,
        health_sentinel=True — the `engine:*` canonical) must add ZERO
        collectives and ZERO host transfers, so the uninstrumented variant
        (`engine_nohealth:*`) is wired to the SAME committed budget keys
        and both must lower clean. The health row rides the existing packed
        boundary: (5, n_slots) instrumented vs (4, n_slots) without."""
        import inspect

        import jax

        from eventstreamgpt_tpu.analysis import program_checks as pc
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_nohealth_engine_programs,
            check_no_f64,
            check_no_host_transfers,
        )

        programs = canonical_nohealth_engine_programs(8)
        assert set(programs) == {"decode", "prefill_b8", "boundary_pack"}
        for label, (fn, args) in programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"engine_nohealth:{label}") == []
            assert check_no_host_transfers(text, f"engine_nohealth:{label}") == []
        # The uninstrumented boundary pack has no health row; Tier B holds
        # both decode programs to the SAME committed engine_dp8 budget
        # (byte-identical inventories — the zero-collective contract).
        fn, args = programs["boundary_pack"]
        assert jax.eval_shape(fn, *args).shape[0] == 4
        src = inspect.getsource(pc.run_program_checks)
        assert 'budget_keys["engine_nohealth:decode"] = "engine_dp8"' in src
        assert (
            'budget_keys["engine_nohealth:prefill_b8"] = "engine_prefill_dp8"' in src
        )

    def test_instrumented_boundary_pack_carries_the_health_row(self):
        """The production engine's packed boundary readback grew exactly one
        row (the per-slot health flags) — the sentinel's only host-visible
        surface, riding the copy the host already makes every chunk."""
        import jax

        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_engine_programs,
        )

        fn, args = canonical_engine_programs(8)["boundary_pack"]
        assert jax.eval_shape(fn, *args).shape[0] == 5

    def test_kvq_and_pallas_programs_are_f64_and_host_transfer_free(self):
        """The r09 kernel-round programs: the int8-cache engine decode on
        dp8 (quantize-on-write / dequantize-on-read must add no host
        traffic and no f64 — the scale tables are fp32 by design, not
        f64), the unsharded Pallas fused-sampling decode program, and the
        Pallas dep-graph-kernel NA pretrain step (the custom_vjp pair must
        not smuggle callbacks into fwd or bwd)."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_kvq_engine_programs,
            canonical_pretrain_step,
            canonical_sampling_engine_program,
            check_no_f64,
            check_no_host_transfers,
        )

        programs = canonical_kvq_engine_programs(8)
        assert set(programs) == {"decode", "prefill_b8", "boundary_pack"}
        for label, (fn, args) in programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"engine_kvq:{label}") == []
            assert check_no_host_transfers(text, f"engine_kvq:{label}") == []

        fn, args = canonical_sampling_engine_program()["decode"]
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "engine_sampling:decode") == []
        assert check_no_host_transfers(text, "engine_sampling:decode") == []

        fn, args = canonical_pretrain_step(8, 1, na=True, na_impl="pallas_interpret")
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "pretrain:na_pallas_dp8") == []
        assert check_no_host_transfers(text, "pretrain:na_pallas_dp8") == []

    def test_spec_programs_are_f64_and_host_transfer_free(self):
        """The r13 speculative-decoding programs: the draft-chunk and
        verify programs are the new serving hot loop — a callback smuggled
        into either would resurrect the per-event host sync, and the
        accept/residual math must not leak f64 (log-pmf ratios are fp32 by
        construction). Covers the dp8 CI set and the NA variant's
        draft/verify pair."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_spec_engine_na_programs,
            canonical_spec_engine_programs,
            check_no_f64,
            check_no_host_transfers,
        )

        programs = canonical_spec_engine_programs(8)
        assert set(programs) == {"draft_chunk", "verify", "prefill_b8", "boundary_pack"}
        for label, (fn, args) in programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"engine_spec:{label}") == []
            assert check_no_host_transfers(text, f"engine_spec:{label}") == []

        na_programs = canonical_spec_engine_na_programs()
        assert set(na_programs) == {"draft_chunk", "verify"}
        for label, (fn, args) in na_programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"engine_spec_na:{label}") == []
            assert check_no_host_transfers(text, f"engine_spec_na:{label}") == []

    def test_spec_verify_budget_has_no_new_collective_kinds(self):
        """The ISSUE-13 acceptance gate, against the COMMITTED budgets: the
        K-event verify program must show zero collective kinds beyond the
        baseline decode's (engine_dp8) scalar bookkeeping — in particular
        the fused-sampling mesh rule (auto -> XLA tail on multi-device
        meshes) must keep holding inside the verify forward, where a
        regression would all-gather the slot-sharded logits plane and show
        up as a KB-scale max_bytes."""
        import json

        from eventstreamgpt_tpu.analysis.program_checks import REPO_ROOT

        layouts = json.loads((REPO_ROOT / "COLLECTIVES.json").read_text())["layouts"]
        base = layouts["engine_dp8"]
        verify = layouts["engine_spec_verify_dp8"]
        kind_keys = [k for k in base if isinstance(base[k], dict) and "count" in base[k]]
        base_kinds = {k for k in kind_keys if base[k]["count"] > 0}
        verify_kinds = {k for k in kind_keys if verify.get(k, {}).get("count", 0) > 0}
        assert verify_kinds <= base_kinds, (
            f"verify introduced new collective kinds: {verify_kinds - base_kinds}"
        )
        # Scalar-bookkeeping class: no single collective grows past the
        # baseline's largest op (a logits-plane gather would be KBs).
        max_base = max(base[k]["max_bytes"] for k in kind_keys)
        max_verify = max(verify[k]["max_bytes"] for k in kind_keys)
        assert max_verify <= max_base, (verify, base)
        # The NA variant and the single-device programs are zero-collective.
        for key in ("engine_spec_na_draft_1dev", "engine_spec_na_verify_1dev"):
            assert layouts[key]["total_count"] == 0, layouts[key]

    def test_scan_and_fsdp_steps_are_f64_and_host_transfer_free(self):
        """The r10 scale-up programs: the scan-over-layers pretrain step on
        the dp8 mesh (one scanned block body — the stacked-param relayout
        must not smuggle f64 constants or callbacks into the loop) and the
        FSDP step (scan + parameter/optimizer sharding over an 8-way fsdp
        axis — the gather-on-use/reduce-scatter-on-grad schedule is pure
        collectives, never host traffic)."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_pretrain_step,
            check_no_f64,
            check_no_host_transfers,
        )

        fn, args = canonical_pretrain_step(8, 1, scan=True)
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "pretrain:scan_dp8") == []
        assert check_no_host_transfers(text, "pretrain:scan_dp8") == []

        fn, args = canonical_pretrain_step(1, 1, scan=True, n_fsdp=8)
        text = fn.lower(*args).as_text()
        assert check_no_f64(text, "pretrain:fsdp8") == []
        assert check_no_host_transfers(text, "pretrain:fsdp8") == []

    def test_scan_and_fsdp_budgets_are_committed(self):
        """COLLECTIVES.json carries the r10 budgets the Tier-B gate holds
        the compiled programs to: scan_dp8 (byte-identical to dp8 — the
        scan relayout adds zero communication) and fsdp8, the one layout
        whose bytes are all-gather dominated by design (sharded weights
        gathered on use; at the canonical toy shapes XLA folds the grad
        reduce-scatter into its all-reduce), with the n_params the bench
        width ladder derives its pod-scale prediction factor from."""
        import json

        from eventstreamgpt_tpu.analysis.program_checks import REPO_ROOT

        budgets = json.loads((REPO_ROOT / "COLLECTIVES.json").read_text())["layouts"]
        assert "scan_dp8" in budgets and "fsdp8" in budgets
        assert budgets["scan_dp8"]["total_bytes"] == budgets["dp8"]["total_bytes"]
        fsdp = budgets["fsdp8"]
        assert fsdp["all-gather"]["bytes"] > 0, "FSDP must gather sharded weights"
        assert fsdp["n_params"] > 0, "the width ladder needs n_params in the entry"

    def test_service_programs_are_f64_and_host_transfer_free(self):
        """The online service's dispatch programs (2-replica service over
        dp8): the async double-buffered pipeline is only host-transfer-free
        beyond the boundary fetch if decode, prefill, AND the boundary pack
        carry no callbacks — a smuggled sync in any of them re-serializes
        the overlap the service exists to create."""
        from eventstreamgpt_tpu.analysis.program_checks import (
            canonical_service_programs,
            check_no_f64,
            check_no_host_transfers,
        )

        programs = canonical_service_programs(8)
        assert set(programs) == {"decode", "prefill_b8", "boundary_pack", "decode_r1"}
        for label, (fn, args) in programs.items():
            text = fn.lower(*args).as_text()
            assert check_no_f64(text, f"service:{label}") == []
            assert check_no_host_transfers(text, f"service:{label}") == []


# ------------------------------------------------------- baseline pruning
class TestBaselinePrune:
    SRC = "import numpy as np\nx = np.zeros(3, dtype=np.float64)\n"

    def test_prune_drops_stale_and_keeps_live(self):
        from eventstreamgpt_tpu.analysis.lint import prune_baseline

        findings = lint_source(self.SRC, "mod.py")
        live_key = findings[0].key()
        baseline = {
            live_key: 1,
            ("gone.py", "GC002", "x = np.float64(1)"): 2,  # fixed long ago
            (live_key[0], live_key[1], "y = old_snippet"): 1,  # snippet drifted
        }
        pruned, stale = prune_baseline(findings, baseline)
        assert pruned == {live_key: 1}
        assert stale == 3

    def test_prune_shrinks_overcounted_entries(self):
        from eventstreamgpt_tpu.analysis.lint import prune_baseline

        findings = lint_source(self.SRC, "mod.py")
        key = findings[0].key()
        pruned, stale = prune_baseline(findings, {key: 5})
        assert pruned == {key: 1} and stale == 4

    def test_checked_in_baseline_has_no_stale_entries(self):
        """The CI `baseline --prune --check` contract, mirrored in tier-1:
        every committed suppression must still match a current finding."""
        from eventstreamgpt_tpu.analysis.lint import prune_baseline

        findings = lint_paths(default_targets(REPO_ROOT), REPO_ROOT)
        baseline = load_baseline(
            REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json"
        )
        _, stale = prune_baseline(findings, baseline)
        assert stale == 0, (
            f"{stale} stale baseline suppression(s); run "
            "`python scripts/graftcheck.py baseline --prune`"
        )

    def test_cli_prune_check_exit_codes(self, tmp_path, monkeypatch):
        from scripts import graftcheck as cli

        # A baseline with one stale entry: --check exits 1 without writing;
        # --prune rewrites and a second --check passes.
        stale_fp = tmp_path / "baseline.json"
        import json as _json

        committed = _json.loads(
            (REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json").read_text()
        )
        committed["findings"].append(
            {"path": "gone.py", "rule": "GC002", "snippet": "x = 1", "count": 1}
        )
        stale_fp.write_text(_json.dumps(committed))
        monkeypatch.setattr(cli, "BASELINE_FP", stale_fp)
        assert cli.main(["baseline", "--prune", "--check"]) == 1
        assert cli.main(["baseline", "--prune"]) == 0
        assert cli.main(["baseline", "--prune", "--check"]) == 0


# ------------------------------------------- Tier C: kind-resolved inventory
_FOLDED_RS_HLO = """\
HloModule jit_step, is_scheduled=true, num_partitions=8

%fused_slice (param_0: f32[1024,8]) -> f32[128,8] {
  %param_0 = f32[1024,8]{1,0} parameter(0)
  %pid = u32[] partition-id()
  %c = s32[] constant(128)
  ROOT %dynamic-slice.1 = f32[128,8]{1,0} dynamic-slice(f32[1024,8]{1,0} %param_0, s32[] %c, s32[] %c), dynamic_slice_sizes={128,8}
}

ENTRY %main (p0: f32[1024,8], p1: f32[64,8]) -> (f32[128,8], f32[64,8]) {
  %p0 = f32[1024,8]{1,0} parameter(0)
  %p1 = f32[64,8]{1,0} parameter(1)
  %all-reduce.7 = f32[1024,8]{1,0} all-reduce(f32[1024,8]{1,0} %p0), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
  %fusion.1 = f32[128,8]{1,0} fusion(f32[1024,8]{1,0} %all-reduce.7), kind=kLoop, calls=%fused_slice
  %all-reduce.8 = f32[64,8]{1,0} all-reduce(f32[64,8]{1,0} %p1), channel_id=2, replica_groups=[1,8]<=[8], to_apply=%add
  ROOT %tuple.1 = (f32[128,8]{1,0}, f32[64,8]{1,0}) tuple(f32[128,8]{1,0} %fusion.1, f32[64,8]{1,0} %all-reduce.8)
}
"""


class TestKindResolvedInventory:
    def test_folded_reduce_scatter_resolves_through_fusion(self):
        from eventstreamgpt_tpu.parallel import resolve_folded_reduce_scatters

        folded = resolve_folded_reduce_scatters(_FOLDED_RS_HLO)
        # all-reduce.7 (32KB payload, group 8) flows into a fusion whose body
        # dynamic-slices exactly 1/8 of it -> effective reduce-scatter of the
        # 4KB shard; all-reduce.8 is consumed whole and stays an all-reduce.
        assert folded == {"all-reduce.7": 1024 * 8 * 4 // 8}

    def test_resolved_inventory_reclassifies(self):
        from eventstreamgpt_tpu.parallel import collective_inventory

        raw = collective_inventory(_FOLDED_RS_HLO)
        assert raw["all-reduce"]["count"] == 2
        assert raw["reduce-scatter"]["count"] == 0

        resolved = collective_inventory(_FOLDED_RS_HLO, resolve_folded=True)
        assert resolved["all-reduce"]["count"] == 1
        assert resolved["all-reduce"]["bytes"] == 64 * 8 * 4
        assert resolved["reduce-scatter"]["count"] == 1
        assert resolved["reduce-scatter"]["bytes"] == 1024 * 8 * 4 // 8

    def test_whole_tensor_consumption_is_not_resolved(self):
        from eventstreamgpt_tpu.parallel import resolve_folded_reduce_scatters

        hlo = """\
HloModule jit_step, is_scheduled=true, num_partitions=8

ENTRY %main (p0: f32[64,8]) -> f32[64,8] {
  %p0 = f32[64,8]{1,0} parameter(0)
  ROOT %all-reduce.1 = f32[64,8]{1,0} all-reduce(f32[64,8]{1,0} %p0), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
}
"""
        assert resolve_folded_reduce_scatters(hlo) == {}


# ------------------------------------------------- Tier C: memory checks
class TestMemoryChecks:
    def test_peak_formula(self):
        from eventstreamgpt_tpu.analysis.memory_checks import peak_hbm_bytes

        class Stats:
            argument_size_in_bytes = 1000
            output_size_in_bytes = 600
            alias_size_in_bytes = 500
            temp_size_in_bytes = 300
            generated_code_size_in_bytes = 7

        assert peak_hbm_bytes(Stats()) == 1000 + 600 - 500 + 300 + 7

    def test_compare_memory_bounds(self):
        from eventstreamgpt_tpu.analysis.memory_checks import compare_memory

        budget = {"peak_hbm_bytes": 100 << 20}
        assert compare_memory({"peak_hbm_bytes": 100 << 20}, budget) == []
        # within +10% + 1MiB
        assert compare_memory({"peak_hbm_bytes": int(105e6)}, budget) == []
        assert compare_memory({"peak_hbm_bytes": 200 << 20}, budget) != []
        # shrinking never fails
        assert compare_memory({"peak_hbm_bytes": 1}, budget) == []

    def test_hbm_fit_expectations(self):
        from eventstreamgpt_tpu.analysis.memory_checks import check_hbm_fit

        fits = {"peak_hbm_bytes": int(5e9)}
        ooms = {"peak_hbm_bytes": int(39e9)}
        assert check_hbm_fit(fits, 16.0, True, "x") == []
        assert check_hbm_fit(ooms, 16.0, False, "x") == []
        assert check_hbm_fit(ooms, 16.0, True, "x") != []
        # the negative control: a layout expected to OOM that "fits" is an
        # analyzer failure, not good news
        assert check_hbm_fit(fits, 16.0, False, "x") != []

    def test_donation_and_resharding_on_real_program(self):
        """One real compiled program end to end: a donated sharded update
        must report full aliasing and no implicit resharding; dropping the
        donation must surface every donated leaf."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from eventstreamgpt_tpu.analysis.memory_checks import (
            donation_report,
            resharding_report,
        )
        from eventstreamgpt_tpu.training.sharding import make_mesh

        mesh = make_mesh(8, 1)
        x = jax.device_put(
            jnp.ones((16, 4)), NamedSharding(mesh, P("data", None))
        )
        y = jax.device_put(jnp.ones((16, 4)), NamedSharding(mesh, P("data", None)))

        donated = jax.jit(lambda a, b: a + b, donate_argnums=(0,)).lower(x, y).compile()
        rep = donation_report(donated, (x, y), (0,))
        assert rep["n_donated"] == 1 and rep["n_aliased"] == 1
        assert rep["undonated"] == []
        assert resharding_report(donated, (x, y)) == []

        undonated = jax.jit(lambda a, b: a + b).lower(x, y).compile()
        rep = donation_report(undonated, (x, y), (0,))
        assert rep["n_aliased"] == 0 and len(rep["undonated"]) == 1


# --------------------------------------------- Tier C: census completeness
class TestCensusCompleteness:
    """No orphan compiled programs: every label any `aot_programs` surface
    can produce must be covered by a Tier B or Tier C gate. A new engine
    bucket, service replica, or training layout that ships without a
    registered census entry fails here, not in a post-mortem."""

    @pytest.fixture(scope="class")
    def programs(self):
        """Every registered provider's programs, built once for the class."""
        from eventstreamgpt_tpu.analysis import program_census as census

        return census.census_programs()

    def test_every_aot_program_is_gated(self, programs):
        from eventstreamgpt_tpu.analysis import program_census as census

        surface = census.aot_surface()
        surface_labels = set().union(*surface.values())
        census_labels = set(programs)
        orphans = surface_labels - census_labels
        assert not orphans, f"aot programs with no census gate: {sorted(orphans)}"
        # and the registry carries nothing the surfaces cannot produce
        phantoms = census_labels - surface_labels
        assert not phantoms, f"census entries with no aot surface: {sorted(phantoms)}"

    def test_every_provider_registers(self):
        from eventstreamgpt_tpu.analysis import program_census as census

        providers = census.registered_providers()
        assert set(providers) == {
            "training",
            "generation",
            "engine",
            "service",
            "fleet",
            "ladder",
        }

    def test_tier_b_budget_keys_exist_in_collectives(self, programs):
        import json as _json

        layouts = _json.loads((REPO_ROOT / "COLLECTIVES.json").read_text())["layouts"]
        for label, prog in programs.items():
            if prog.budget_key is not None:
                assert prog.budget_key in layouts, (
                    f"{label} names missing COLLECTIVES.json budget {prog.budget_key}"
                )


# ------------------------------------------- Tier C: committed MEMORY.json
class TestCommittedMemoryBudgets:
    """The committed artifact mirrors the acceptance contract: the
    width-4096 replicated rung must FAIL the 16 GB budget, the fsdp8 rungs
    must fit, the scaled fsdp8 inventories must show reduce-scatter, and
    every donated program must be fully aliased."""

    @pytest.fixture(scope="class")
    def artifact(self):
        import json as _json

        return _json.loads((REPO_ROOT / "MEMORY.json").read_text())

    def test_schema_and_coverage(self, artifact):
        assert artifact["n_devices"] == 8
        assert artifact["hbm_budget_gb"] == 16.0
        programs = artifact["programs"]
        for label in (
            "pretrain:dp8",
            "pretrain:dp4_tp2",
            "pretrain:fsdp8",
            "finetune:dp8",
            "generation:ci",
            "engine:decode",
            "engine_kvq:decode",
            "engine_sampling:decode",
            "engine_spec:draft_chunk",
            "engine_spec:verify",
            "engine_spec_na:draft_chunk",
            "engine_spec_na:verify",
            "service:decode",
            "service:decode_r1",
            "ladder:fsdp8@w2048",
            "ladder:fsdp8@w4096",
            "ladder:replicated_dp8@w4096",
        ):
            assert label in programs, f"missing committed memory budget for {label}"
            assert programs[label]["peak_hbm_bytes"] > 0

    def test_width4096_replicated_fails_and_fsdp_fits_the_chip(self, artifact):
        budget = int(artifact["hbm_budget_gb"] * 1e9)
        programs = artifact["programs"]
        assert programs["ladder:replicated_dp8@w4096"]["peak_hbm_bytes"] > budget
        assert programs["ladder:replicated_dp8@w4096"]["hbm_expect"] == "oom"
        for label in ("ladder:fsdp8@w2048", "ladder:fsdp8@w4096"):
            assert programs[label]["peak_hbm_bytes"] <= budget
            assert programs[label]["hbm_expect"] == "fit"

    def test_scaled_fsdp_shows_reduce_scatter(self, artifact):
        for label in ("ladder:fsdp8@w2048", "ladder:fsdp8@w4096"):
            inv = artifact["programs"][label]["collectives"]
            assert inv["reduce-scatter"]["count"] > 0, (
                f"{label}: the committed kind-resolved inventory must show the "
                "FSDP gradient sweep as reduce-scatter"
            )
            assert inv["reduce-scatter"]["bytes"] > 0

    def test_donated_programs_are_fully_aliased(self, artifact):
        # jit-pruned donated leaves hold no buffer and are exempt: the clean
        # contract is n_donated == n_aliased + n_pruned.
        for label, entry in artifact["programs"].items():
            if "n_donated" in entry:
                accounted = entry["n_aliased"] + entry.get("n_pruned", 0)
                assert accounted == entry["n_donated"], (
                    f"{label}: {entry['n_donated'] - accounted} donated "
                    "buffer(s) not aliased in the committed census"
                )


# ----------------------------------------------- Tier A: online-ingest path
class TestIngestPathGate:
    """The r11 online-admission path is host-side BY DESIGN: the transform
    must stay off the traced hot path (no jax in the module, no host syncs
    reachable from traced scopes) and contribute ZERO new baseline entries —
    the whole point of admitting raw streams through the frozen batch
    preprocessors is that the engine never sees untraced host work."""

    INGEST_FILES = (
        "eventstreamgpt_tpu/serving/ingest.py",
        "eventstreamgpt_tpu/data/dataset_base.py",
        "eventstreamgpt_tpu/data/dataset_pandas.py",
    )

    def test_ingest_path_lints_clean_with_zero_baseline_entries(self):
        baseline = load_baseline(
            REPO_ROOT / "eventstreamgpt_tpu" / "analysis" / "baseline.json"
        )
        for rel in self.INGEST_FILES:
            findings = lint_paths([REPO_ROOT / rel], REPO_ROOT)
            new, _ = apply_baseline(findings, baseline)
            assert new == [], f"{rel} lint findings:\n" + "\n".join(
                f.render() for f in new
            )
            assert not any(k[0] == rel for k in baseline), (
                f"{rel} must carry zero suppressed baseline entries — the "
                "ingest path is new code, not legacy"
            )

    def test_ingest_module_never_imports_jax(self):
        src = (REPO_ROOT / "eventstreamgpt_tpu" / "serving" / "ingest.py").read_text()
        import ast as _ast

        for node in _ast.walk(_ast.parse(src)):
            names = []
            if isinstance(node, _ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, _ast.ImportFrom) and node.module:
                names = [node.module]
            for name in names:
                assert not name.split(".")[0] in ("jax", "jaxlib"), (
                    "the online-admission transform must stay host-side; "
                    f"found import {name!r} in serving/ingest.py"
                )
