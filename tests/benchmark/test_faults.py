"""`correct` has to come out false when the timed path is broken underneath,
and when the lower-precision control stands in the program's place. Each test
drives the rest of a run (tiny size, CPU) with one fault planted."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.harness import loader

from .tiny import WORKLOADS, run_tiny, tiny_cell

TRAIN_CELLS = [w for w in WORKLOADS if loader.load_cell(w)["job"] == "pretrain"]


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")


def _job(cell):
    return loader.load_job(cell)


def _judged(name):
    """The tiny cell with limits to be judged by: its own, or, for a cell
    that is not listed yet and has none (the NA cell), the packed cell's."""
    cell = tiny_cell(name)
    if all(limit is None for limit in cell["check"]["limits"].values()):
        cell["check"]["limits"] = loader.load_cell("ci_w1024.pretrain_packed")["check"]["limits"]
    return cell


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(name, tmp_path, monkeypatch):
    cell = _judged(name)
    job = _job(cell)
    real = job.Program.dispatch

    def frozen(self, plans):
        keep = self.state
        import jax

        self.state = jax.tree_util.tree_map(lambda a: a.copy(), keep)  # the step donates its input
        losses = real(self, plans)
        self.state = keep
        return losses

    monkeypatch.setattr(job.Program, "dispatch", frozen)
    monkeypatch.setattr(loader, "load_job", lambda c, root=None: job)
    record = run_tiny(cell, tmp_path)
    assert record["correct"] is False
    assert record["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert record["compared"]["param_change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_half_of_the_batch_left_out_is_not_correct(name, tmp_path, monkeypatch):
    cell = _judged(name)
    job = _job(cell)
    real = job.Program.dispatch
    half = cell["feed"]["batch_size"] // 2

    def halved(self, plans):
        plans = {k: np.array(v) for k, v in plans.items()}
        # rows past the first half hold no event: the loss is the mean over the rest
        mask = "event_mask" if "event_mask" in plans else "valid_mask"
        plans[mask][:, half:] = False
        return real(self, plans)

    monkeypatch.setattr(job.Program, "dispatch", halved)
    monkeypatch.setattr(loader, "load_job", lambda c, root=None: job)
    record = run_tiny(cell, tmp_path)
    assert record["correct"] is False
    limit = record["compared"]["grad_norm_gap"]["limit"]
    assert record["compared"]["grad_norm_gap"]["value"] > limit


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_the_fp8_control_is_not_correct(name):
    """The reference in the program's place, computed in fp8, against the
    same in bfloat16 (the precision the configurations state). On the chip at
    the cell's size the control reads 0.60-0.65 on ``grad_diff_gap`` against
    the program's 0.08-0.09 and the limit 0.2 (PERF.md, PR 25); at a width a
    test can hold both read lower, so the test holds what the limit rests on:
    the control reads at least three times the stated precision, and with the
    limit between the two readings the control comes out not correct while
    the stated precision comes out correct."""
    from benchmark.harness import cohort as cohort_lib

    cell = tiny_cell(name)
    cell["model"]["config"]["num_hidden_layers"] = 12  # the real depth: rounding adds up over it
    job, reference = _job(cell), loader.load_reference(cell)
    seed = 78
    cohort = cohort_lib.make_cohort(cell["cohort"], seed)
    sizes = job.reference_model(cell, cohort)
    plans = _first_plans(cell, cohort, seed)
    ref = job.follow(cell, cohort, reference, sizes, plans, seed)

    def reading(quant):
        got = job.follow(cell, cohort, reference, sizes, plans, seed, quant=quant)
        return {"losses": got[0], "delta": got[1], "mu": got[2], "mu_tensors": got[3]}

    stated, control = reading(reference.bf16_operand), reading(reference.fp8_operand)
    lower = job.compare(cell, stated, *ref)["numbers"]["grad_diff_gap"]["value"]
    upper = job.compare(cell, control, *ref)["numbers"]["grad_diff_gap"]["value"]
    assert upper >= 3 * lower
    cell["check"]["limits"] = {"loss_gap": None, "grad_norm_gap": None, "param_change_gap": None,
                               "grad_diff_gap": (lower * upper) ** 0.5}
    assert job.compare(cell, stated, *ref)["ok"] is True
    assert job.compare(cell, control, *ref)["ok"] is False


def _first_plans(cell, cohort, seed):
    """A dispatch of plans made by the benchmark itself (no program needed):
    rows of whole histories in seeded order."""
    rng = np.random.default_rng(seed)
    feed = cell["feed"]
    k, B = feed["steps_per_dispatch"], feed["batch_size"]
    order = rng.permutation(len(cohort.offsets) - 1)[: k * B].reshape(k, B)
    if not feed["packed"]:
        return {
            "subject_indices": order,
            "starts": np.zeros_like(order),
            "valid_mask": np.ones_like(order, bool),
        }
    L = feed["seq_len"]
    ids = np.zeros((k, B, L), np.int64)
    mask = np.zeros((k, B, L), bool)
    for i in range(k):
        for b in range(B):
            lo, hi = cohort.offsets[order[i, b]], cohort.offsets[order[i, b] + 1]
            n = min(hi - lo, L)
            ids[i, b, :n] = np.arange(lo, lo + n)
            mask[i, b, :n] = True
    return {"event_ids": ids, "event_mask": mask}
